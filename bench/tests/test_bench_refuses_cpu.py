"""The measurement path refuses a CPU: no TPU, non-zero exit, no result."""
import json

import run


def test_run_without_a_tpu_exits_nonzero_with_no_result(capsys):
    rc = run.main(["--workload", "qwen1.5-110b.prefill-mix", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert "no TPU" in err
    for line in out.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert "metrics" not in obj


def test_too_few_chips_is_refused():
    import pytest
    with pytest.raises(run.NoChip):
        run.devices_for(4, require_chip=False)
