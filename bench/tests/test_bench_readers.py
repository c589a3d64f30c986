"""The end-to-end readers of inter-token latency on a hand-made token log,
and ``BENCHMARK.json`` against the readers under ``bench/metrics/``."""
import json

import pytest

import cells
import run
import stats

BENCH = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
END_TO_END = {m["name"]: m for m in BENCH["end_to_end"]}
ALL_CELLS = [w["name"] for w in BENCH["workloads"]]


def _read(name, ctx):
    return run._reader(name).read(ctx)


def _itl_ctx():
    """A window (1, 3] with five gaps ending in it: 0.2, 0.3, 0.0 (an
    admission tick stamps its prefill's token and its decode's at the same
    time), 1.5 (ending at t1, in) and 0.1; one gap ends at t0 (out) and
    one after t1 (out)."""
    lg = stats.TokenLog()
    lg.add(0, 1, 0.5)
    lg.add(0, 1, 1.0)  # its gap ends at t0
    lg.add(0, 1, 1.2)
    lg.add(0, 2, 1.5)
    lg.add(0, 1, 3.0)  # its gap ends at t1
    lg.add(1, 1, 2.0)
    lg.add(1, 1, 2.1)
    lg.add(1, 1, 3.5)  # after the window
    return {"t0": 1.0, "t1": 3.0, "log": lg}


def test_itl_gaps_end_in_the_window_and_count_same_time_stamps():
    ctx = _itl_ctx()
    gaps = sorted(ctx["log"].gaps_in(ctx["t0"], ctx["t1"]))
    assert gaps == pytest.approx([0.0, 0.1, 0.2, 0.3, 1.5])


@pytest.mark.parametrize("name, want", [
    ("itl_p50_ms", 200.0),  # the middle of five
    ("itl_p99.2_ms", 300.0 + 0.968 * 1200.0),  # 96.8% from 0.3 to 1.5
])
def test_itl_readers_by_hand(name, want):
    assert _read(name, _itl_ctx()) == pytest.approx(want)


@pytest.mark.parametrize("name", ["itl_p50_ms", "itl_p99.2_ms"])
def test_itl_readers_refuse_a_window_without_gaps(name):
    lg = stats.TokenLog()
    lg.add(0, 1, 2.0)  # one token: no gap
    with pytest.raises(ValueError):
        _read(name, {"t0": 1.0, "t1": 3.0, "log": lg})


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_moves_names_an_end_to_end_metric_its_cells_report(metric):
    e2e = END_TO_END.get(metric["moves"])
    assert e2e is not None, f"{metric['name']} moves {metric['moves']}"
    mine = set(metric.get("workloads", ALL_CELLS))
    assert mine <= set(e2e.get("workloads", ALL_CELLS)), metric["name"]


@pytest.mark.parametrize("name", sorted(
    [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]))
def test_every_metric_has_a_reader(name):
    assert (cells.BENCH / "metrics" / f"{name}.py").is_file()
    assert callable(run._reader(name).read)
