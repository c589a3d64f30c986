"""Record the small device trace the trace-reduction test reads.

    python bench/testdata/record_trace.py <out_dir>

On one TPU: a bf16 matmul, the program's paged FairKV decode kernel on a
small pool, then host-side sleeps between them, all inside a
``bench.traced`` annotation.  Writes the ``.xplane.pb`` as
``<out_dir>/v5e_small.xplane.pb`` and prints every device operation and
host annotation of the traced window, for counting by hand.
"""
from __future__ import annotations

import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import trace_reduce
    from repro.kernels import ops as K
    if jax.devices()[0].platform != "tpu":
        print("record_trace.py: needs a TPU", file=sys.stderr)
        return 2
    S, B, G, Dh, bs, M, N = 4, 8, 4, 64, 128, 4, 64
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, S, G, Dh)), jnp.bfloat16)
    kp = jnp.asarray(rng.normal(size=(N, bs, Dh)), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=(N, bs, Dh)), jnp.bfloat16)
    pos = jnp.asarray(np.tile(np.arange(bs), (N, 1)), jnp.int32)
    table = jnp.asarray(rng.integers(1, N, size=(S, B, M)), jnp.int32)
    lens = jnp.asarray(rng.integers(1, M * bs, size=(S, B)), jnp.int32)
    kern = jax.jit(lambda *a: K.paged_fairkv_decode(
        *a, M * bs, impl="pallas"))
    x = jnp.asarray(rng.normal(size=(2048, 2048)), jnp.bfloat16)
    mm = jax.jit(lambda a: a @ a)
    jax.block_until_ready((kern(q, kp, vp, pos, table, lens), mm(x)))
    log_dir = Path(out_dir) / "trace_run"
    shutil.rmtree(log_dir, ignore_errors=True)
    po = jax.profiler.ProfileOptions()
    po.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=po)
    with jax.profiler.TraceAnnotation("bench.traced"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                jax.block_until_ready(mm(x))
                jax.block_until_ready(kern(q, kp, vp, pos, table, lens))
            with jax.profiler.TraceAnnotation("bench.readout"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    src = trace_reduce.find_xplane(log_dir)
    dst = Path(out_dir) / "v5e_small.xplane.pb"
    shutil.copy(src, dst)
    tr = trace_reduce.load(dst)
    w0, w1 = trace_reduce.window_of(tr, "bench.traced")
    print(f"window {w0} {w1}")
    for plane, ops in tr["devices"].items():
        for s, e, n in sorted(ops):
            if e > w0 and s < w1:
                print(f"op {plane} {s} {e} {n}")
    for s, e, n in sorted(tr["host"]):
        if n.startswith("bench."):
            print(f"host {s} {e} {n}")
    print(f"bytes {dst.stat().st_size}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
