"""Roofline share of the paged FairKV decode kernel over the traced
stretch: the least time the chip could take for the kernel's work — the
larger of its bytes (K and V of every valid block, from each decoded row's
live per-head lengths, ``counts.paged_decode_bytes``) over peak HBM
bandwidth and its FLOPs over peak bf16 rate — divided by the kernel's
device time in the trace (%)."""
import counts

# the kernel in the device trace, by the operand list of its Pallas call
# (``tpu_custom_call``): block tables (slots, rows, blocks) and lengths
# (slots, rows) first, then K and V pools of one shape (blocks, block size,
# head_dim) and the pool's positions (blocks, 1, block size).  The other
# Pallas call on the serving path, HeadKV's SnapKV scoring at admission,
# also takes positions as (rows, 1, tokens) but none of the rest.
KERNEL = (r'custom_call_target="tpu_custom_call", operand_layout_constraints='
          r'\{s32\[\d+,\d+,\d+\]\{[^}]*\}, s32\[\d+,\d+\]\{[^}]*\}, .*'
          r'\w+\[(\d+),(\d+),(\d+)\]\{[^}]*\}, \w+\[\1,\2,\3\]\{[^}]*\}, '
          r's32\[\1,1,\2\]\{[^}]*\}\}')


def read(ctx):
    red = ctx.get("trace")
    if red is None:
        return None
    ns = red["kernel_ns"].get("paged_decode_roofline", 0.0)
    if ns <= 0:
        return None
    m, comp = ctx["model"], ctx["cell"].compression
    eng = ctx["cell"].config["engine"]
    bs = int(eng["paging"]["block_size"])
    item = {"bfloat16": 2, "float16": 2, "float32": 4}[eng["dtype"]]
    cap = counts.static_capacity(comp)
    G = m["n_heads"] // m["n_kv_heads"]
    keep = {}
    nbytes = flops = 0.0
    for _, decoded in ctx["trace_ticks"]:
        for idx, appended in decoded:
            T = len(ctx["reqs"][idx].prompt)
            if T not in keep:
                keep[T] = counts.headkv_keep(ctx["imp"], comp, T)
            lens = counts.live_lengths(keep[T], appended, cap)
            nbytes += counts.paged_decode_bytes(lens, bs, m["head_dim"], item)
            flops += 4.0 * G * m["head_dim"] * float(lens.sum())
    pk = ctx["peaks"]
    t_min = max(nbytes / pk["hbm_bytes_per_s"], flops / pk["bf16_flops"])
    return 100.0 * t_min / (ns / 1e9)
