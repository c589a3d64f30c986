"""Model FLOPs utilization of decode over the traced stretch of the window:
the model FLOPs of every token decoded there (weights plus attention over
each head's retained cache, ``counts.decode_flops``) over the stretch's
host-clock length times the chips' bf16 peak (%)."""
import counts


def read(ctx):
    ticks = ctx.get("trace_ticks")
    if not ticks:
        return None
    m, comp = ctx["model"], ctx["cell"].compression
    cap = counts.static_capacity(comp)
    keep = {}
    flops = 0.0
    for _, decoded in ticks:
        for idx, appended in decoded:
            T = len(ctx["reqs"][idx].prompt)
            if T not in keep:
                keep[T] = counts.headkv_keep(ctx["imp"], comp, T)
            flops += counts.decode_flops(
                m, counts.live_lengths(keep[T], appended, cap))
    if flops == 0:
        return None
    peak = ctx["peaks"]["bf16_flops"] * ctx["chips"]
    return 100.0 * flops / (ctx["trace_host_s"] * peak)
