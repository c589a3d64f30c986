"""Model FLOPs utilization of decode over the traced stretch of the window:
the model FLOPs of every token decoded there (weights plus attention over
each head's retained cache, ``counts.decode_flops``) over the stretch's
host-clock length times the chips' bf16 peak (%)."""
import counts


def read(ctx):
    ticks = ctx.get("trace_ticks")
    if not ticks:
        return None
    flops = sum(counts.decode_flops(ctx["model"], lens)
                for lens in counts.decoded_lengths(
                    ticks, ctx["reqs"], ctx["imp"], ctx["cell"].compression))
    if flops == 0:
        return None
    peak = ctx["peaks"]["bf16_flops"] * ctx["chips"]
    return 100.0 * flops / (ctx["trace_host_s"] * peak)
