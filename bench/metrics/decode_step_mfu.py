"""Model FLOPs utilization of the decode step over the window: the model
FLOPs of every token decoded there (weights plus attention over each
head's retained cache, ``counts.decode_flops``) over the decode StepFn's
wall time there (``stepfn_wall_s{kind="decode"}``) times the chips' bf16
peak (%).  Unlike ``decode_mfu`` it leaves out the time between decode
steps, so in an open loop below its knee, where the tokens decoded are the
offered load, it moves with the step and not with the load."""
import counts


def read(ctx):
    s, n = ctx["stepfn"].get("decode", (0.0, 0))
    if not n:
        return None
    flops = sum(counts.decode_flops(ctx["model"], lens)
                for lens in counts.decoded_lengths(
                    ctx["ticks"], ctx["reqs"], ctx["imp"],
                    ctx["cell"].compression))
    if flops == 0:
        return None
    return 100.0 * flops / (s * ctx["peaks"]["bf16_flops"] * ctx["chips"])
