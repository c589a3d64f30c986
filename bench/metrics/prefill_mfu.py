"""Model FLOPs utilization of admission prefill: the model FLOPs of the
prompts prefilled in the window (``counts.prefill_flops``) over the
prefill StepFn's wall time there times the chips' bf16 peak (%)."""
import counts


def read(ctx):
    s, n = ctx["stepfn"].get("prefill", (0.0, 0))
    prompts = ctx["admitted_prompts"]
    if not n or not prompts:
        return None
    flops = sum(counts.prefill_flops(ctx["model"], T) for T in prompts)
    return 100.0 * flops / (s * ctx["peaks"]["bf16_flops"] * ctx["chips"])
