"""Model step: FLOPs the model needs for the tokens of the traced slice
(stats.model_flops) over the slice's seconds at the bf16 peak, %."""
from perfbench import stats


def read(ctx):
    steps, slice_s = ctx["slice_steps"], ctx["slice_s"]
    if not steps or not slice_s:
        return None
    tokens = sum(s["decode_rows"] + s["prefill_tokens"] for s in steps)
    if not tokens:
        return None
    flops = stats.model_flops(
        ctx["D"], tokens,
        sum(s["decode_ctx"] + s["prefill_ctx"] for s in steps),
        sum(s["head_rows"] for s in steps))
    return 100.0 * flops / (slice_s * ctx["peaks"]["bf16_flops_per_s"])
