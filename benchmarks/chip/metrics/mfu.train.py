"""Model FLOP/s utilization of the training job, in %: the forward and
backward FLOPs each token requires (the benchmark's own formula,
recomputation not counted) times the tokens per second of the window's
steps that the profiler did not watch, over the chips' summed bf16 peak.
Nothing when every step of the window was traced."""
from chipbench import flops


def read(ctx, win, trace):
    rate = win.get("untraced_tokens_per_s")
    if rate is None:
        return None
    per_token = flops.train_flops_per_token(ctx.model, ctx.mix["seq"])
    return 100.0 * per_token * rate / (ctx.chips * ctx.peak.flops)
