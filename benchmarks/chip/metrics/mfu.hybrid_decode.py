"""A hybrid model's decode step's share of the chip's roofline, in %: the
least time one step needs (the larger of its required FLOPs over peak
FLOP/s and its required bytes over peak HBM bandwidth, both counted per
layer kind by ``chipbench.hybrid_flops``: the held experts only, the
shared expert, the Mamba state read and written, the valid cache), over
the mean time of the window's steps that the profiler did not watch.  It
carries the ``mfu`` name as the whole step's share of the chip's peak.
Nothing when every step of the window was traced."""
from chipbench import hybrid_flops


def read(ctx, win, trace):
    if win.get("untraced_step_s_mean") is None:
        return None
    cost = hybrid_flops.decode_step(ctx.model, win["batch"],
                                    win["untraced_valid_mean"])
    return 100.0 * cost.seconds(ctx.peak) / win["untraced_step_s_mean"]
