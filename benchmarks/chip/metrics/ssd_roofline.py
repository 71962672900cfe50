"""The SSD scan kernel's share of its roofline, in %: events of the
kernel in the trace times the least time one forward call needs at the
cell's shapes (memory-bound on a v5e: bytes over HBM bandwidth), over
the kernel events' summed device time.  Silent where the kernel does not
run."""
from chipbench import flops
from chipbench import trace as tr

# the SSD kernel: the only Pallas call that returns two float32 arrays,
# y [b,h,s,p] and the final state [b,h,p,n]
SIGNATURE = (r"= \(f32\[\d+,\d+,\d+,\d+\]\{[^}]*\}, "
             r"f32\[\d+,\d+,\d+,\d+\]\{[^}]*\}\) custom-call\(")


def read(ctx, win, trace):
    if trace is None:
        return None
    m, s = ctx.model, ctx.model["ssm"]
    d_inner = s["expand"] * m["d_model"]
    data = int(ctx.mix["mesh"].split("x")[0])
    call = flops.ssd_forward(ctx.mix["batch"] // data, ctx.mix["seq"],
                             d_inner // s["head_dim"], s["head_dim"],
                             s["n_groups"], s["state_dim"], s["chunk_size"])
    events, seconds = 0, 0.0
    for n, sec in tr.pallas_kernel(trace, SIGNATURE).values():
        events, seconds = events + n, seconds + sec
    if not events:
        return None
    return 100.0 * events * call.seconds(ctx.peak) / seconds
