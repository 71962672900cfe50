"""The flash-decode kernel's share of its roofline, in %: events of the
kernel in the trace times the least time one call needs at the cell's
shapes, its bytes counting only the cache positions valid in the traced
steps (memory-bound), over the kernel events' summed device time.  A
kernel that skips masked blocks gets the credit.  Silent where the kernel
does not run."""
from chipbench import flops
from chipbench import trace as tr

# flash-decode: q, K and V in bf16, then the int32 validity mask [b,1,c]
SIGNATURE = (r"custom-call\((bf16\[[\d,]+\]\{[^}]*\} %[\w.-]+, ){3}"
             r"s32\[\d+,1,\d+\]")


def read(ctx, win, trace):
    if trace is None or win.get("traced_valid_mean") is None:
        return None
    m = ctx.model
    dh = m.get("head_dim") or m["d_model"] // m["n_heads"]
    call = flops.decode_attention(win["batch"], m["n_heads"],
                                  m["n_kv_heads"], dh,
                                  win["traced_valid_mean"])
    events, seconds = 0, 0.0
    for n, sec in tr.pallas_kernel(trace, SIGNATURE).values():
        events, seconds = events + n, seconds + sec
    if not events:
        return None
    return 100.0 * events * call.seconds(ctx.peak) / seconds
