"""The Pallas flash-attention kernel's share of its roofline, in %: events
of the kernel in the trace times the least time one forward call needs at
the cell's shapes on one chip (causal: each query attends to the keys up
to its own; compute-bound at training lengths), over the kernel events'
summed device time.  The backward, recomputed through the jnp oracle, is
not the kernel.  Silent where the kernel does not run."""
from chipbench import trace as tr
from chipbench.flops import Cost

# flash attention forward: q, k, v [b,h,s,dh] in bf16 and nothing else in,
# o [b,h,s,dh] out (the decode kernel also takes an int32 mask)
SIGNATURE = (r"= bf16\[\d+,\d+,\d+,\d+\]\{[^}]*\} custom-call\("
             r"bf16\[\d+,\d+,\d+,\d+\]\{[^}]*\} %[\w.-]+, "
             r"bf16\[\d+,\d+,\d+,\d+\]\{[^}]*\} %[\w.-]+, "
             r"bf16\[\d+,\d+,\d+,\d+\]\{[^}]*\} %[\w.-]+\)")


def causal_call(b: int, h: int, kv: int, s: int, dh: int,
                itemsize: int = 2) -> Cost:
    """One causal flash-attention forward: q and o [b,h,s,dh], K and V
    [b,kv,s,dh] read once; s(s+1)/2 query-key pairs per head, each a
    score and a weighted value (two multiply-adds per dh)."""
    flops = 4 * b * h * dh * s * (s + 1) / 2
    nbytes = itemsize * (2 * b * h * s * dh + 2 * b * kv * s * dh)
    return Cost(flops, nbytes)


def read(ctx, win, trace):
    if trace is None:
        return None
    m, mix = ctx.model, ctx.mix
    dh = m.get("head_dim") or m["d_model"] // m["n_heads"]
    data = int(mix["mesh"].split("x")[0])
    call = causal_call(mix["batch"] // data, m["n_heads"], m["n_kv_heads"],
                       mix["seq"], dh)
    events, seconds = 0, 0.0
    for n, sec in tr.pallas_kernel(trace, SIGNATURE).values():
        events, seconds = events + n, seconds + sec
    if not events:
        return None
    return 100.0 * events * call.seconds(ctx.peak) / seconds
