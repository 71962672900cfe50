"""Share of the traced training window, in %, in which the first device
runs the SSD backward: scope ``ssd_bwd``, the recompute through the jnp
oracle that the Pallas kernel's custom VJP runs.  Time is charged to
scopes as ``chipbench.scopes`` says.  Silent where the program has no
scopes."""
from chipbench import scopes

SCOPES = ("ssd_bwd",)


def read(ctx, win, trace):
    return scopes.share(ctx, trace, SCOPES)
