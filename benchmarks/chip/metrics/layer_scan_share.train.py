"""Share of the traced training window, in %, in which the first device
runs the layer scan's own work: scope ``layer_scan``, slicing each
layer's parameters out of the stacked ones and stacking results and
gradients back, outside the scan body (scope ``layer``).  Time is charged
to scopes as ``chipbench.scopes`` says.  Silent where the program has no
scopes."""
from chipbench import scopes

SCOPES = ("layer_scan",)


def read(ctx, win, trace):
    return scopes.share(ctx, trace, SCOPES)
