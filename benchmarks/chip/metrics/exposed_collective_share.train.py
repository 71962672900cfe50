"""Share of the traced training window, in %, on the device where it is
largest, in which an op of a ring scope (``ring_gather``, ``ring_scatter``,
``ring_all_reduce``, ``ring_all_to_all``: the photonic rings' permutes)
runs and no op of any other scope, nor an unscoped one, does: the rails'
time the step does not hide.  Silent where no ring op runs (one chip, or
a program without scopes)."""
from chipbench import scope_extra


def read(ctx, win, trace):
    return scope_extra.exposed_share(ctx, trace, "ring_")
