"""Share of the traced decode window, in %, in which the first device
runs the mixture of experts: scopes ``moe_route`` (router, top-k, slot
positions), ``moe_dispatch``, ``moe_experts`` and ``moe_combine``.  Time
is charged to scopes as ``chipbench.scopes`` says.  Silent where the
program has no scopes."""
from chipbench import scopes

SCOPES = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine")


def read(ctx, win, trace):
    return scopes.share(ctx, trace, SCOPES)
