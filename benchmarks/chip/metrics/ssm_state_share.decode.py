"""Share of the traced decode window, in %, in which the first device
runs the Mamba-2 recurrent state update and its readout: scope
``ssm_state`` (dA * state + dt * x B, then C * state), inside scope
``ssm``.  Time is charged to scopes as ``chipbench.scopes`` says.  Silent
where no instruction of the step carries the scope."""
from chipbench import scope_extra

SCOPE = "ssm_state"


def read(ctx, win, trace):
    return scope_extra.share(ctx, trace, SCOPE)
