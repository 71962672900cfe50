"""Device idle share of the traced training steps, in %: 1 - (union of
the intervals in which an operation runs) / traced window, on the device
that idles most."""
from chipbench import trace as tr


def read(ctx, win, trace):
    if trace is None or not trace.devices:
        return None
    busy = tr.busy_seconds(trace)
    return 100.0 * (1.0 - min(busy.values()) / trace.window_s)
