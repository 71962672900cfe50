"""The host's and the device's clocks of a trace, tied by the runtime's ids.

Each program run is one event on a device's ``XLA Modules`` line, whose
flow id (stat ``_c``) the host's runtime gives twice: as ``_p`` of the
``DoEnqueueProgram`` that queued the run and as ``_c`` of the
``CompleteCallbacks`` that saw it end.  The device cannot start a run
before the host queued it, and the host cannot see it end before the
device ended it, so every run bounds the offset (host time minus device
time) from both sides; ``host_offset`` intersects those bounds.

``chipbench.trace`` aligns the clocks with a heuristic of its own, which
the accepted readers keep; the functions here read the raw trace file.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Tuple

from chipbench import trace as tr

ENQUEUE = "DoEnqueueProgram"
COMPLETE = "CompleteCallbacks"
MODULES_LINE = "XLA Modules"


class Event(NamedTuple):
    start: float    # ns
    end: float      # ns
    name: str
    stats: Dict[str, str]


class Raw(NamedTuple):
    """A trace file as recorded: each device's op and module events on
    the device's clock, every host event on the host's."""
    ops: Dict[str, List[Event]]
    modules: Dict[str, List[Event]]
    host: List[Event]


def read(path: str) -> Raw:
    from jax.profiler import ProfileData
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []

    def events(line):
        return [Event(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                      {k: str(v) for k, v in ev.stats})
                for ev in line.events]

    for plane in ProfileData.from_file(path).planes:
        if tr.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    ops[plane.name] = sorted(events(line), key=_at)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = sorted(events(line), key=_at)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += events(line)
    host.sort(key=_at)
    return Raw(ops, modules, host)


def _at(e: Event):
    return e.start, e.end


def host_offset(raw: Raw) -> Tuple[float, float]:
    """[lo, hi] in ns of host time minus device time, from every program
    run that the host's runtime queued and completed within the trace."""
    enqueued = {e.stats["_p"]: e for e in raw.host
                if e.name == ENQUEUE and "_p" in e.stats}
    completed = {e.stats["_c"]: e for e in raw.host
                 if e.name == COMPLETE and "_c" in e.stats}
    lo, hi = float("-inf"), float("inf")
    for runs in raw.modules.values():
        for run in runs:
            flow = run.stats.get("_c")
            if flow in enqueued:
                lo = max(lo, enqueued[flow].start - run.start)
            if flow in completed:
                hi = min(hi, completed[flow].start - run.end)
    if lo == float("-inf") or hi == float("inf"):
        raise ValueError("no program run that the host both queued and "
                         "completed")
    return lo, hi


def _innermost(events: List[Event], t: float) -> str:
    best = None
    for e in events:
        if e.start > t:
            break
        if e.end >= t and (
                best is None or e.end - e.start < best.end - best.start):
            best = e
    return best.name if best else "none"


def host_at_gaps(raw: Raw, n: int = 10) -> List[list]:
    """The longest idle gaps of the first device on the tied clock (the
    midpoint of ``host_offset``), each named twice: by the innermost
    ``bench.*`` span and by the innermost runtime event on any host
    thread (Python frames, ``$...``, left out) around its middle.
    [[bench span, runtime event, seconds], ...]."""
    if not raw.ops:
        return []
    lo, hi = host_offset(raw)
    off = (lo + hi) / 2
    windows = [e for e in raw.host if e.name == tr.WINDOW_SPAN]
    window = (min(e.start for e in windows), max(e.end for e in windows))
    dev = sorted(raw.ops)[0]
    busy = tr.clip(tr.union((o.start + off, o.end + off)
                            for o in raw.ops[dev]), window)
    gaps = sorted(tr.subtract([window], busy), key=lambda g: g[0] - g[1])
    spans = [e for e in raw.host if e.name.startswith(tr.HOST_SPAN_PREFIX)
             and e.name != tr.WINDOW_SPAN]
    runtime = [e for e in raw.host
               if not e.name.startswith(("$", tr.HOST_SPAN_PREFIX))]
    out = []
    for s, e in gaps[:n]:
        mid = (s + e) / 2
        out.append([_innermost(spans, mid),
                    _innermost(runtime, mid),
                    (e - s) * 1e-9])
    return out
