"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Busy time is the union of the intervals in which an operation runs on a
device, kernel time the summed device time of a Pallas kernel's calls
(known by their shapes), and exposed collective time the part of the
collectives' union that no other operation on that device covers.  All of it is
clipped to the traced window, the host span ``bench.window`` that the
harness opens around the traced steps; the harness's other host spans
(``bench.*``) name what the host was doing in each idle gap.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Callable, Dict, List, NamedTuple, Tuple

WINDOW_SPAN = "bench.window"
HOST_SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"collective-permute|all-gather|all-reduce|"
                        r"reduce-scatter|all-to-all", re.IGNORECASE)

Interval = Tuple[float, float]


class Op(NamedTuple):
    start: float    # ns
    end: float      # ns
    name: str       # the HLO instruction's name, e.g. ``copy.446``
    text: str       # the event's whole text (an HLO instruction on a TPU)


PALLAS = 'custom_call_target="tpu_custom_call"'
# ops that contain other ops of the same line (a layer scan's loop)
CONTAINER = re.compile(r"^(while|conditional|call)$")


class Trace(NamedTuple):
    window: Interval
    devices: Dict[str, List[Op]]
    host: List[Tuple[float, float, str]]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    host: List[Tuple[float, float, str]] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name = ev.name.split(" = ", 1)[0].lstrip("%")
                    ops.append(Op(ev.start_ns, ev.start_ns + ev.duration_ns,
                                  name, ev.name))
            devices[plane.name] = sorted(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        host.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                     ev.name))
    windows = [(s, e) for s, e, n in host if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW_SPAN} host span")
    window = (min(s for s, _ in windows), max(e for _, e in windows))
    host.sort()
    return Trace(window, _align(devices, host), host)


def _align(devices: Dict[str, List[Op]], host) -> Dict[str, List[Op]]:
    """Device ops on the host's clock.  The two clocks of a trace can lie
    a millisecond or so apart (a v5e's put its ops before the host spans
    that launched them); no op can start before the host began the first
    step, so the device clock is moved forward by what it lies ahead."""
    first_span = next((s for s, _, n in host if n != WINDOW_SPAN), None)
    starts = [ops[0].start for ops in devices.values() if ops]
    if first_span is None or not starts or min(starts) >= first_span:
        return devices
    shift = first_span - min(starts)
    return {d: [o._replace(start=o.start + shift, end=o.end + shift)
                for o in ops] for d, ops in devices.items()}


def union(intervals) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, window: Interval) -> List[Interval]:
    w0, w1 = window
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            if e > w0 and s < w1]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """a minus b, both unions (sorted, disjoint)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def busy(trace: Trace, device: str) -> List[Interval]:
    return clip(union((o.start, o.end) for o in trace.devices[device]),
                trace.window)


def busy_seconds(trace: Trace) -> Dict[str, float]:
    return {d: length(busy(trace, d)) * 1e-9 for d in trace.devices}


def matching(trace: Trace, device: str, pred: Callable[[Op], bool]):
    return [o for o in trace.devices[device] if pred(o)
            and o.end > trace.window[0] and o.start < trace.window[1]]


def pallas_kernel(trace: Trace,
                  signature: str) -> Dict[str, Tuple[int, float]]:
    """{device: (events, seconds)} of the Pallas kernel calls whose HLO
    text matches ``signature`` (a regular expression over the result and
    operand shapes).  The trace names a Pallas call only by the enclosing
    function, never by its kernel, so a kernel is known by its shapes."""
    sig = re.compile(signature)
    out = {}
    for d in trace.devices:
        ops = matching(trace, d, lambda o: PALLAS in o.text
                       and sig.search(o.text))
        out[d] = (len(ops), sum(o.end - o.start for o in ops) * 1e-9)
    return out


def exposed_collective_seconds(trace: Trace) -> Dict[str, float]:
    """{device: seconds in which a collective runs and nothing else}."""
    out = {}
    for d in trace.devices:
        coll = union((o.start, o.end) for o in matching(
            trace, d, lambda o: COLLECTIVE.search(o.name)))
        rest = union((o.start, o.end) for o in matching(
            trace, d, lambda o: not COLLECTIVE.search(o.name)))
        out[d] = length(clip(subtract(coll, rest), trace.window)) * 1e-9
    return out


def _family(op: Op) -> str:
    """Op name without its instance number (``fusion.123`` -> ``fusion``),
    Pallas kernels marked as such."""
    fam = re.sub(r"[.\d]+$", "", op.name) or op.name
    return f"pallas:{fam}" if PALLAS in op.text else fam


def top_ops(trace: Trace, n: int = 10) -> List[list]:
    """[[op family, seconds averaged over devices], ...], largest first;
    loops and calls that contain other ops are left out."""
    acc: Dict[str, float] = {}
    for d in trace.devices:
        for o in matching(trace, d, lambda o: True):
            fam = _family(o)
            if CONTAINER.match(fam):
                continue
            acc[fam] = acc.get(fam, 0.0) + (min(o.end, trace.window[1])
                                            - max(o.start, trace.window[0]))
    nd = max(len(trace.devices), 1)
    return [[k, v * 1e-9 / nd] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def _host_span_at(trace: Trace, t: float) -> str:
    """The innermost ``bench.*`` span (other than the window) around t."""
    best = None
    for s, e, name in trace.host:
        if s <= t <= e and name != WINDOW_SPAN:
            if best is None or e - s < best[1] - best[0]:
                best = (s, e, name)
    return best[2] if best else "bench.none"


def idle_gaps(trace: Trace, n: int = 10) -> List[list]:
    """The longest idle gaps of the first device, each named by what the
    host was doing at its middle: [[host span, seconds], ...]."""
    if not trace.devices:
        return []
    dev = sorted(trace.devices)[0]
    gaps = subtract([trace.window], union(busy(trace, dev)))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_host_span_at(trace, (s + e) / 2), (e - s) * 1e-9]
            for s, e in gaps[:n]]
