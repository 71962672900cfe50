"""Device time of scopes that ``chipbench.scopes`` does not charge as its
readers do: scopes of the program that its list of names lacks (with the
fusions that hold their work), and the time in which a device runs the
rings' collectives and nothing else.

Both read the compiled step that ``scopes.step_texts`` gives and the ops
of the traced window as ``scopes.charge`` does.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional

from chipbench import scopes
from chipbench import trace as tr


def _innermost(op_name: str, names) -> Optional[str]:
    for part in reversed(op_name.split("/")):
        name = scopes._WRAPPER.sub("", part)
        if name in names:
            return name
    return None


_COMPUTATION = re.compile(r"^(?:ENTRY )?%([^\s(]+) ")
_CALLS = re.compile(r"calls=%([\w.-]+)")


def _fused_scopes(text: str, names) -> Dict[str, set]:
    """{fusion instruction: the scopes among ``names`` that the
    instructions of its fused computation carry}."""
    inside: Dict[str, set] = {}
    comp = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        op = scopes._OP_NAME.search(line)
        if comp and op:
            sc = _innermost(op.group(1), names)
            if sc:
                inside.setdefault(comp, set()).add(sc)
    out = {}
    for line in text.splitlines():
        m, c = scopes._INSTR.match(line), _CALLS.search(line)
        if m and c and " fusion(" in line:
            out[m.group(1)] = inside.get(c.group(1), set())
    return out


def modules(ctx, extra=()) -> List[scopes.Module]:
    """The cell's compiled step, each instruction charged as
    ``chipbench.scopes`` charges it but with the scopes ``extra`` known
    too: to the innermost scope on its ``op_name``, and a fusion that
    holds an instruction of an ``extra`` scope to that scope.  XLA names a
    fusion by its root alone, and the root can be another scope's cheap
    last step (the layer scan stacking the Mamba state that the fusion
    updates)."""
    names = scopes.NAMES | set(extra)
    out = []
    for text in scopes.step_texts(ctx):
        mod = scopes.parse_module(text)
        fused = _fused_scopes(text, names) if extra else {}
        instrs = {}
        for k, i in mod.instrs.items():
            sc = _innermost(i.op_name, names) if i.op_name else None
            held = [e for e in extra if e in fused.get(k, ())]
            instrs[k] = i._replace(scope=held[0] if held else sc)
        out.append(scopes.Module(mod.name, instrs))
    return out


def share(ctx, trace, name: str) -> Optional[float]:
    """% of the traced window that the first device spent in scope
    ``name``, which ``chipbench.scopes.NAMES`` need not list; None
    without a trace, or where no instruction of the step carries it."""
    if trace is None or not trace.devices \
            or not scopes._program_has_scopes():
        return None
    mods = modules(ctx, (name,))
    if not any(i.scope == name for m in mods for i in m.instrs.values()):
        return None
    sec = scopes.charge(trace, mods)
    return 100.0 * sec.get(name, 0.0) / trace.window_s


def exposed_share(ctx, trace, prefix: str = "ring_") -> Optional[float]:
    """% of the traced window, on the device where it is largest, in which
    an op of a scope named ``prefix``... runs and no op of any other scope
    (nor an unscoped one) does; loops and calls, which hold other ops, are
    not counted as work of their own.  None without a trace, or where no
    op of the window carries such a scope."""
    if trace is None or not trace.devices \
            or not scopes._program_has_scopes():
        return None
    mods = modules(ctx)
    worst, seen = 0.0, False
    for dev in trace.devices:
        ring, other = [], []
        for o in tr.matching(trace, dev, lambda o: True):
            if tr.CONTAINER.match(tr._family(o)):
                continue
            sc = scopes.scope_of(o, mods) or scopes.UNSCOPED
            (ring if sc.startswith(prefix) else other).append(
                (o.start, o.end))
        seen = seen or bool(ring)
        alone = tr.subtract(tr.union(ring), tr.union(other))
        worst = max(worst, tr.length(tr.clip(alone, trace.window)) * 1e-9)
    return 100.0 * worst / trace.window_s if seen else None
