"""Device time charged to the program's named scopes.

A device trace names an op only by its HLO instruction (``fusion.12``,
``copy.446``) and result shape.  The compiled module's text gives each
instruction the ``op_name`` that the program's scopes (``repro.scopes``)
left on it, e.g.
``jit(step)/transpose(jvp(layer_scan))/while/body/checkpoint/layer/ssm/
ssd_fwd/mul``.  An op belongs to the innermost registered scope on that
path, read through JAX's transform wrappers; an op under none, or found in
no module, is ``unscoped``.

Time is charged as busy time is counted: each stretch of the window in
which ops run is split evenly among the ops running then, and a loop or
call (``while``, ``call``, ``conditional``) gets only the stretches in
which none of its own ops runs.  So the scopes' seconds, ``unscoped``
included, add up to the device's busy time.

The readers need the compiled text of the steps a window ran.
``step_texts`` compiles the cell's step again from the kind's runner with
the window's argument shapes and shardings: on the chip a hit in JAX's
persistent cache, which returns the executable that ran.
"""
from __future__ import annotations

import re
import sys
from contextlib import contextmanager
from typing import Dict, List, NamedTuple, Optional

from chipbench import trace as tr

#: the program's registered scopes (``repro.scopes.SCOPES``), kept here as
#: literals: a program without them reads as all unscoped, and the readers
#: then stay silent
NAMES = frozenset({
    "embed", "layer_scan", "layer", "lm_head", "ssm", "ssd_fwd", "ssd_bwd",
    "attn_flash", "attn_decode", "kv_cache", "moe_route", "moe_dispatch",
    "moe_experts", "moe_combine", "adamw", "ring_gather", "ring_scatter",
    "ring_all_reduce", "ring_all_to_all"})
UNSCOPED = "unscoped"

_MODULE = re.compile(r"^HloModule ([^\s,]+)")
# ``%name = <result shape> <opcode>(``; no shape holds a space before "("
_INSTR = re.compile(r"^\s*(?:ROOT )?%([^\s=]+) = (.+?) [a-z][\w-]*\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_WRAPPER = re.compile(r"^(?:[\w-]+\()+|\)+$")


class Instr(NamedTuple):
    shape: str              # result shape with its layout, as printed
    op_name: Optional[str]  # its metadata's ``op_name``, if any
    scope: Optional[str]    # innermost registered scope on it, or None


class Module(NamedTuple):
    name: str
    instrs: Dict[str, Instr]


def innermost(op_name: str) -> Optional[str]:
    """The innermost registered scope on an ``op_name`` path, each part
    read through its transform wrappers (``transpose(jvp(x))`` is x)."""
    for part in reversed(op_name.split("/")):
        name = _WRAPPER.sub("", part)
        if name in NAMES:
            return name
    return None


def parse_module(text: str) -> Module:
    """{instruction: (result shape, op_name, scope)} of a compiled
    module's text (``Compiled.as_text()``)."""
    lines = text.splitlines()
    head = _MODULE.match(lines[0]) if lines else None
    instrs = {}
    for line in lines:
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line, m.end())
            op_name = op.group(1) if op else None
            instrs[m.group(1)] = Instr(
                m.group(2), op_name, innermost(op_name) if op else None)
    return Module(head.group(1) if head else "", instrs)


def _result_shape(op: tr.Op) -> Optional[str]:
    m = _INSTR.match(op.text)
    return m.group(2) if m else None


def scope_of(op: tr.Op, modules: List[Module]) -> Optional[str]:
    """The op's scope from the first module holding an instruction of its
    name and result shape; None where none does."""
    shape = _result_shape(op)
    for mod in modules:
        ins = mod.instrs.get(op.name)
        if ins is not None and ins.shape == shape:
            return ins.scope or UNSCOPED
    return None


def charge(trace: tr.Trace, modules: List[Module],
           device: str = None) -> Dict[str, float]:
    """{scope: seconds} of one device (the first by default) inside the
    traced window, ``unscoped`` included; they sum to its busy time."""
    if device is None:
        device = sorted(trace.devices)[0]
    w0, w1 = trace.window
    edges = []
    for i, o in enumerate(tr.matching(trace, device, lambda o: True)):
        s, e = max(o.start, w0), min(o.end, w1)
        if e > s:
            edges += [(s, 1, i, o), (e, -1, i, o)]
    edges.sort(key=lambda x: (x[0], x[1]))
    ops, loops = {}, {}     # running: index -> scope
    acc: Dict[str, float] = {}
    last = None
    for t, kind, i, o in edges:
        if last is not None and t > last:
            running = list(ops.values()) or list(loops.values())[-1:]
            for sc in running:
                acc[sc] = acc.get(sc, 0.0) + (t - last) / len(running)
        last = t
        into = loops if tr.CONTAINER.match(tr._family(o)) else ops
        if kind > 0:
            into[i] = scope_of(o, modules) or UNSCOPED
        else:
            into.pop(i, None)
    return {k: v * 1e-9 for k, v in acc.items()}


def scoped(trace: tr.Trace, modules: List[Module]) -> Dict[str, float]:
    """{scope: seconds}, largest first, when any op of the first device
    carries a registered scope; empty where none does (a program without
    scopes, or texts of other programs)."""
    sec = charge(trace, modules)
    if not set(sec) - {UNSCOPED}:
        return {}
    return dict(sorted(sec.items(), key=lambda kv: -kv[1]))


# -- the compiled steps of a cell's window --------------------------------

def step_texts(ctx) -> List[str]:
    """Compiled text of the step a cell's window ran, as a list of one.
    The kind's runner builds the step again; its arguments are made
    abstract as set-up makes them (the state placed by its initializers)
    and then as the window feeds them (from the step's own outputs).

    JAX's persistent cache keys a program without its metadata, so it can
    return the step as another version of the program compiled it, one
    without scopes.  Where the text carries none, the step is compiled
    anew with every cache off: the instructions that ran keep their
    names, which follow from the program and not from its scopes.

    Kept on ``ctx``: the readers of a run share one compile."""
    texts = vars(ctx).get("_step_texts")
    if texts is None:
        import jax
        from chipbench import spec as sp
        kind = ctx.mix["kind"]
        runner = sp.kind(kind).Runner(ctx)
        with jax.set_mesh(runner.mesh):
            args = _FEEDS[kind](runner)
            text = runner.step.lower(*args).compile().as_text()
            if not _has_scopes(text):
                with _no_caches():
                    text = runner.step.lower(*args).compile().as_text()
        texts = ctx._step_texts = [text]
    return texts


def _program_has_scopes() -> bool:
    """Whether the program under test names scopes at all: one without
    (an older version) is not compiled again to find none."""
    import importlib.util
    return importlib.util.find_spec("repro.scopes") is not None


def _has_scopes(text: str) -> bool:
    return any(i.scope for i in parse_module(text).instrs.values())


@contextmanager
def _no_caches():
    """JAX's persistent compile cache off, its in-memory caches empty."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _abstract(tree, shardings):
    import jax
    return jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, shardings)


def _window_args(step, args, feed, limit: int = 4):
    """The arguments the window calls ``step`` with: ``args`` as set-up
    first passes them, then ``feed(args, outputs)`` of each compiled
    executable's outputs, until they repeat."""
    seen = []
    while repr(args) not in seen and len(seen) < limit:
        seen.append(repr(args))
        compiled = step.lower(*args).compile()
        args = feed(args, _abstract(compiled.out_info,
                                    compiled.output_shardings))
    return args


def _train_feed(runner) -> tuple:
    import jax
    import jax.numpy as jnp
    from chipbench import traffic as tg
    params, opt, ef = jax.eval_shape(lambda: runner._st.init_sharded_state(
        runner.setup, runner.mesh, tg.jax_key(0)))
    ps = _param_shardings(runner, runner.setup)
    # AdamW's moments are placed as the parameters, its step uncommitted
    state = (_abstract(params, ps),
             _abstract(opt, {"m": ps, "v": ps, "step": None}), ef)
    rows = (runner.mix["batch"], runner.mix["seq"])
    batch = {k: jax.ShapeDtypeStruct(rows, jnp.int32)
             for k in ("tokens", "targets")}
    # (params, opt, ef, batch) -> (params, opt, ef, metrics)
    return _window_args(runner.step, (*state, batch),
                        lambda a, out: (*out[:3], a[3]))


def _decode_feed(runner) -> tuple:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.serve import step as ss
    from repro.train.step import dp_axes_of, init_sharded_params
    from chipbench import traffic as tg
    params = _abstract(
        jax.eval_shape(lambda: init_sharded_params(
            runner.train_setup, runner.mesh, tg.jax_key(0))),
        _param_shardings(runner, runner.train_setup))
    cache = jax.eval_shape(lambda: ss.init_serve_state(
        runner.serve_setup, runner.mesh, params, runner.batch, runner.cap))
    specs = ss._cache_specs(runner.cfg, dp_axes_of(runner.mesh),
                            context_shard=runner.serve_setup.context_shard)
    cache = _abstract(cache, jax.tree_util.tree_map(
        lambda s: NamedSharding(runner.mesh, s), specs,
        is_leaf=lambda x: isinstance(x, PartitionSpec)))
    tok = jax.ShapeDtypeStruct((runner.batch, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    # (params, cache, token, pos) -> (token, top logit, cache)
    return _window_args(runner.step, (params, cache, tok, pos),
                        lambda a, out: (a[0], out[2], out[0], a[3]))


def _param_shardings(runner, setup):
    """The shardings the program's initializer gives the parameters."""
    import jax
    from jax.sharding import NamedSharding
    from repro.models.transformer import init_lm
    from repro.train.step import state_specs
    tpl = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), runner.cfg))
    return jax.tree_util.tree_map(lambda s: NamedSharding(runner.mesh, s),
                                  state_specs(setup, runner.mesh, tpl))


_FEEDS = {"train": _train_feed, "decode": _decode_feed}


def report(ctx, trace) -> Dict[str, float]:
    """{scope: seconds} of the run's traced window, kept on ``ctx`` and
    printed once on standard error; empty where the program has no
    scopes."""
    held = vars(ctx).get("_scope_seconds")
    if held is not None and held[0] is trace:
        return held[1]
    sec = scoped(trace, [parse_module(t) for t in step_texts(ctx)])
    ctx._scope_seconds = (trace, sec)
    if sec:
        busy = sum(sec.values())
        print(f"scopes (first device, {busy:.6f} s busy of "
              f"{trace.window_s:.6f} s): "
              + ", ".join(f"{k} {v:.6f}" for k, v in sec.items()),
              file=sys.stderr)
    return sec


def share(ctx, trace, names) -> Optional[float]:
    """% of the traced window that the first device spent in the given
    scopes; None without a trace or without scopes in the program."""
    if trace is None or not trace.devices or not _program_has_scopes():
        return None
    sec = report(ctx, trace)
    if not sec:
        return None
    return 100.0 * sum(sec.get(n, 0.0) for n in names) / trace.window_s
