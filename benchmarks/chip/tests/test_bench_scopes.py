"""Device time per named scope (``chipbench.scopes``) and the tied clocks
(``chipbench.clocks``): the parser and the innermost-scope rule on a
CPU-compiled toy, the charging arithmetic by hand, the readers on the
smoke steps compiled on the CPU, and both traces recorded on a TPU v5e:
``small_trace`` (three kernels, no scopes) and ``scoped_trace``
(``record_scoped_trace.py``: one smoke train step and one decode step)."""
import gzip
import json
import re

import pytest
from bench_smoke import DATA
from chipbench import clocks
from chipbench import scopes as sc
from chipbench import spec as sp
from chipbench import trace as tr

SMALL = DATA / "small_trace.xplane.pb"
SCOPED = DATA / "scoped_trace.xplane.pb.gz"
SCOPED_TEXTS = DATA / "scoped_trace_hlo.json.gz"
SCOPE_READERS = ("ssd_bwd_share", "layer_scan_share.train",
                 "layer_scan_share.decode", "moe_share.decode")


def test_names_are_the_programs_and_the_readers_name_them():
    from repro.scopes import SCOPES
    assert sc.NAMES == set(SCOPES)
    for name in SCOPE_READERS:
        assert set(sp.metric_reader(name).SCOPES) <= set(SCOPES)


def test_innermost_reads_through_transform_wrappers():
    path = ("jit(step)/transpose(jvp(layer_scan))/while/body/closed_call/"
            "checkpoint/layer/ssm/ssd_fwd/ssd_bwd/cos")
    assert sc.innermost(path) == "ssd_bwd"
    assert sc.innermost("jit(step)/jvp(layer_scan)/while/body/"
                        "dynamic_slice") == "layer_scan"
    assert sc.innermost("jit(step)/transpose(jvp(moe_combine))/gather") \
        == "moe_combine"
    assert sc.innermost("jit(step)/while/body/closed_call/mul") is None
    assert sc.innermost("jit(layers)/mul") is None


def _toy_text() -> str:
    """grad of a scanned, rematerialized layer around a custom-VJP
    kernel, each piece in a registered scope, compiled on the CPU."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def kernel(x):
        with jax.named_scope("ssd_fwd"):
            return jnp.sin(x) * 2.0

    def kernel_fwd(x):
        return kernel(x), x

    def kernel_bwd(x, g):
        with jax.named_scope("ssd_bwd"):
            return (jnp.cos(x) * 2.0 * g,)

    kernel.defvjp(kernel_fwd, kernel_bwd)

    def body(h, w):
        with jax.named_scope("layer"):
            return jnp.tanh(kernel(h) @ w), None

    def loss(ws, x):
        with jax.named_scope("layer_scan"):
            h, _ = jax.lax.scan(jax.checkpoint(body), x, ws)
        return jnp.sum(h)

    ws, x = jnp.ones((3, 8, 8)), jnp.ones((4, 8))
    return jax.jit(jax.grad(loss)).lower(ws, x).compile().as_text()


def test_parser_on_a_compiled_toy():
    text = _toy_text()
    mod = sc.parse_module(text)
    assert mod.name.startswith("jit_")
    scopes = {i.scope for i in mod.instrs.values()}
    assert {"ssd_fwd", "ssd_bwd", "layer", "layer_scan"} <= scopes
    names = dict(re.findall(
        r'^\s*(?:ROOT )?%(\S+) = .*?op_name="([^"]*)"', text, re.M))
    assert {i: ins.op_name for i, ins in mod.instrs.items()
            if ins.op_name} == names
    for ins in mod.instrs.values():
        assert ins.scope == (ins.op_name and sc.innermost(ins.op_name))
    ops = [i for i in mod.instrs.values() if i.op_name]
    # the backward of the custom VJP, under the transposed scan and remat
    assert any("transpose(" in i.op_name and i.scope == "ssd_bwd"
               for i in ops)
    # the scan's own slicing of the stacked weights, outside its body
    assert any(i.scope == "layer_scan" and "dynamic_slice" in i.op_name
               for i in ops)


MODULE = """HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

ENTRY %main.1 (p: f32[8]) -> f32[8] {
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%c1, metadata={op_name="jit(step)/layer_scan/while/body/layer/ssm/ssd_fwd/mul" stack_frame_id=3}
  %copy.2 = f32[8]{0} copy(%fusion.1), metadata={op_name="jit(step)/transpose(jvp(layer_scan))/dynamic_slice"}
  %while.3 = (f32[8]{0}) while(%t), condition=%c, body=%b, metadata={op_name="jit(step)/layer_scan/while"}
  ROOT %copy.4 = f32[8]{0} copy(%x)
}
"""


def _op(name, shape, opcode, start, end):
    return tr.Op(start, end, name,
                 f"%{name} = {shape} {opcode}(f32[8]{{0}} %p)")


def _synthetic():
    ops = [_op("while.3", "(f32[8]{0})", "while", 0, 100),
           _op("fusion.1", "f32[8]{0}", "fusion", 10, 40),
           _op("copy.2", "f32[8]{0}", "copy", 30, 50),
           _op("copy.4", "f32[8]{0}", "copy", 60, 70),
           _op("fusion.9", "f32[8]{0}", "fusion", 80, 85),    # no module
           _op("copy.2", "bf16[8]{0}", "copy", 90, 95)]      # other shape
    return tr.Trace((0.0, 200.0), {"/device:TPU:0": ops},
                    [(0, 200, tr.WINDOW_SPAN)])


def test_charge_by_hand():
    """Overlaps split evenly; the loop gets what none of its ops covers;
    an op found in no module, or of another shape, is unscoped."""
    t, mods = _synthetic(), [sc.parse_module(MODULE)]
    got = sc.charge(t, mods)
    want = {"ssd_fwd": 25e-9, "layer_scan": 55e-9, "unscoped": 20e-9}
    assert got.keys() == want.keys()
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-12)


def test_scope_shares_unscoped_and_idle_sum_to_100():
    t, mods = _synthetic(), [sc.parse_module(MODULE)]
    busy, = tr.busy_seconds(t).values()
    idle = 100.0 * (1.0 - busy / t.window_s)
    shares = {k: 100.0 * v / t.window_s for k, v in sc.charge(t, mods).items()}
    assert sum(shares.values()) + idle == pytest.approx(100.0, abs=1e-9)


def test_unscoped_program_reads_as_nothing():
    t = _synthetic()
    bare = re.sub(r", metadata=\{[^}]*\}", "", MODULE)
    assert sc.scoped(t, [sc.parse_module(bare)]) == {}
    assert sc.scoped(t, [sc.parse_module(MODULE)])


@pytest.mark.parametrize("kind,readers", [
    ("train", ("ssd_bwd_share", "layer_scan_share.train")),
    ("decode", ("layer_scan_share.decode", "moe_share.decode")),
])
def test_readers_on_the_smoke_steps_compiled_on_cpu(kind, readers,
                                                    monkeypatch):
    """The readers compile the cell's step again as its window ran it
    (the SSD kernel in interpret mode, so that its custom VJP is there)
    and find each scope they name; a trace of every instruction once, 1 ns
    each, reads as each scope's share of the instructions."""
    import bench_smoke
    monkeypatch.setenv("REPRO_KERNELS", "pallas_interpret")
    ctx = bench_smoke.ctx(kind)
    texts = sc.step_texts(ctx)
    assert texts and all(t.startswith("HloModule jit_") for t in texts)
    mod = sc.parse_module(texts[0])
    ops, t = [], 0
    for line in texts[0].splitlines():
        m = sc._INSTR.match(line)
        if m:
            ops.append(tr.Op(t, t + 1, m.group(1), line.strip()))
            t += 1
    trace = tr.Trace((0.0, float(t)), {"/device:TPU:0": ops}, [])
    count = {}
    for ins in mod.instrs.values():
        count[ins.scope] = count.get(ins.scope, 0) + 1
    for name in readers:
        reader = sp.metric_reader(name)
        assert all(count.get(s) for s in reader.SCOPES), (name, count)
        want = sum(count[s] for s in reader.SCOPES)
        got = reader.read(ctx, {}, trace)
        assert got == pytest.approx(100.0 * want / t, rel=1e-9)
    assert sp.metric_reader(readers[0]).read(ctx, {}, None) is None


def test_readers_silent_without_scopes_in_the_program(monkeypatch):
    """A program with no ``repro.scopes`` reads as nothing, and its step
    is not compiled again to find that out."""
    import bench_smoke

    def no_compile(ctx):
        raise AssertionError("compiled")

    monkeypatch.setattr(sc, "_program_has_scopes", lambda: False)
    monkeypatch.setattr(sc, "step_texts", no_compile)
    trace = _synthetic()
    for name in SCOPE_READERS:
        kind = "train" if name in ("ssd_bwd_share",
                                   "layer_scan_share.train") else "decode"
        assert sp.metric_reader(name).read(
            bench_smoke.ctx(kind), {}, trace) is None
    monkeypatch.undo()
    assert sc._program_has_scopes()


def test_step_texts_compile_anew_when_the_cache_gives_no_scopes(
        monkeypatch):
    """A persistent-cache hit can return the step as a program without
    scopes compiled it; the step is then compiled again with that cache
    off and JAX's in-memory caches emptied, and the cache is on again
    afterwards."""
    import bench_smoke
    import jax
    has, clear = sc._has_scopes, jax.clear_caches
    cache_on_at_clear = []

    def clear_spy():
        cache_on_at_clear.append(jax.config.jax_enable_compilation_cache)
        clear()

    monkeypatch.setattr(sc, "_has_scopes", lambda text: False)
    monkeypatch.setattr(jax, "clear_caches", clear_spy)
    was = jax.config.jax_enable_compilation_cache
    text, = sc.step_texts(bench_smoke.ctx("decode"))
    assert cache_on_at_clear == [False]
    assert jax.config.jax_enable_compilation_cache == was
    assert has(text)


# -- the clocks and the accepted readers on the recorded small trace -------

@pytest.fixture(scope="module")
def small_raw():
    return clocks.read(str(SMALL))


def test_host_offset_on_the_recorded_trace(small_raw):
    """Three program runs bound host minus device time to [1.211, 1.626]
    ms: each started on the device after the host queued it, and each
    ended before the host completed it."""
    lo, hi = clocks.host_offset(small_raw)
    assert (lo, hi) == (1211177.0, 1625831.0)


def test_host_at_gaps_on_the_recorded_trace(small_raw):
    gaps = clocks.host_at_gaps(small_raw)
    assert [g[0] for g in gaps[:3]] == ["bench.pause"] * 3
    assert all(s > 0 for _, _, s in gaps)
    assert {"bench.flash", "bench.decode", "bench.ssd"} >= {
        g[0] for g in gaps[3:]}


def test_host_offset_needs_a_completed_run():
    raw = clocks.Raw({}, {"/device:TPU:0": [clocks.Event(
        0, 1, "jit_f(1)", {"_c": "7"})]}, [])
    with pytest.raises(ValueError):
        clocks.host_offset(raw)


ACCEPTED = {   # reader: (cell, window readings, value on the small trace)
    "idle_share.train": ("mamba2_370m.train_2k", {}, 98.00274847655798),
    "ssd_roofline": ("mamba2_370m.train_2k", {}, 131.84349836044586),
    "mfu.train": ("mamba2_370m.train_2k",
                  {"untraced_tokens_per_s": 6000.0}, 7.308278643654822),
    "idle_share.decode": ("granite_moe_1b_a400m.decode_4k", {},
                          98.00274847655798),
    "decode_attention_roofline": (
        "granite_moe_1b_a400m.decode_4k",
        {"traced_valid_mean": 1000.0, "batch": 4}, 16.62731537144663),
    "mfu.decode": ("granite_moe_1b_a400m.decode_4k",
                   {"untraced_step_s_mean": 0.068, "batch": 4,
                    "untraced_valid_mean": 800.0}, 5.078317029375852),
}


@pytest.mark.parametrize("name", sorted(ACCEPTED))
def test_accepted_readers_unchanged_on_the_recorded_trace(name):
    from chipbench import harness
    from chipbench.peaks import peak
    cell, win, want = ACCEPTED[name]
    ctx = harness.make_ctx(cell)
    ctx.peak = peak("TPU v5 lite")
    assert sp.metric_reader(name).read(ctx, win, tr.load(str(SMALL))) == want


# -- the scoped trace: smoke train and decode steps on the chip ------------

@pytest.fixture(scope="module")
def scoped_path(tmp_path_factory):
    path = tmp_path_factory.mktemp("scoped") / "scoped_trace.xplane.pb"
    with gzip.open(SCOPED) as f:
        path.write_bytes(f.read())
    return str(path)


@pytest.fixture(scope="module")
def scoped(scoped_path):
    with gzip.open(SCOPED_TEXTS, "rt") as f:
        texts = json.load(f)
    return tr.load(scoped_path), [sc.parse_module(t) for t in texts.values()]


def test_scoped_trace_is_small():
    assert SCOPED.stat().st_size + SCOPED_TEXTS.stat().st_size < 1 << 20


def _kernel_ops(trace):
    dev, = trace.devices
    return dev, [o for o in trace.devices[dev] if tr.PALLAS in o.text]


def test_scoped_trace_every_pallas_call_in_its_kernels_scope(scoped):
    trace, mods = scoped
    _, kernels = _kernel_ops(trace)
    got = sorted(sc.scope_of(o, mods) for o in kernels)
    # two layers: the SSD forward twice each (the scan and its remat
    # recompute), the flash-decode kernel once each
    assert got == ["attn_decode"] * 2 + ["ssd_fwd"] * 4


@pytest.mark.parametrize("reader,scope", [
    ("ssd_roofline", "ssd_fwd"),
    ("decode_attention_roofline", "attn_decode")])
def test_scoped_trace_signatures_and_scopes_pick_the_same_events(
        scoped, reader, scope):
    trace, mods = scoped
    _, kernels = _kernel_ops(trace)
    sig = re.compile(sp.metric_reader(reader).SIGNATURE)
    by_sig = [o for o in kernels if sig.search(o.text)]
    by_scope = [o for o in kernels if sc.scope_of(o, mods) == scope]
    assert by_sig and by_sig == by_scope


def test_scoped_trace_busy_time_is_scoped(scoped):
    trace, mods = scoped
    sec = sc.charge(trace, mods)
    busy, = tr.busy_seconds(trace).values()
    assert sum(sec.values()) == pytest.approx(busy, rel=1e-9)
    assert sec.get(sc.UNSCOPED, 0.0) <= 0.1 * busy
    assert {"ssd_fwd", "ssd_bwd", "adamw", "layer_scan", "attn_decode",
            "kv_cache", "moe_route"} <= set(sec)


def test_scoped_trace_clocks_tie(scoped_path):
    lo, hi = clocks.host_offset(clocks.read(scoped_path))
    assert 0 < lo <= hi < lo + 1e6
