"""Granite 4.0-H, the hybrid configuration, at smoke size on the CPU: its
configuration file as the harness reads it, its plain reference against
the program (weights, full forward, decode through the SSM and KV caches,
the held share of the experts), and the readers and counts this cell and
the four-chip training cell add."""
import dataclasses
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench_smoke import DATA
from chipbench import flops, harness, hybrid_flops, scope_extra
from chipbench import scopes as sc
from chipbench import spec as sp
from chipbench import trace as tr
from chipbench import traffic as tg
from chipbench.peaks import peak
from chipbench.program import model_config

SEED = 2**31 + 4099
CELL = "granite_4_0_h_small.decode_4k_b64"
TRAIN4 = "granite_moe_1b_a400m.train_4k_4chip"


def smoke_ctx():
    """The hybrid cell's context at the smoke configuration's size."""
    conf = json.loads((DATA / "granite_hybrid_smoke.json").read_text())
    w = sp.workload(sp.load_spec(), CELL)
    mix = dict(sp.traffic(w["traffic"]), batch=4, prompt_len=8,
               capacity=24, check_rows=2)
    return harness.Ctx(cell=CELL, conf=conf, model=conf["model"], mix=mix,
                       chips=w["chips"], cfg=model_config(conf))


def test_config_file_builds_unchanged_and_hashes():
    """``model_config`` takes the file's ``model`` as it is: the JSON list
    of layer kinds becomes a tuple, so the config hashes and can be a
    static argument of ``jit``; the top-level keys are the published
    config.json's, with the cut ones, and only those, listed in
    ``reduced``."""
    conf = sp.config("granite_4_0_h_small")
    cfg = model_config(conf)
    assert isinstance(cfg.layer_pattern, tuple) and hash(cfg)
    assert cfg.pattern == ("mamba",) * 5 + ("attn",) + ("mamba",) * 4
    assert (cfg.moe.n_experts, cfg.moe.held, cfg.moe.top_k) == (72, 9, 10)
    assert jax.jit(lambda x, c: x * c.d_model, static_argnums=1)(
        1.0, cfg) == 4096.0
    changed = {k for k, v in conf["published"].items() if conf[k] != v}
    assert changed == set(conf["reduced"])
    assert conf["num_hidden_layers"] == cfg.n_layers
    assert conf["num_local_experts"] == cfg.moe.held


def _program_params(c):
    from repro.models import transformer as tf
    return tf.init_lm(tg.jax_key(SEED), c.cfg)


def _reference_logits(c, toks):
    ref = sp.reference(c.conf["reference"])
    with jax.default_matmul_precision("highest"):
        pf = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                    ref.init(tg.jax_key(SEED), c.model))
        return ref.forward(pf, toks, c.model)[0][..., :c.cfg.vocab_size]


def test_reference_makes_the_programs_weights():
    from repro.models import transformer as tf
    c = smoke_ctx()
    ref = sp.reference(c.conf["reference"])
    key = tg.jax_key(SEED)
    got = jax.tree_util.tree_flatten_with_path(tf.init_lm(key, c.cfg))[0]
    want = jax.tree_util.tree_flatten_with_path(ref.init(key, c.model))[0]
    assert [jax.tree_util.keystr(p) for p, _ in got] == \
        [jax.tree_util.keystr(p) for p, _ in want]
    for (path, g), (_, w) in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, path
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


def _tokens(c, n):
    return jnp.asarray(tg.prompts(dict(c.mix, prompt_len=n),
                                  c.cfg.vocab_size, SEED))


# bf16 weights and activations through four layers: every logit within 2%
# of the largest; a near-tied expert choice that bf16 rounding flips moves
# a position by a whole expert's share, which the smoke model's logits
# (divided by 16) keep under that too
LOGIT_TOL = 0.02


def test_forward_matches_reference():
    """The program's teacher-forced forward (train and prefill path) at a
    capacity no routing overflows, against the reference's."""
    from repro.models import transformer as tf
    c = smoke_ctx()
    moe = dataclasses.replace(c.cfg.moe, capacity_factor=c.cfg.moe.n_experts
                              / c.cfg.moe.top_k)
    toks = _tokens(c, 24)
    got, _ = tf.lm_forward(_program_params(c), {"tokens": toks},
                           c.cfg.replace(moe=moe))
    want = _reference_logits(c, toks)
    got = got[..., :c.cfg.vocab_size].astype(jnp.float32)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= LOGIT_TOL * scale


def test_teacher_forced_decode_matches_full_forward():
    """Decode through the serving path, one token a step through the SSM
    (conv, state) and KV caches, against the reference's one full forward:
    the logits at every position, not the tokens."""
    from repro.launch.train import parse_mesh
    from repro.models import transformer as tf
    from repro.serve.step import (ServeSetup, init_serve_state,
                                  make_decode_step)
    from repro.train.step import TrainSetup, init_sharded_params
    c = smoke_ctx()
    cfg, mix = c.cfg, c.mix
    key = tg.jax_key(SEED)
    mesh = parse_mesh("1x1")
    toks = _tokens(c, mix["capacity"])
    tpl = jax.eval_shape(lambda: tf.init_lm(key, cfg))
    with jax.set_mesh(mesh):
        params = init_sharded_params(TrainSetup(cfg=cfg), mesh, key)
        ss = ServeSetup(cfg=cfg)
        step = jax.jit(make_decode_step(ss, mesh, tpl, batch=mix["batch"],
                                        capacity=mix["capacity"]))
        state = init_serve_state(ss, mesh, params, mix["batch"],
                                 mix["capacity"])
        dec = []
        for t in range(mix["capacity"]):
            lg, state = step(params, state, toks[:, t:t + 1], jnp.int32(t))
            dec.append(lg[:, 0])
    got = jnp.stack(dec, 1)[..., :cfg.vocab_size].astype(jnp.float32)
    want = _reference_logits(c, toks)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= LOGIT_TOL * scale


@pytest.mark.parametrize("split", [(3, 3, 2), (1, 7), (8,)])
def test_expert_shares_add_up_to_the_whole_layer(split):
    """Each share of the experts routes over all of them and computes its
    own experts' part; the parts of all shares, the shared expert counted
    once, add up to the uncut layer, and to the uncut reference layer.
    In float32, so that only the order of sums differs."""
    from repro.models import moe as moe_mod
    from repro.models.layers import mlp_apply
    c = smoke_ctx()
    whole = c.cfg.replace(dtype="float32", moe=dataclasses.replace(
        c.cfg.moe, n_held=None, first_held=0))
    p = moe_mod.moe_init(jax.random.PRNGKey(7), whole, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(8), (4, 1, whole.d_model))
    y_whole, _ = moe_mod.moe_apply(p, x, whole)
    shared = mlp_apply(p["shared"], x)
    total, first = shared, 0
    for n in split:
        cfg = whole.replace(moe=dataclasses.replace(
            whole.moe, n_held=n, first_held=first))
        part = dict(p, **{k: p[k][first:first + n]
                          for k in ("w_gate", "w_up", "w_down")})
        y, _ = moe_mod.moe_apply(part, x, cfg)
        total = total + (y - shared)
        first += n
    np.testing.assert_allclose(total, y_whole, rtol=1e-5, atol=1e-6)
    ref = sp.reference(c.conf["reference"])
    model = dict(c.model, moe=dict(c.model["moe"], n_held=8, first_held=0))
    with jax.default_matmul_precision("highest"):
        want = ref.moe(p, x, model, "f32")
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def test_decode_cell_runs_and_checks_at_smoke_size():
    """The decode kind drives the hybrid through set-up, a short window and
    the check against the reference, with the fp8 control beside it."""
    c = smoke_ctx()
    kind = sp.kind("decode")
    runner = kind.Runner(c)
    runner.prepare(SEED)
    win = runner.window(0.2)
    assert win["steps"] > 0 and win["failed"] == 0
    gaps = kind.reference_gaps(c, SEED, runner.readings(), control="fp8")
    # bf16 through four layers at smoke width: a tenth of the spread of
    # the reference's logits, where a wrong mixer or multiplier reads 1
    assert gaps["tokens_checked"] > 0 and gaps["logit_error"] < 0.1
    assert 0 < gaps["control"]["logit_error"] < 1


# -- counts and readers ----------------------------------------------------

def test_hybrid_count_of_an_all_attention_model_is_the_attention_count():
    """With every layer attention and every expert held, the hybrid count
    is ``flops.decode_step``'s, and its bytes add only the one cache slot
    each layer writes."""
    model = dict(sp.config("granite_moe_1b_a400m")["model"],
                 layer_pattern=["attn"])
    b, valid = 16, 800.0
    got = hybrid_flops.decode_step(model, b, valid)
    want = flops.decode_step(model, b, valid)
    assert got.flops == pytest.approx(want.flops, rel=1e-12)
    written = model["n_layers"] * 2 * 2 * b * model["n_kv_heads"] * 64
    assert got.bytes == pytest.approx(want.bytes + written, rel=1e-12)


def test_hybrid_count_of_the_cell():
    """The cell's step at 800 valid positions: 4.83 GB of weights held,
    the Mamba state read and written (9 layers x 64 streams x 4.19 MB,
    twice; 4.83 GB), 0.21 GB of valid cache: memory-bound."""
    model = sp.config("granite_4_0_h_small")["model"]
    cost = hybrid_flops.decode_step(model, 64, 800.0)
    state = 2 * 9 * 64 * 128 * 64 * 128 * 4
    assert 9.9e9 < cost.bytes < 10.1e9 and cost.bytes > state
    chip = peak("TPU v5 lite")
    assert cost.bytes / chip.hbm_bytes > cost.flops / chip.flops


def _hlo(*instrs) -> str:
    lines = ["HloModule jit_step"]
    for name, op_name in instrs:
        lines.append(f'  %{name} = f32[8]{{0}} fusion(%p), '
                     f'metadata={{op_name="{op_name}"}}')
    return "\n".join(lines)


def _op(name, start, end):
    return tr.Op(start, end, name, f"%{name} = f32[8]{{0}} fusion(%p)")


def test_exposed_ring_share_by_hand(monkeypatch):
    """Ring time no other op covers, on the worst device; loops do not
    cover it; nothing where no ring op runs."""
    text = _hlo(("cp.1", "jit(step)/transpose(jvp(ring_gather))/ppermute"),
                ("cp.2", "jit(step)/ring_scatter/ppermute"),
                ("fusion.3", "jit(step)/layer/mul"),
                ("fusion.4", "jit(step)/mul"),
                ("while.5", "jit(step)/layer_scan/while"))
    monkeypatch.setattr(sc, "step_texts", lambda ctx: [text])
    dev0 = [_op("while.5", 0, 100), _op("cp.1", 0, 30),
            _op("fusion.3", 10, 20), _op("cp.2", 50, 60)]
    dev1 = [_op("cp.1", 0, 40), _op("fusion.4", 0, 35)]
    trace = tr.Trace((0.0, 100.0), {"/device:TPU:0": dev0,
                                    "/device:TPU:1": dev1}, [])
    # device 0: 0..30 less 10..20, and 50..60: 30 of 100
    assert scope_extra.exposed_share(None, trace) == pytest.approx(30.0)
    quiet = tr.Trace((0.0, 100.0), {"/device:TPU:0": [
        _op("fusion.3", 0, 10)]}, [])
    assert scope_extra.exposed_share(None, quiet) is None
    assert sp.metric_reader("exposed_collective_share.train").read(
        None, {}, None) is None


def test_extra_scope_share_on_the_compiled_hybrid_step(monkeypatch):
    """``ssm_state`` reaches the compiled decode step's ``op_name``s, and
    its reader reads those ops' share of a synthetic window of one op per
    instruction; ``chipbench.scopes``, which does not know the name,
    charges the same ops to a scope around it."""
    monkeypatch.setenv("REPRO_KERNELS", "pallas_interpret")
    c = smoke_ctx()
    text = sc.step_texts(c)[0]
    mod, = scope_extra.modules(c, ("ssm_state",))
    names = [k for k, i in mod.instrs.items() if i.scope == "ssm_state"]
    assert names
    plain = sc.parse_module(text)
    assert {plain.instrs[k].scope for k in names} <= {"ssm", "layer_scan",
                                                      "layer"}
    ops = [tr.Op(t, t + 1, k, f"%{k} = {i.shape} fusion(%p)")
           for t, (k, i) in enumerate(mod.instrs.items())]
    trace = tr.Trace((0.0, float(len(ops))), {"/device:TPU:0": ops}, [])
    got = sp.metric_reader("ssm_state_share.decode").read(c, {}, trace)
    assert got == pytest.approx(100.0 * len(names) / len(ops), rel=1e-9)
    assert sp.metric_reader("ssm_state_share.decode").read(c, {}, None) \
        is None


FUSED = """HloModule jit_step

%fused_computation.7 (param_0: f32[8]) -> f32[1,8] {
  %param_0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/layer_scan/while/body/layer/ssm/ssm_state/mul"}
  ROOT %bitcast.2 = f32[1,8]{1,0} bitcast(%mul.1), metadata={op_name="jit(step)/layer_scan/while/body/broadcast_in_dim"}
}

ENTRY %main (p: f32[8]) -> f32[1,8] {
  %p = f32[8]{0} parameter(0)
  ROOT %fusion.3 = f32[1,8]{1,0} fusion(%p), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(step)/layer_scan/while/body/broadcast_in_dim"}
}
"""


def test_fusion_holding_an_extra_scope_is_charged_to_it(monkeypatch):
    """XLA names a fusion by its root: here the layer scan's stacking of
    the state that the fusion updates.  ``chipbench.scopes`` charges it to
    ``layer_scan``; with ``ssm_state`` asked for, it is ``ssm_state``'s."""
    monkeypatch.setattr(sc, "step_texts", lambda ctx: [FUSED])
    assert sc.parse_module(FUSED).instrs["fusion.3"].scope == "layer_scan"
    mod, = scope_extra.modules(None, ("ssm_state",))
    assert mod.instrs["fusion.3"].scope == "ssm_state"
    mod, = scope_extra.modules(None)
    assert mod.instrs["fusion.3"].scope == "layer_scan"
    trace = tr.Trace((0.0, 10.0), {"/device:TPU:0": [
        tr.Op(0, 4, "fusion.3", "%fusion.3 = f32[1,8]{1,0} fusion(%p)")]},
        [])
    monkeypatch.setattr(sc, "_program_has_scopes", lambda: True)
    assert scope_extra.share(None, trace, "ssm_state") == pytest.approx(40.0)


def test_flash_signature_finds_the_flash_call_only():
    """On the trace recorded on a v5e, the reader's signature picks out
    the one flash-attention call and not the decode or SSD kernels."""
    trace = tr.load(str(DATA / "small_trace.xplane.pb"))
    sig = sp.metric_reader("flash_attention_roofline").SIGNATURE
    (n, seconds), = tr.pallas_kernel(trace, sig).values()
    assert n == 1 and seconds > 0
    dev, = trace.devices.values()
    spans = {name: (s, e) for s, e, name in trace.host}
    op, = [o for o in dev if tr.PALLAS in o.text
           and re.search(sig, o.text)]
    assert spans["bench.flash"][0] - 2e5 <= op.start <= spans["bench.flash"][1]


def test_causal_flash_count():
    """One call at the four-chip cell's shapes on a chip (2 rows, 16 heads
    of 64, 4096 positions): 68.7 GFLOP, so compute-bound on a v5e."""
    reader = sp.metric_reader("flash_attention_roofline")
    call = reader.causal_call(2, 16, 8, 4096, 64)
    assert call.flops == 4 * 2 * 16 * 64 * 4096 * 4097 / 2
    assert call.bytes == 2 * (2 * 2 * 16 * 4096 * 64 + 2 * 2 * 8 * 4096 * 64)
    chip = peak("TPU v5 lite")
    assert call.seconds(chip) == call.flops / chip.flops


def test_sharded_reference_is_the_reference():
    """The four-chip kind's reference, its state split over as many of
    four devices as there are (the suite forces eight on the CPU), gives
    the single-device reference's readings."""
    conf = json.loads((DATA / "granite_smoke.json").read_text())
    w = sp.workload(sp.load_spec(), TRAIN4)
    mix = dict(sp.traffic(w["traffic"]), batch=4, seq=32)
    c = harness.Ctx(cell=TRAIN4, conf=conf, model=conf["model"], mix=mix,
                    chips=min(4, jax.device_count()),
                    cfg=model_config(conf))
    got = sp.kind("train_sharded").reference_readings(c, SEED)
    want = sp.kind("train").reference_readings(c, SEED)
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=1e-5)
    assert got["grad_norm"] == pytest.approx(want["grad_norm"], rel=1e-5)
    for k, g in want["grad"].items():
        np.testing.assert_allclose(got["grad"][k], g, rtol=1e-4,
                                   atol=1e-6 * float(np.abs(g).max()))



def _train4_ctx(dtype: str):
    """The four-chip cell on four CPU devices, at smoke size."""
    conf = json.loads((DATA / "granite_smoke.json").read_text())
    conf["model"]["dtype"] = dtype
    w = sp.workload(sp.load_spec(), TRAIN4)
    mix = dict(sp.traffic(w["traffic"]), batch=8, seq=64)
    return harness.Ctx(cell=TRAIN4, conf=conf, model=conf["model"], mix=mix,
                       chips=4, cfg=model_config(conf))


def _skip_ring_reduce_scatter():
    """Plant the fault only a sharded cell can have: each chip keeps its
    own rows' part of its gradient shard, the ring reduce-scatter of the
    gradients left out."""
    from repro.core import _fabric_rings as fr
    gather = fr.Fabric.all_gather

    def all_gather(self, x, axis=0):
        @jax.custom_vjp
        def g(x):
            return gather(self, x, axis)

        def bwd(_, ct):
            n = ct.shape[axis] // self.n_shards
            return (jax.lax.dynamic_slice_in_dim(
                ct, self.axis_index() * n, n, axis),)

        g.defvjp(lambda x: (g(x), None), bwd)
        return g(x)

    fr.Fabric.all_gather = all_gather


def train4_numbers(dtype: str, fault: bool) -> dict:
    """The four-chip cell's numbers against its reference."""
    if fault:
        _skip_ring_reduce_scatter()
    c = _train4_ctx(dtype)
    kind = sp.kind("train_sharded")
    runner = kind.Runner(c)
    runner.prepare(SEED)
    return kind.compare(runner.readings(), kind.reference_readings(c, SEED))


def _train4(dtype: str, fault: bool = False) -> dict:
    """``train4_numbers`` in a process of its own with four CPU devices,
    whatever devices this one has."""
    here = Path(__file__).resolve().parent
    paths = [str(here), str(here.parent), str(here.parents[2] / "src")]
    code = ("import json, sys; sys.path[:0] = %r; "
            "import test_bench_hybrid as t; "
            "print(json.dumps(t.train4_numbers(%r, %r)))"
            % (paths, dtype, fault))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True)
    return json.loads(out.stdout.splitlines()[-1])


def test_rings_reproduce_the_reference_in_float32():
    """With weights and activations in float32 the photonic step on four
    devices (weights ring-gathered, gradients ring-reduce-scattered) gives
    the reference's numbers to round-off: the rings and the reference's
    blocks of a chip's rows add nothing, so what the cell reads in
    bfloat16 is the program's arithmetic.  The gaps stay under 1e-4,
    where bfloat16 reads about 2e-3 and the limits start at 2e-3."""
    got = _train4("float32")
    assert max(got.values()) < 1e-4, got


def test_skipped_ring_reduce_scatter_is_caught():
    """The gradients' ring reduce-scatter left out fails the cell's
    gradient limits by far."""
    got = _train4("bfloat16", fault=True)
    lim = sp.limits(TRAIN4)
    assert got["grad_norm_gap"] > 10 * lim["grad_norm_gap"], got
    assert got["grad_error"] > 2 * lim["grad_error"], got
    assert not harness.check_numbers(got, lim)[0]
