"""Distributed train step: photonic == eps == single-device; HSDP/accum/
compression; checkpoint restart + elastic reshard."""
import math

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_config
from repro.models import transformer as T
from repro.train.checkpoint import restore, save
from repro.train.data import DataConfig, synth_batch
from repro.train.optimizer import OptConfig
from repro.train.step import TrainSetup, init_sharded_state, make_train_step

CFG = get_config("yi_9b", smoke=True).replace(dtype="float32")
RNG = jax.random.PRNGKey(0)
B, S = 8, 16


@pytest.fixture(scope="module")
def batch():
    return {"tokens": jax.random.randint(RNG, (B, S), 0, CFG.vocab_size,
                                         jnp.int32),
            "targets": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                          CFG.vocab_size, jnp.int32)}


@pytest.fixture(scope="module")
def reference(batch):
    params = T.init_lm(RNG, CFG)
    loss, _ = T.lm_loss(params, batch, CFG)
    g = jax.grad(lambda p: T.lm_loss(p, batch, CFG)[0])(params)
    gn = math.sqrt(sum(float(jnp.sum(jnp.square(x)))
                       for x in jax.tree_util.tree_leaves(g)))
    return float(loss), gn


@pytest.fixture(scope="module")
def tpl():
    return jax.eval_shape(lambda: T.init_lm(RNG, CFG))


@pytest.mark.parametrize("fabric", ["photonic", "eps"])
def test_step_matches_reference(mesh8, batch, reference, tpl, fabric):
    loss_ref, gn_ref = reference
    with jax.set_mesh(mesh8):
        setup = TrainSetup(cfg=CFG, fabric=fabric)
        params, opt, ef = init_sharded_state(setup, mesh8, RNG)
        step = jax.jit(make_train_step(setup, mesh8, tpl))
        _, _, _, m = step(params, opt, ef, batch)
    assert abs(float(m["loss"]) - loss_ref) < 1e-4
    assert abs(float(m["grad_norm"]) - gn_ref) / gn_ref < 1e-3


@pytest.mark.parametrize("kw,tol", [
    ({}, 2e-3),                                     # hierarchical FSDP
    ({"hsdp": True}, 2e-3),                         # pod-replicated + AR
    ({"hsdp": True, "compress_pod_grads": True}, 0.02),  # int8 + EF
    ({"accum": 2}, 2e-3),                           # grad accumulation
])
def test_multipod_variants(mesh_pod, batch, reference, tpl, kw, tol):
    loss_ref, gn_ref = reference
    with jax.set_mesh(mesh_pod):
        setup = TrainSetup(cfg=CFG, **kw)
        params, opt, ef = init_sharded_state(setup, mesh_pod, RNG)
        step = jax.jit(make_train_step(setup, mesh_pod, tpl))
        _, _, _, m = step(params, opt, ef, batch)
    assert abs(float(m["loss"]) - loss_ref) < 2e-4
    assert abs(float(m["grad_norm"]) - gn_ref) / gn_ref < tol


def test_loss_decreases_over_steps(mesh8, tpl):
    dc = DataConfig(seq_len=S, global_batch=B)
    with jax.set_mesh(mesh8):
        setup = TrainSetup(cfg=CFG, opt=OptConfig(lr=3e-3, warmup_steps=2))
        params, opt, ef = init_sharded_state(setup, mesh8, RNG)
        step = jax.jit(make_train_step(setup, mesh8, tpl))
        losses = []
        fixed = synth_batch(CFG, dc, 0)
        for i in range(8):
            params, opt, ef, m = step(params, opt, ef, fixed)
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.2


def test_error_feedback_accumulates(mesh_pod, batch, tpl):
    with jax.set_mesh(mesh_pod):
        setup = TrainSetup(cfg=CFG, hsdp=True, compress_pod_grads=True)
        params, opt, ef = init_sharded_state(setup, mesh_pod, RNG)
        step = jax.jit(make_train_step(setup, mesh_pod, tpl))
        _, _, ef2, _ = step(params, opt, ef, batch)
    # EF state must be non-zero (quantization residue retained)
    total = sum(float(jnp.sum(jnp.abs(x)))
                for x in jax.tree_util.tree_leaves(ef2))
    assert total > 0


def test_checkpoint_restart_and_elastic_reshard(tmp_path, mesh8, mesh_pod,
                                                batch, tpl):
    """Save on (4,2) mesh, restore on (2,2,2): elastic restart (§4.2)."""
    ck = str(tmp_path / "ck")
    with jax.set_mesh(mesh8):
        setup = TrainSetup(cfg=CFG)
        params, opt, ef = init_sharded_state(setup, mesh8, RNG)
        step = jax.jit(make_train_step(setup, mesh8, tpl))
        params, opt, ef, m1 = step(params, opt, ef, batch)
        save(ck, params, opt, ef, extra={"step": 1})
        params, opt, ef, m2 = step(params, opt, ef, batch)

    # restart on a DIFFERENT mesh, resharded
    with jax.set_mesh(mesh_pod):
        setup2 = TrainSetup(cfg=CFG)
        p2, o2, e2, extra = restore(ck, setup2, mesh_pod, tpl)
        assert extra["step"] == 1
        step2 = jax.jit(make_train_step(setup2, mesh_pod, tpl))
        _, _, _, m2b = step2(p2, o2, e2, batch)
    # the continued step must match the original trajectory
    assert abs(float(m2b["loss"]) - float(m2["loss"])) < 1e-4
    assert abs(float(m2b["grad_norm"]) - float(m2["grad_norm"])) < 1e-3


def test_moe_arch_through_distributed_step(mesh8, batch):
    cfg = get_config("deepseek_moe_16b", smoke=True).replace(dtype="float32")
    tpl = jax.eval_shape(lambda: T.init_lm(RNG, cfg))
    loss_ref, _ = T.lm_loss(T.init_lm(RNG, cfg), batch, cfg)
    with jax.set_mesh(mesh8):
        setup = TrainSetup(cfg=cfg)
        params, opt, ef = init_sharded_state(setup, mesh8, RNG)
        step = jax.jit(make_train_step(setup, mesh8, tpl))
        _, _, _, m = step(params, opt, ef, batch)
    # per-device aux-balance loss is a different (nonlinear) partition of
    # the same quantity — small tolerance (DESIGN.md §Arch-applicability)
    assert abs(float(m["loss"]) - float(loss_ref)) < 1e-2


@pytest.mark.parametrize("fabric", ["photonic", "eps"])
def test_kernel_path_under_mesh_matches_ref(mesh8, batch, monkeypatch,
                                            fabric):
    """The Pallas SSD kernel (interpret mode) inside the distributed step:
    ops wraps it in a shard_map over the mesh axes not yet manual, with
    heads split over `model`; the step must equal the oracle path."""
    cfg = get_config("mamba2_370m", smoke=True).replace(dtype="float32")
    tpl = jax.eval_shape(lambda: T.init_lm(RNG, cfg))
    out = {}
    for mode in ("ref", "pallas_interpret"):
        monkeypatch.setenv("REPRO_KERNELS", mode)
        with jax.set_mesh(mesh8):
            setup = TrainSetup(cfg=cfg, fabric=fabric)
            params, opt, ef = init_sharded_state(setup, mesh8, RNG)
            step = jax.jit(make_train_step(setup, mesh8, tpl))
            _, _, _, m = step(params, opt, ef, batch)
        out[mode] = (float(m["loss"]), float(m["grad_norm"]))
    assert out["pallas_interpret"] == pytest.approx(out["ref"], rel=1e-4)
