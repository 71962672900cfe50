"""Serve paths: batch-sharded + context-sharded decode, prefill."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import transformer as T
from repro.serve.step import (ServeSetup, init_serve_state, make_decode_step,
                              make_prefill_step)
from repro.train.step import TrainSetup, init_sharded_state

CFG = get_config("yi_9b", smoke=True).replace(dtype="float32")
RNG = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def toks():
    return jax.random.randint(jax.random.PRNGKey(1), (8, 12), 0,
                              CFG.vocab_size, jnp.int32)


@pytest.fixture(scope="module")
def params_ref():
    return T.init_lm(RNG, CFG)


def _ref_decode(params, toks, cfg, b, s, cap):
    st = T.init_decode_state(cfg, b, cap)
    outs = []
    for t in range(s):
        lg, st = T.decode_step(params, st, toks[:b, t:t + 1], jnp.int32(t),
                               cfg)
        outs.append(lg[:, 0])
    return jnp.stack(outs, 1)


def test_batch_sharded_decode(mesh8, toks, params_ref):
    tpl = jax.eval_shape(lambda: T.init_lm(RNG, CFG))
    ref = _ref_decode(params_ref, toks, CFG, 8, 12, 16)
    with jax.set_mesh(mesh8):
        params, _, _ = init_sharded_state(TrainSetup(cfg=CFG), mesh8, RNG)
        ssetup = ServeSetup(cfg=CFG)
        state = init_serve_state(ssetup, mesh8, params, 8, 16)
        dstep = jax.jit(make_decode_step(ssetup, mesh8, tpl, batch=8,
                                         capacity=16))
        outs = []
        for t in range(12):
            lg, state = dstep(params, state, toks[:, t:t + 1], jnp.int32(t))
            outs.append(lg[:, 0])
    np.testing.assert_allclose(jnp.stack(outs, 1), ref, atol=1e-4)


def test_context_sharded_decode(mesh8, toks, params_ref):
    """long_500k cell analogue: batch=1, cache sharded over rails."""
    tpl = jax.eval_shape(lambda: T.init_lm(RNG, CFG))
    ref = _ref_decode(params_ref, toks, CFG, 1, 12, 16)
    with jax.set_mesh(mesh8):
        params, _, _ = init_sharded_state(TrainSetup(cfg=CFG), mesh8, RNG)
        ssetup = ServeSetup(cfg=CFG, context_shard=True)
        state = init_serve_state(ssetup, mesh8, params, 1, 16)
        dstep = jax.jit(make_decode_step(ssetup, mesh8, tpl, batch=1,
                                         capacity=16))
        outs = []
        for t in range(12):
            lg, state = dstep(params, state, toks[:1, t:t + 1], jnp.int32(t))
            outs.append(lg[:, 0])
    np.testing.assert_allclose(jnp.stack(outs, 1), ref, atol=1e-4)


def test_context_sharded_ssm_decode(mesh8, toks):
    cfg = get_config("mamba2_370m", smoke=True).replace(dtype="float32")
    params_ref = T.init_lm(RNG, cfg)
    tpl = jax.eval_shape(lambda: T.init_lm(RNG, cfg))
    ref = _ref_decode(params_ref, toks, cfg, 1, 6, 16)
    with jax.set_mesh(mesh8):
        params, _, _ = init_sharded_state(TrainSetup(cfg=cfg), mesh8, RNG)
        ssetup = ServeSetup(cfg=cfg, context_shard=True)
        state = init_serve_state(ssetup, mesh8, params, 1, 16)
        dstep = jax.jit(make_decode_step(ssetup, mesh8, tpl, batch=1,
                                         capacity=16))
        outs = []
        for t in range(6):
            lg, state = dstep(params, state, toks[:1, t:t + 1], jnp.int32(t))
            outs.append(lg[:, 0])
    np.testing.assert_allclose(jnp.stack(outs, 1), ref, atol=1e-4)


def test_prefill(mesh8, toks, params_ref):
    tpl = jax.eval_shape(lambda: T.init_lm(RNG, CFG))
    ref, _ = T.lm_forward(params_ref, {"tokens": toks}, CFG, last_only=True)
    with jax.set_mesh(mesh8):
        params, _, _ = init_sharded_state(TrainSetup(cfg=CFG), mesh8, RNG)
        pstep = jax.jit(make_prefill_step(ServeSetup(cfg=CFG), mesh8, tpl))
        got = pstep(params, {"tokens": toks})
    np.testing.assert_allclose(got, ref, atol=1e-4)


def test_pipeline_parallel_loss(params_ref):
    """GPipe over a pipe axis == reference loss, and it trains."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.parallel.pipeline import make_pipeline_train_step
    cfg = CFG.replace(n_layers=4)
    mesh = jax.make_mesh((4,), ("pipe",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    params = T.init_lm(RNG, cfg)
    batch = {"tokens": jax.random.randint(RNG, (8, 16), 0, cfg.vocab_size,
                                          jnp.int32),
             "targets": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                           cfg.vocab_size, jnp.int32)}
    ref, _ = T.lm_loss(params, batch, cfg, aux_weight=0.0)
    with jax.set_mesh(mesh):
        pp = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, NamedSharding(mesh, P())), params)
        pp["layers"] = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, NamedSharding(mesh, P("pipe"))),
            params["layers"])
        step = jax.jit(make_pipeline_train_step(cfg, mesh, pipe_axis="pipe",
                                                n_micro=4))
        p2, loss = step(pp, batch)
        assert abs(float(loss) - float(ref)) < 1e-4
        _, l2 = step(p2, batch)
        assert float(l2) < float(loss)
