"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Each kernel is compiled for one chip of a described ``v5e:2x2`` topology
(no chip needed) and must come out as a Mosaic kernel
(``tpu_custom_call``).  Interpret mode cannot show this: Mosaic refuses
block shapes and primitives that the interpreter runs.  The topology is
described only inside the fixture, never at import.

The program's decode step is compiled the same way, to check that the
stacked KV cache reaches the flash-decode kernel with no relayout.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.configs.base import get_config
from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import ssd_scan
from repro.models import transformer as tf
from repro.serve.step import ServeSetup, _cache_specs, make_decode_step
from repro.train.step import TrainSetup, dp_axes_of, state_specs

BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _flash(b, s):   # granite_moe_1b_a400m prefill: 16 heads, 8 kv, dh 64
    return (lambda q, k, v: fa.flash_attention(q, k, v),
            [((b, s, 16, 64), BF16), ((b, s, 8, 64), BF16),
             ((b, s, 8, 64), BF16)])


def _decode(b, c):  # granite_moe_1b_a400m decode, caches [B,KV,dh,C]
    return (lambda q, k, v, m: da.decode_attention(q, k, v, m),
            [((b, 1, 16, 64), BF16), ((b, 8, 64, c), BF16),
             ((b, 8, 64, c), BF16), ((b, c), jnp.bool_)])


def _ssd(b, s):     # mamba2_370m: 32 heads, P 64, one group, N 128
    return (lambda x, dt, a, bm, cm: ssd_scan.ssd(x, dt, a, bm, cm, 64),
            [((b, s, 32, 64), F32), ((b, s, 32), F32), ((32,), F32),
             ((b, s, 1, 128), F32), ((b, s, 1, 128), F32)])


@pytest.mark.parametrize("case", [
    _flash(8, 3584),
    _flash(2, 3000),     # ragged: padded to the 512 blocks
    _decode(8, 4096),
    _decode(2, 4500),    # ragged: padded to the 1024 blocks
    _ssd(4, 2048),
], ids=["flash", "flash_ragged", "decode", "decode_ragged", "ssd"])
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = case
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_decode_step_reads_cache_in_place(one_chip, monkeypatch):
    """The jitted decode step at granite widths (2 layers, batch 2,
    capacity 4096, so the flash-decode kernel runs) copies and transposes
    no layer's K or V cache: the scan slices each layer's cache, the
    kernel reads it, and the one-slot update writes it, all in the
    cache's stored layout."""
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    cfg = get_config("granite_moe_1b_a400m").replace(n_layers=2)
    batch, cap = 2, 4096
    device = next(iter(one_chip.device_set))
    mesh = Mesh(np.array([device]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)

    def placed(tree, specs):
        return jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
            tree, specs)

    tpl = jax.eval_shape(lambda: tf.init_lm(jax.random.PRNGKey(0), cfg))
    params = placed(tpl, state_specs(TrainSetup(cfg=cfg), mesh, tpl))
    setup = ServeSetup(cfg=cfg)
    state = jax.eval_shape(lambda: tf.init_decode_state(cfg, batch, cap))
    specs = jax.tree_util.tree_leaves(
        _cache_specs(cfg, dp_axes_of(mesh), context_shard=False),
        is_leaf=lambda x: isinstance(x, PartitionSpec))
    state = placed(state, jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(state), specs))
    with jax.set_mesh(mesh):
        step = make_decode_step(setup, mesh, tpl, batch=batch, capacity=cap)
        text = jax.jit(step).lower(
            params, state, jax.ShapeDtypeStruct((batch, 1), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text
    per_layer = batch * cfg.n_kv_heads * cfg.resolved_head_dim * cap
    relayouts = []
    for m in re.finditer(r"= \w+\[([\d,]*)\]\S* (copy|transpose)\(", text):
        dims = [int(d) for d in m.group(1).split(",") if d]
        if math.prod(dims) == per_layer:
            relayouts.append(m.group(0))
    assert not relayouts, relayouts
