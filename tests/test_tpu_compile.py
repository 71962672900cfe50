"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Each kernel is compiled for one chip of a described ``v5e:2x2`` topology
(no chip needed) and must come out as a Mosaic kernel
(``tpu_custom_call``).  Interpret mode cannot show this: Mosaic refuses
block shapes and primitives that the interpreter runs.  The topology is
described only inside the fixture, never at import.

The program's decode step is compiled the same way, to check that the
layer scan carries the stacked KV cache and updates it in place, and that
the flash-decode kernel reads each layer inside the stack: no per-layer
slice, copy or relayout of the cache.
"""
import math
import os
import re
import sys
from pathlib import Path

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


def _decode(b, c):  # granite_moe_1b_a400m decode, one cache [B,KV,dh,C]
    return (lambda q, k, v, m: da.decode_attention(q, k[None], v[None], m, 0),
            [((b, 1, 16, 64), BF16), ((b, 8, 64, c), BF16),
             ((b, 8, 64, c), BF16), ((b, c), jnp.bool_)])


def _decode_stacked(n_layers, b, h, dh, c):
    """Stacked caches [L,B,KV,dh,C] and a traced layer index."""
    return (lambda q, k, v, m, i: da.decode_attention(q, k, v, m, i),
            [((b, 1, h, dh), BF16), ((n_layers, b, 8, dh, c), BF16),
             ((n_layers, b, 8, dh, c), BF16), ((b, c), jnp.bool_),
             ((), jnp.int32)])


def _ssd(b, s):     # mamba2_370m: 32 heads, P 64, one group, N 128
    return (lambda x, dt, a, bm, cm: ssd_scan.ssd(x, dt, a, bm, cm, 64),
            [((b, s, 32, 64), F32), ((b, s, 32), F32), ((32,), F32),
             ((b, s, 1, 128), F32), ((b, s, 1, 128), F32)])


@pytest.mark.parametrize("case", [
    _flash(8, 3584),
    _flash(2, 3000),     # ragged: padded to the 512 blocks
    _decode(8, 4096),
    _decode(2, 4500),    # ragged: a shorter last block, the mask padded
    _decode_stacked(24, 16, 16, 64, 4096),   # granite_moe_1b_a400m
    _decode_stacked(1, 64, 32, 128, 4096),   # granite_4_0_h_small
    _ssd(4, 2048),
], ids=["flash", "flash_ragged", "decode", "decode_ragged", "decode_stacked",
        "decode_stacked_dh128", "ssd"])
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = case
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def roofline_signature():
    """The benchmark's pattern for the flash-decode call's operands."""
    chip = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
    if str(chip) not in sys.path:
        sys.path.insert(0, str(chip))
    from chipbench import spec
    return spec.metric_reader("decode_attention_roofline").SIGNATURE


def _with_operand_shapes(text: str, line: str) -> str:
    """The custom call ``line`` with each operand's shape before its name,
    as a profiler trace prints the instruction."""
    shapes = dict(re.findall(r"(%[\w.-]+) = (\w+\[[\d,]*\]\{[^}]*\})", text))
    head, rest = line.split("custom-call(", 1)
    operands, tail = rest.split(")", 1)
    typed = ", ".join(f"{shapes[o]} {o}" for o in operands.split(", "))
    return f"{head}custom-call({typed}){tail}"


def test_decode_step_reads_cache_in_place(one_chip, monkeypatch,
                                          roofline_signature):
    """The jitted decode step at granite widths (2 layers, batch 2,
    capacity 4096, so the flash-decode kernel runs) neither slices,
    copies nor transposes any layer's K or V cache: the layer scan carries
    the stacks, the one-slot update writes into them in place, and the
    kernel, still matched by the benchmark's operand signature, reads its
    layer inside the stack.  Since the step does not donate its state,
    the stacks are copied once, whole, into the step's output."""
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
    per_layer = batch * cfg.n_kv_heads * cfg.resolved_head_dim * cap
    stack = cfg.n_layers * per_layer
    relayouts, slices, stack_copies = [], [], []
    for m in re.finditer(r"%([\w.-]+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(",
                         text):
        name, op = m.group(1), m.group(3)
        size = math.prod(int(d) for d in m.group(2).split(",") if d)
        if size == per_layer and op in ("copy", "transpose"):
            relayouts.append(m.group(0))
        if size == per_layer and (op == "dynamic-slice" or (
                op == "fusion" and "dynamic-slice" in name)):
            slices.append(m.group(0))
        if size == stack and op in ("copy", "copy-start"):
            stack_copies.append(m.group(0))
    assert not relayouts, relayouts
    assert not slices, slices
    assert len(stack_copies) <= 2, stack_copies      # K's and V's, once
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    assert re.search(roofline_signature,
                     _with_operand_shapes(text, calls[0])), calls[0]
