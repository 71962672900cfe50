"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Each kernel is compiled for one chip of a described ``v5e:2x2`` topology
(no chip needed) and must come out as a Mosaic kernel
(``tpu_custom_call``).  Interpret mode cannot show this: Mosaic refuses
block shapes and primitives that the interpreter runs.  The topology is
described only inside the fixture, never at import.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import ssd_scan

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


def _decode(b, c):  # granite_moe_1b_a400m decode
    return (lambda q, k, v, m: da.decode_attention(q, k, v, m),
            [((b, 1, 16, 64), BF16), ((b, c, 8, 64), BF16),
             ((b, c, 8, 64), BF16), ((b, c), jnp.bool_)])


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
