"""Pallas TPU flash-decode: one query token against a long KV cache.

The cache dimension is the grid's innermost axis; each step brings a
[dh, block_k] cache tile into VMEM and folds it into running (m, l, acc)
statistics held in VMEM scratch, i.e. the classic flash-decoding split-K
scheme mapped onto the TPU memory hierarchy (HBM -> VMEM tiles -> VREG
reductions).  GQA is the grid's kv-head axis, and the query block is the
[rep, dh] bundle of query heads sharing one kv head, so the MXU
contractions are [rep, dh] @ [dh, block_k] and
[rep, block_k] @ [dh, block_k]^T.

The caches are the decode state's stacks [L, B, KV, dh, C] (the layout
``models.attention`` stores, cache position minor, so lane-dense and
unpadded), and the kernel attends over one layer of them, picked by an
index in SMEM.  K and V stay in HBM: the kernel DMAs the layer's tiles
itself, double-buffered, each grid step starting the next step's copy.
So the layer scan hands the kernel the stack it carries, and no layer's
cache is sliced out or relaid around the call.  The operands stay q, K,
V, then the int32 mask, then the layer index: the benchmark's
``decode_attention_roofline`` finds the kernel by that list.

Used by the decode_32k / long_500k serve cells; validated against
``ref.decode_attention`` in interpret mode.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.kernels import ref
from repro.kernels.partition import on_mesh

NEG_INF = ref.NEG_INF


def _decode_kernel(q_ref, k_hbm, v_hbm, valid_ref, layer_ref, o_ref, k_buf,
                   v_buf, sem, m_scr, l_scr, acc_scr, *, scale: float,
                   block_k: int, tail: int):
    ib, g, ik = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nkv, nk = pl.num_programs(1), pl.num_programs(2)
    layer = layer_ref[0]
    # the grid runs in order, one block a step; each step starts the DMA
    # of the next step's block into the other slot, so the copy of block
    # n+1 overlaps the arithmetic on block n across (b, g) boundaries too
    step = (ib * nkv + g) * nk + ik
    last = pl.num_programs(0) * nkv * nk - 1

    def copies(b_, g_, ik_, slot, start: bool):
        def run(width):
            off = pl.multiple_of(ik_ * block_k, block_k)
            for j, (src, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                c = pltpu.make_async_copy(
                    src.at[layer, b_, g_, :, pl.ds(off, width)],
                    buf.at[slot, :, pl.ds(0, width)], sem.at[j, slot])
                c.start() if start else c.wait()
        if not tail:
            run(block_k)
            return
        pl.when(ik_ < nk - 1)(lambda: run(block_k))
        pl.when(ik_ == nk - 1)(lambda: run(tail))

    @pl.when(step == 0)
    def _first():
        copies(ib, g, ik, 0, True)

    @pl.when(step < last)
    def _prefetch():
        nxt = ik + 1 == nk
        g1 = jnp.where(nxt, g + 1, g)
        b1 = jnp.where(nxt & (g1 == nkv), ib + 1, ib)
        copies(b1, jnp.where(g1 == nkv, 0, g1), jnp.where(nxt, 0, ik + 1),
               (step + 1) % 2, True)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    slot = step % 2
    copies(ib, g, ik, slot, False)
    q = q_ref[0, 0].astype(jnp.float32) * scale              # [rep, dh]
    k = k_buf[slot].astype(jnp.float32)                      # [dh, bk]
    s = jax.lax.dot(q, k, preferred_element_type=jnp.float32)  # [rep, bk]
    vmask = valid_ref[0] != 0                                # [1, bk]
    s = jnp.where(vmask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1, keepdims=True)
    v = v_buf[slot].astype(jnp.float32)                      # [dh, bk]
    if tail:  # past C the last block's buffer holds stale tiles or the HBM
        v = jnp.where(vmask, v, 0.0)  # buffer's padding: 0 * NaN is NaN
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [rep, dh]
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _flush():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _decode(q, k_cache, v_cache, valid_mask, layer, scale, block_k,
            interpret):
    return _decode_fwd(q, k_cache, v_cache, valid_mask, layer, scale,
                       block_k, interpret)


def _decode_vjp_fwd(q, k_cache, v_cache, valid_mask, layer, scale, block_k,
                    interpret):
    out = _decode_fwd(q, k_cache, v_cache, valid_mask, layer, scale,
                      block_k, interpret)
    return out, (q, k_cache, v_cache, valid_mask, layer)


def _decode_vjp_bwd(scale, block_k, interpret, res, g):
    # pallas_call has no AD rule: recompute through the jnp oracle on the
    # indexed layer (exact same math, asserted allclose in tests); the mask
    # and the layer index are non-float
    import numpy as np
    q, k_cache, v_cache, valid_mask, layer = res

    def oracle(q_, k_, v_):
        k_, v_ = (jax.lax.dynamic_index_in_dim(t, layer, 0, keepdims=False)
                  for t in (k_, v_))
        return ref.decode_attention(q_, k_, v_, valid_mask, scale=scale)

    out, vjp = jax.vjp(oracle, q, k_cache, v_cache)
    dq, dk, dv = vjp(g.astype(out.dtype))
    return (dq, dk, dv, np.zeros(valid_mask.shape, jax.dtypes.float0),
            np.zeros(layer.shape, jax.dtypes.float0))


_decode.defvjp(_decode_vjp_fwd, _decode_vjp_bwd)


def decode_attention(q, k_cache, v_cache, valid_mask, layer, *,
                     scale: Optional[float] = None, block_k: int = 1024,
                     interpret: bool = False) -> jnp.ndarray:
    """q [B,1,H,dh]; k/v_cache [L,B,KV,dh,C], the stacked caches of L
    layers; valid_mask [B,C]; layer, an int32 scalar -> [B,1,H,dh].

    Attends over layer ``layer`` of the stack, which the kernel reads in
    place: a 4-D cache is passed as ``k[None]`` with layer 0.  A capacity
    that ``block_k`` does not divide ends in a shorter block; only the
    mask is padded.  Differentiable: grads recompute through
    ``ref.decode_attention``'s VJP (the Pallas forward has no AD rule).
    """
    dh = q.shape[3]
    c = k_cache.shape[4]
    scale = scale if scale is not None else 1.0 / (dh ** 0.5)
    block_k = min(block_k, c)
    layer = jnp.asarray(layer, jnp.int32)
    return _decode(q, k_cache, v_cache, valid_mask, layer, scale, block_k,
                   interpret)


def _decode_fwd(q, k_cache, v_cache, valid_mask, layer, scale, block_k,
                interpret):
    """``_decode_call`` under the ambient mesh (``kernels.partition``)."""
    def spec(b, m):
        return ((P(b, None, m, None), P(None, b, m, None, None),
                 P(None, b, m, None, None), P(b, None), P()),
                P(b, None, m, None))
    call = functools.partial(_decode_call, scale=scale, block_k=block_k,
                             interpret=interpret)
    return on_mesh(call, q.shape[0], math.gcd(q.shape[2], k_cache.shape[2]),
                   spec)(q, k_cache, v_cache, valid_mask, layer)


def _decode_call(q, k_cache, v_cache, valid_mask, layer, *, scale, block_k,
                 interpret):
    b, _, h, dh = q.shape
    kvh, c = k_cache.shape[2], k_cache.shape[4]
    rep = h // kvh
    nk = -(-c // block_k)

    qt = q.reshape(b, kvh, rep, dh)                         # [B,KV,rep,dh]
    # [B,1,C]: a (1, block_k) tile of the mask meets Mosaic's (8, 128)
    # rule; padded to whole blocks with invalid slots (the cache is not)
    vm, _ = ref._pad_to(valid_mask.astype(jnp.int32)[:, None, :], block_k, 2)

    # the last block's copy when block_k does not divide C: its C % block_k
    # slots, rounded up to the 128-lane tile that Mosaic slices HBM by (the
    # tiled HBM buffer holds those lanes as padding, which the mask hides)
    tail = c % block_k
    if tail and not interpret:
        tail = min(-(-tail // 128) * 128, block_k)
    kernel = functools.partial(_decode_kernel, scale=scale, block_k=block_k,
                               tail=tail)
    o = pl.pallas_call(
        kernel,
        grid=(b, kvh, nk),
        in_specs=[
            pl.BlockSpec((1, 1, rep, dh), lambda b_, g, ik: (b_, g, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 1, block_k), lambda b_, g, ik: (b_, 0, ik)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, dh), lambda b_, g, ik: (b_, g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kvh, rep, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, dh, block_k), k_cache.dtype),
            pltpu.VMEM((2, dh, block_k), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, dh), jnp.float32),
        ],
        # the cross-step prefetch needs the grid run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(qt, k_cache, v_cache, vm, jnp.reshape(layer, (1,)))
    return o.reshape(b, 1, h, dh)
