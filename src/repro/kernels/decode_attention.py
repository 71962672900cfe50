"""Pallas TPU flash-decode: one query token against a long KV cache.

The cache dimension is the grid's sequential axis; each step loads a
[dh, block_k] cache tile into VMEM and folds it into running (m, l, acc)
statistics held in VMEM scratch, i.e. the classic flash-decoding split-K
scheme mapped onto the TPU memory hierarchy (HBM -> VMEM tiles -> VREG
reductions).  GQA reads the kv head via the BlockSpec index_map, and the
query block is the [rep, dh] bundle of query heads sharing one kv head, so
the MXU contractions are [rep, dh] @ [dh, block_k] and
[rep, block_k] @ [dh, block_k]^T.

The cache is [B, KV, dh, C], the layout ``models.attention`` stores: the
cache position is minor, so the stored cache is lane-dense and unpadded,
and the kernel reads it as it lies, with no relayout around the call.

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


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, nk: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale              # [rep, dh]
    k = k_ref[0, 0].astype(jnp.float32)                      # [dh, bk]
    s = jax.lax.dot(q, k, preferred_element_type=jnp.float32)  # [rep, bk]
    vmask = valid_ref[0] != 0                                # [1, bk]
    s = jnp.where(vmask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1, keepdims=True)
    v = v_ref[0, 0].astype(jnp.float32)                      # [dh, bk]
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [rep, dh]
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _flush():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _decode(q, k_cache, v_cache, valid_mask, scale, block_k, interpret):
    return _decode_fwd(q, k_cache, v_cache, valid_mask, scale, block_k,
                       interpret)


def _decode_vjp_fwd(q, k_cache, v_cache, valid_mask, scale, block_k,
                    interpret):
    out = _decode_fwd(q, k_cache, v_cache, valid_mask, scale, block_k,
                      interpret)
    return out, (q, k_cache, v_cache, valid_mask)


def _decode_vjp_bwd(scale, block_k, interpret, res, g):
    # pallas_call has no AD rule: recompute through the jnp oracle (exact
    # same math, asserted allclose in tests); the mask is non-float
    import numpy as np
    q, k_cache, v_cache, valid_mask = res
    out, vjp = jax.vjp(
        lambda q_, k_, v_: ref.decode_attention(q_, k_, v_, valid_mask,
                                                scale=scale), q, k_cache,
        v_cache)
    dq, dk, dv = vjp(g.astype(out.dtype))
    return dq, dk, dv, np.zeros(valid_mask.shape, jax.dtypes.float0)


_decode.defvjp(_decode_vjp_fwd, _decode_vjp_bwd)


def decode_attention(q, k_cache, v_cache, valid_mask, *,
                     scale: Optional[float] = None, block_k: int = 1024,
                     interpret: bool = False) -> jnp.ndarray:
    """q [B,1,H,dh]; k/v_cache [B,KV,dh,C]; valid_mask [B,C] -> [B,1,H,dh].

    A capacity that ``block_k`` does not divide is padded with invalid
    slots on the last axis.  Differentiable: grads recompute through
    ``ref.decode_attention``'s VJP (the Pallas forward has no AD rule).
    """
    b, _, h, dh = q.shape
    c = k_cache.shape[3]
    scale = scale if scale is not None else 1.0 / (dh ** 0.5)
    block_k = min(block_k, c)
    k_cache, _ = ref._pad_to(k_cache, block_k, 3)
    v_cache, _ = ref._pad_to(v_cache, block_k, 3)
    vm, _ = ref._pad_to(valid_mask, block_k, 1)
    return _decode(q, k_cache, v_cache, vm, scale, block_k, interpret)


def _decode_fwd(q, k_cache, v_cache, valid_mask, scale, block_k,
                interpret):
    """``_decode_call`` under the ambient mesh (``kernels.partition``)."""
    def spec(b, m):
        return ((P(b, None, m, None), P(b, m, None, None),
                 P(b, m, None, None), P(b, None)), P(b, None, m, None))
    call = functools.partial(_decode_call, scale=scale, block_k=block_k,
                             interpret=interpret)
    return on_mesh(call, q.shape[0], math.gcd(q.shape[2], k_cache.shape[1]),
                   spec)(q, k_cache, v_cache, valid_mask)


def _decode_call(q, k_cache, v_cache, valid_mask, *, scale, block_k,
                 interpret):
    b, _, h, dh = q.shape
    kvh, c = k_cache.shape[1], k_cache.shape[3]
    rep = h // kvh
    nk = c // block_k

    qt = q.reshape(b, kvh, rep, dh)                         # [B,KV,rep,dh]
    # [B,1,C]: a (1, block_k) tile of the mask meets Mosaic's (8, 128) rule
    vm = valid_mask.astype(jnp.int32)[:, None, :]

    kernel = functools.partial(_decode_kernel, scale=scale, nk=nk)
    o = pl.pallas_call(
        kernel,
        grid=(b, kvh, nk),
        in_specs=[
            pl.BlockSpec((1, 1, rep, dh), lambda b_, g, ik: (b_, g, 0, 0)),
            pl.BlockSpec((1, 1, dh, block_k), lambda b_, g, ik: (b_, g, 0, ik)),
            pl.BlockSpec((1, 1, dh, block_k), lambda b_, g, ik: (b_, g, 0, ik)),
            pl.BlockSpec((1, 1, block_k), lambda b_, g, ik: (b_, 0, ik)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, dh), lambda b_, g, ik: (b_, g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kvh, rep, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qt, k_cache, v_cache, vm)
    return o.reshape(b, 1, h, dh)
