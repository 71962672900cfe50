"""Pallas TPU flash attention (forward) with GQA, causal and sliding-window
masking, and causal/window block skipping.

TPU co-design notes (vs the CUDA flash algorithm):
  * Tiling is chosen for the MXU (128x128 systolic array): block_q and
    block_k default to 512 sequence rows with the full head_dim as the lane
    dimension, giving [bq, dh] @ [dh, bk] contractions that are multiples of
    the 128-lane MXU tiles for every assigned head_dim (64/128/256).
  * Running max / denominator live in VMEM scratch across the kv grid steps
    (grid dim 2 is "arbitrary" = sequential on TPU), replacing the
    warp-shuffle reductions of the GPU version with vector-unit reductions.
  * GQA is expressed through the k/v BlockSpec index_map (q head h reads kv
    head h // rep) — no repeated K/V is ever materialized in HBM or VMEM.
  * VMEM budget per step: q(bq*dh) + k/v(2*bk*dh) + acc(bq*dh f32)
    + p(bq*bk f32); with defaults and dh=128 that is ~2.4 MB << 16 MB VMEM.

The backward pass reuses the blocked-jnp flash VJP from ``ref.py`` (same
recompute-from-lse scheme flash2 uses); a fused bwd kernel is a listed
§Perf follow-up.  Numerics are validated against ``ref.mha`` in
``tests/test_kernels.py`` via interpret mode.
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


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale: float, causal: bool, window: Optional[int],
               q_offset: int, block_q: int, block_k: int, nk: int,
               kv_len: Optional[int]):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = q_offset + iq * block_q
    k_start = ik * block_k
    relevant = jnp.bool_(True)
    if causal:  # kv block begins after the last q row -> nothing to do
        relevant &= k_start <= q_start + block_q - 1
    if window is not None:  # kv block entirely left of every row's window
        relevant &= k_start + block_k - 1 > q_start - window
    if kv_len is not None:  # kv block made only of padding
        relevant &= k_start < kv_len

    @pl.when(relevant)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [bq, dh]
        k = k_ref[0, 0].astype(jnp.float32)                  # [bk, dh]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        if kv_len is not None:
            mask &= kpos < kv_len
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                  # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)                  # [bk, dh]
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _flush():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _flash_fwd2(q, k, v, **kw):
    """``_flash_call`` under the ambient mesh (``kernels.partition``)."""
    def spec(b, m):
        return (P(b, m, None, None),) * 3, P(b, m, None, None)
    return on_mesh(functools.partial(_flash_call, **kw), q.shape[0],
                   math.gcd(q.shape[1], k.shape[1]), spec)(q, k, v)


def _flash_call(q, k, v, *, causal, window, scale, q_offset,
                block_q, block_k, kv_len, interpret):
    """q [B,H,Sq,dh], k/v [B,KV,Sk,dh] -> o [B,H,Sq,dh]."""
    b, h, sq, dh = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    rep = h // kvh
    nq, nk = sq // block_q, sk // block_k

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, block_q=block_q, block_k=block_k, nk=nk,
        kv_len=kv_len)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_k, dh), lambda b_, h_, iq, ik: (b_, h_ // rep, ik, 0)),
            pl.BlockSpec((1, 1, block_k, dh), lambda b_, h_, iq, ik: (b_, h_ // rep, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom l
            pltpu.VMEM((block_q, dh), jnp.float32),  # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, causal, window, scale, q_offset, block_q, block_k,
           kv_len, interpret):
    return _flash_fwd2(q, k, v, causal=causal, window=window, scale=scale,
                       q_offset=q_offset, block_q=block_q, block_k=block_k,
                       kv_len=kv_len, interpret=interpret)


def _flash_vjp_fwd(q, k, v, causal, window, scale, q_offset, block_q,
                   block_k, kv_len, interpret):
    out = _flash_fwd2(q, k, v, causal=causal, window=window, scale=scale,
                      q_offset=q_offset, block_q=block_q, block_k=block_k,
                      kv_len=kv_len, interpret=interpret)
    return out, (q, k, v)


def _flash_vjp_bwd(causal, window, scale, q_offset, block_q, block_k,
                   kv_len, interpret, res, do):
    """Blocked flash backward via the ref VJP (recompute-from-lse)."""
    q, k, v = res  # [B,H,Sq,dh] / [B,KV,Sk,dh]
    b, h, sq, dh = q.shape
    kvh = k.shape[1]
    rep = h // kvh
    # convert to ref layout [B,S,KV,rep,dh] / [B,S,KV,dh]
    q5 = jnp.transpose(q.reshape(b, kvh, rep, sq, dh), (0, 3, 1, 2, 4))
    kr = jnp.transpose(k, (0, 2, 1, 3))
    vr = jnp.transpose(v, (0, 2, 1, 3))
    out, lse = ref._mha_fwd_blocks(q5, kr, vr, causal=causal, window=window,
                                   scale=scale, q_offset=q_offset,
                                   block_q=block_q, block_k=block_k,
                                   kv_valid_len=kv_len)
    do5 = jnp.transpose(do.reshape(b, kvh, rep, sq, dh), (0, 3, 1, 2, 4))
    dq, dk, dv = ref._mha_bwd_blocks(q5, kr, vr, out, lse, do5, causal=causal,
                                     window=window, scale=scale,
                                     q_offset=q_offset, block_q=block_q,
                                     block_k=block_k, kv_valid_len=kv_len)
    dq = jnp.transpose(dq, (0, 2, 3, 1, 4)).reshape(b, h, sq, dh)
    dk = jnp.transpose(dk, (0, 2, 1, 3))
    dv = jnp.transpose(dv, (0, 2, 1, 3))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None, q_offset: int = 0,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False) -> jnp.ndarray:
    """Public entry.  q [B,Sq,H,dh], k/v [B,Sk,KV,dh] -> [B,Sq,H,dh].

    Ragged lengths are padded to the blocks; padded keys are masked out.
    """
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / (dh ** 0.5)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    q, _ = ref._pad_to(q, block_q, 1)
    k, _ = ref._pad_to(k, block_k, 1)
    v, _ = ref._pad_to(v, block_k, 1)
    kv_len = sk if k.shape[1] != sk else None
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    o = _flash(qt, kt, vt, causal, window, scale, q_offset, block_q, block_k,
               kv_len, interpret)
    return jnp.transpose(o, (0, 2, 1, 3))[:, :sq]
