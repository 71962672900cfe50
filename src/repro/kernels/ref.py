"""Pure-jnp oracles for the Pallas kernels.

``mha`` here is also the portable implementation used on non-TPU backends:
a blocked (flash) attention with a custom flash-style VJP, so neither the
forward nor the backward ever materializes the [Sq, Sk] score matrix.  This
is what makes the 32k prefill / 500k decode cells compile with sane memory
footprints on every backend; the Pallas kernels in this package are the
TPU-tiled versions of exactly these loops and are asserted allclose against
these functions in tests.

Conventions
  q        [B, Sq, H, dh]
  k, v     [B, Sk, KV, dh]        (GQA: H = KV * rep)
  k/v_cache [B, KV, dh, C]        decode caches, the position minor: lane-
                                  dense as stored, read with no relayout
  window   sliding-window size (None = unlimited); causal masking optional
  q_offset absolute position of q[0] (decode/chunked prefill)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _pad_to(x, mult: int, axis: int):
    s = x.shape[axis]
    pad = (-s) % mult
    if pad == 0:
        return x, s
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), s


def _block_mask(qi, ki, *, causal: bool, window: Optional[int]):
    """qi [bq] absolute q positions, ki [bk] absolute k positions -> bool."""
    m = jnp.ones((qi.shape[0], ki.shape[0]), bool)
    if causal:
        m &= ki[None, :] <= qi[:, None]
    if window is not None:
        m &= ki[None, :] > qi[:, None] - window
    return m


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _mha_fwd_blocks(q, k, v, *, causal, window, scale, q_offset,
                    block_q, block_k, kv_valid_len=None):
    """Core blocked forward.  Returns (out [B,Sq,KV,R,dh], lse [B,KV,R,Sq])."""
    b, sq, kvh, rep, dh = q.shape
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_k
    f32 = jnp.float32

    qb = q.reshape(b, nq, block_q, kvh, rep, dh)
    kb = k.reshape(b, nk, block_k, kvh, dh)
    vb = v.reshape(b, nk, block_k, kvh, dh)

    def per_q_block(args):
        qblk, qidx = args  # [B,bq,KV,R,dh], scalar block index

        def kv_step(carry, inp):
            m, l, acc = carry
            kblk, vblk, kidx = inp
            s = jnp.einsum("bqgrd,bkgd->bgrqk", qblk, kblk,
                           preferred_element_type=f32) * scale
            qpos = q_offset + qidx * block_q + jnp.arange(block_q)
            kpos = kidx * block_k + jnp.arange(block_k)
            mask = _block_mask(qpos, kpos, causal=causal, window=window)
            if kv_valid_len is not None:
                mask &= (kpos < kv_valid_len)[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bgrqk,bkgd->bgrqd", p, vblk.astype(f32))
            return (m_new, l_new, acc_new), None

        init = (jnp.full((b, kvh, rep, block_q), NEG_INF, f32),
                jnp.zeros((b, kvh, rep, block_q), f32),
                jnp.zeros((b, kvh, rep, block_q, dh), f32))
        kidxs = jnp.arange(nk)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, init, (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0),
                            kidxs))
        l = jnp.maximum(l, 1e-30)
        out = acc / l[..., None]                        # [B,KV,R,bq,dh]
        lse = m + jnp.log(l)                            # [B,KV,R,bq]
        return out, lse

    qidxs = jnp.arange(nq)
    out, lse = jax.lax.map(per_q_block, (jnp.moveaxis(qb, 1, 0), qidxs))
    # out [NQ,B,KV,R,bq,dh] -> [B,Sq,KV,R,dh]
    out = jnp.moveaxis(out, 0, 3).reshape(b, kvh, rep, sq, dh)
    out = jnp.transpose(out, (0, 3, 1, 2, 4))
    lse = jnp.moveaxis(lse, 0, 3).reshape(b, kvh, rep, sq)
    return out, lse


# ---------------------------------------------------------------------------
# backward (flash style: recompute P per block from saved lse)
# ---------------------------------------------------------------------------


def _mha_bwd_blocks(q, k, v, out, lse, dout, *, causal, window, scale,
                    q_offset, block_q, block_k, kv_valid_len=None):
    b, sq, kvh, rep, dh = q.shape
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_k
    f32 = jnp.float32

    # delta[i] = rowsum(dO_i * O_i)
    delta = jnp.einsum("bqgrd,bqgrd->bgrq", dout.astype(f32), out.astype(f32))
    lse_t = lse  # [B,KV,R,Sq]

    qb = jnp.moveaxis(q.reshape(b, nq, block_q, kvh, rep, dh), 1, 0)
    dob = jnp.moveaxis(dout.reshape(b, nq, block_q, kvh, rep, dh), 1, 0)
    lseb = jnp.moveaxis(lse_t.reshape(b, kvh, rep, nq, block_q), 3, 0)
    deltab = jnp.moveaxis(delta.reshape(b, kvh, rep, nq, block_q), 3, 0)
    kb = jnp.moveaxis(k.reshape(b, nk, block_k, kvh, dh), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, nk, block_k, kvh, dh), 1, 0)

    def p_block(qblk, kblk, lse_blk, qidx, kidx):
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qblk, kblk,
                       preferred_element_type=f32) * scale
        qpos = q_offset + qidx * block_q + jnp.arange(block_q)
        kpos = kidx * block_k + jnp.arange(block_k)
        mask = _block_mask(qpos, kpos, causal=causal, window=window)
        if kv_valid_len is not None:
            mask &= (kpos < kv_valid_len)[None, :]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        return jnp.exp(s - lse_blk[..., None])          # [B,G,R,bq,bk]

    # ---- dq: for each q block, scan kv blocks ----
    def dq_per_q(args):
        qblk, doblk, lse_blk, delta_blk, qidx = args

        def kv_step(dq_acc, inp):
            kblk, vblk, kidx = inp
            p = p_block(qblk, kblk, lse_blk, qidx, kidx)
            dp = jnp.einsum("bqgrd,bkgd->bgrqk", doblk, vblk.astype(f32))
            ds = p * (dp - delta_blk[..., None])
            dq_acc = dq_acc + jnp.einsum("bgrqk,bkgd->bqgrd", ds,
                                         kblk.astype(f32)) * scale
            return dq_acc, None

        dq0 = jnp.zeros((b, block_q, kvh, rep, dh), f32)
        dq, _ = jax.lax.scan(kv_step, dq0,
                             (kb, vb, jnp.arange(nk)))
        return dq

    dq = jax.lax.map(dq_per_q, (qb, dob.astype(f32), lseb, deltab,
                                jnp.arange(nq)))
    dq = jnp.moveaxis(dq, 0, 1).reshape(b, sq, kvh, rep, dh)

    # ---- dk, dv: for each kv block, scan q blocks ----
    def dkv_per_k(args):
        kblk, vblk, kidx = args

        def q_step(carry, inp):
            dk_acc, dv_acc = carry
            qblk, doblk, lse_blk, delta_blk, qidx = inp
            p = p_block(qblk, kblk, lse_blk, qidx, kidx)
            dv_acc = dv_acc + jnp.einsum("bgrqk,bqgrd->bkgd", p, doblk)
            dp = jnp.einsum("bqgrd,bkgd->bgrqk", doblk, vblk.astype(f32))
            ds = p * (dp - delta_blk[..., None])
            dk_acc = dk_acc + jnp.einsum("bgrqk,bqgrd->bkgd", ds,
                                         qblk.astype(f32)) * scale
            return (dk_acc, dv_acc), None

        z = jnp.zeros((b, block_k, kvh, dh), f32)
        (dk, dv), _ = jax.lax.scan(
            q_step, (z, z),
            (qb.astype(f32), dob.astype(f32), lseb, deltab, jnp.arange(nq)))
        return dk, dv

    dk, dv = jax.lax.map(dkv_per_k, (kb, vb, jnp.arange(nk)))
    dk = jnp.moveaxis(dk, 0, 1).reshape(b, sk, kvh, dh)
    dv = jnp.moveaxis(dv, 0, 1).reshape(b, sk, kvh, dh)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _mha(q5, k, v, causal, window, scale, q_offset, block_q, block_k,
         kv_valid_len):
    out, _ = _mha_fwd_blocks(q5, k, v, causal=causal, window=window,
                             scale=scale, q_offset=q_offset, block_q=block_q,
                             block_k=block_k, kv_valid_len=kv_valid_len)
    return out


def _mha_fwd(q5, k, v, causal, window, scale, q_offset, block_q, block_k,
             kv_valid_len):
    out, lse = _mha_fwd_blocks(q5, k, v, causal=causal, window=window,
                               scale=scale, q_offset=q_offset,
                               block_q=block_q, block_k=block_k,
                               kv_valid_len=kv_valid_len)
    return out, (q5, k, v, out, lse)


def _mha_bwd(causal, window, scale, q_offset, block_q, block_k, kv_valid_len,
             res, dout):
    q5, k, v, out, lse = res
    dq, dk, dv = _mha_bwd_blocks(q5, k, v, out, lse, dout, causal=causal,
                                 window=window, scale=scale,
                                 q_offset=q_offset, block_q=block_q,
                                 block_k=block_k, kv_valid_len=kv_valid_len)
    return dq.astype(q5.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_mha.defvjp(_mha_fwd, _mha_bwd)


def mha(q, k, v, *, causal: bool = True, window: Optional[int] = None,
        scale: Optional[float] = None, q_offset: int = 0,
        block_q: int = 512, block_k: int = 512,
        kv_valid_len=None) -> jnp.ndarray:
    """Blocked flash attention (oracle / portable path).

    q [B,Sq,H,dh], k/v [B,Sk,KV,dh] -> [B,Sq,H,dh].  Never materializes
    [Sq,Sk].  kv_valid_len masks trailing cache slots (decode).
    """
    b, sq, h, dh = q.shape
    kvh = k.shape[2]
    assert h % kvh == 0, (h, kvh)
    rep = h // kvh
    scale = scale if scale is not None else 1.0 / (dh ** 0.5)
    block_q = min(block_q, max(sq, 1))
    block_k = min(block_k, max(k.shape[1], 1))

    q5 = q.reshape(b, sq, kvh, rep, dh)
    q5, sq0 = _pad_to(q5, block_q, 1)
    k, sk0 = _pad_to(k, block_k, 1)
    v, _ = _pad_to(v, block_k, 1)
    # padded KV slots must be masked out
    if k.shape[1] != sk0 and kv_valid_len is None:
        kv_valid_len = sk0
    out = _mha(q5, k, v, causal, window, scale, q_offset, block_q, block_k,
               kv_valid_len)
    out = out[:, :sq0].reshape(b, sq0, h, dh).astype(q.dtype)
    return out


# ---------------------------------------------------------------------------
# decode attention oracle (single query position over a long cache)
# ---------------------------------------------------------------------------


def decode_attention(q, k_cache, v_cache, valid_mask, *,
                     scale: Optional[float] = None,
                     block_k: int = 1024, return_stats: bool = False):
    """q [B,1,H,dh]; k/v_cache [B,KV,dh,C]; valid_mask [B,C] bool.

    The cache position is the minor axis, as the decode cache stores it
    (``models.attention.init_kv_cache``).  Blocked flash-decode over the
    cache dimension.  With ``return_stats=True`` returns
    (acc [B,KV,R,dh], m [B,KV,R], l [B,KV,R]) *unnormalized* partials,
    mergeable across cache shards split along the last axis
    (context-parallel decode: the merge is flash-decoding's split-K
    combine).
    """
    b, _, h, dh = q.shape
    kvh, c = k_cache.shape[1], k_cache.shape[3]
    rep = h // kvh
    scale = scale if scale is not None else 1.0 / (dh ** 0.5)
    block_k = min(block_k, c)
    k_cache, c0 = _pad_to(k_cache, block_k, 3)
    v_cache, _ = _pad_to(v_cache, block_k, 3)
    vm, _ = _pad_to(valid_mask, block_k, 1)
    nk = k_cache.shape[3] // block_k
    f32 = jnp.float32
    qr = q.reshape(b, kvh, rep, dh)

    def step(carry, inp):
        m, l, acc = carry
        kblk, vblk, mblk = inp  # [B,KV,dh,bk],[B,KV,dh,bk],[B,bk]
        s = jnp.einsum("bgrd,bgdk->bgrk", qr, kblk,
                       preferred_element_type=f32) * scale
        s = jnp.where(mblk[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, -1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bgrk,bgdk->bgrd", p, vblk.astype(f32))
        return (m_new, l_new, acc_new), None

    kb = jnp.moveaxis(k_cache.reshape(b, kvh, dh, nk, block_k), 3, 0)
    vb = jnp.moveaxis(v_cache.reshape(b, kvh, dh, nk, block_k), 3, 0)
    mb = jnp.moveaxis(vm.reshape(b, nk, block_k), 1, 0)
    init = (jnp.full((b, kvh, rep), NEG_INF, f32),
            jnp.zeros((b, kvh, rep), f32),
            jnp.zeros((b, kvh, rep, dh), f32))
    (m, l, acc), _ = jax.lax.scan(step, init, (kb, vb, mb))
    if return_stats:
        return acc, m, l
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, 1, h, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# SSD oracle (re-export; the canonical implementation lives in models.ssm)
# ---------------------------------------------------------------------------


def ssd_chunked(x, dt, a, b_mat, c_mat, chunk: int, h_init=None):
    from repro.models.ssm import ssd_chunked as _impl
    return _impl(x, dt, a, b_mat, c_mat, chunk, h_init=h_init)
