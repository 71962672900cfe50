"""GQA/MQA attention: training/prefill (full-sequence) and cached decode.

Mask modes: causal, causal + sliding window (SWA), full (encoder / cross).
Self-attention takes rotary embeddings unless the config has none
(``position_embedding="none"``, NoPE); the softmax scale is the config's
``attention_multiplier`` where it sets one, else 1/sqrt(head_dim).
Decode uses either a full KV cache (capacity = max context) or a ring-buffer
cache of size ``sliding_window`` for SWA archs (true sub-quadratic memory).

The jnp paths here are the reference implementations; perf-critical variants
live in ``repro.kernels`` (flash_attention / decode_attention) and are
validated against these in tests.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init, rope_apply
from repro.scopes import scope

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def attn_init(key, cfg: ModelConfig, cross: bool = False):
    dh = cfg.resolved_head_dim
    d = cfg.d_model
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    return {
        "wq": dense_init(k1, (d, cfg.n_heads, dh), dt, in_axis_size=d),
        "wk": dense_init(k2, (d, cfg.n_kv_heads, dh), dt, in_axis_size=d),
        "wv": dense_init(k3, (d, cfg.n_kv_heads, dh), dt, in_axis_size=d),
        "wo": dense_init(k4, (cfg.n_heads, dh, d), dt, in_axis_size=cfg.n_heads * dh),
    }


def _repeat_kv(k, n_heads: int):
    """[B,S,KV,dh] -> [B,S,H,dh] by repeating each group."""
    kv = k.shape[-2]
    if kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // kv, axis=-2)


def sdpa(q, k, v, *, mask=None, scale: Optional[float] = None):
    """q [B,Sq,H,dh], k/v [B,Sk,H,dh]; softmax in f32."""
    dh = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (dh ** 0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


def make_mask(sq: int, sk: int, *, causal: bool, window: Optional[int],
              q_offset: int = 0):
    """[1,1,Sq,Sk] boolean mask."""
    qi = jnp.arange(sq)[:, None] + q_offset
    ki = jnp.arange(sk)[None, :]
    m = jnp.ones((sq, sk), bool)
    if causal:
        m &= ki <= qi
    if window is not None:
        m &= ki > qi - window
    return m[None, None]


# sequences at or above this length take the blocked-flash path (never
# materializes [Sq,Sk]); below it the plain sdpa is cheaper to compile.
FLASH_MIN_SEQ = 1024


def attention(p, x, positions, cfg: ModelConfig, *, causal: bool = True,
              window: Optional[int] = None,
              context: Optional[jnp.ndarray] = None,
              mask: Optional[jnp.ndarray] = None,
              prefix_len: int = 0):
    """Full-sequence attention (train / prefill / encoder).

    x [B,S,D]; context (for cross-attention) [B,Sk,D] or None (self);
    mask: optional explicit [.,.,Sq,Sk] bool mask — forces the sdpa path.
    prefix_len: prefix-LM semantics — the first ``prefix_len`` rows attend
    bidirectionally *within the prefix* (they precede all text, so they can
    never see text tokens anyway); later rows are causal over everything.
    Composed as causal flash over the full sequence + a small full sdpa over
    the prefix block, so no [S,S] score matrix is ever materialized.
    """
    src = context if context is not None else x
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    if context is None and cfg.position_embedding == "rope":
        q = rope_apply(q, positions, cfg.rope_theta)
        k = rope_apply(k, positions, cfg.rope_theta)
    scale = cfg.attention_multiplier

    if (mask is None and context is None and causal
            and x.shape[1] >= FLASH_MIN_SEQ):
        from repro.kernels import ops  # lazy: kernels never import models.attention
        out = ops.mha(q, k, v, causal=True, window=window, scale=scale)
        if prefix_len:
            pre = sdpa(q[:, :prefix_len],
                       _repeat_kv(k[:, :prefix_len], cfg.n_heads),
                       _repeat_kv(v[:, :prefix_len], cfg.n_heads),
                       scale=scale)
            out = jnp.concatenate([pre.astype(out.dtype), out[:, prefix_len:]],
                                  axis=1)
        return jnp.einsum("bqhd,hdk->bqk", out, p["wo"])

    k = _repeat_kv(k, cfg.n_heads)
    v = _repeat_kv(v, cfg.n_heads)
    if mask is None and context is None and (causal or window is not None):
        mask = make_mask(x.shape[1], src.shape[1], causal=causal, window=window)
        if prefix_len:
            qi = jnp.arange(x.shape[1])[:, None]
            ki = jnp.arange(src.shape[1])[None, :]
            mask |= ((qi < prefix_len) & (ki < prefix_len))[None, None]
    out = sdpa(q, k, v, mask=mask, scale=scale)
    return jnp.einsum("bqhd,hdk->bqk", out, p["wo"])


# ---------------------------------------------------------------------------
# cached decode
# ---------------------------------------------------------------------------


def init_kv_cache(cfg: ModelConfig, batch: int, capacity: int, dtype):
    """k/v [B, KV, dh, C] with the cache position minor; slot_pos [C].

    The position is the longest axis, so as the minor one it fills the
    TPU's 128 lanes with no padding, and it is the layout the stacked
    cache is stored in and the flash-decode kernel reads: no relayout of
    the cache in the layer scan or around the kernel.
    """
    dh = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, cfg.n_kv_heads, dh, capacity), dtype),
        "v": jnp.zeros((batch, cfg.n_kv_heads, dh, capacity), dtype),
        # absolute position stored in each slot; -1 => empty
        "slot_pos": jnp.full((capacity,), -1, jnp.int32),
    }


def decode_attention(p, x, pos, cache, cfg: ModelConfig, *,
                     window: Optional[int] = None,
                     cross_kv: Optional[dict] = None,
                     ctx: Optional[dict] = None):
    """One-token attention. x [B,1,D]; pos scalar int32 (absolute position).

    Full cache: slot = pos.  SWA ring cache: slot = pos % capacity.
    The cache is ``init_kv_cache``'s [B,KV,dh,C]: the new token's K and V
    are written as [B,KV,dh,1] at the slot along the last axis.
    ctx = {"fabric": Fabric, "offset": int32} enables context-parallel
    decode: the cache holds only this rail shard's slot range; partial
    flash-decode stats are merged across shards (split-K combine).  The
    merge stats are small per-head scalars — management-class traffic
    (paper Alg 1: CPU frontend network), emitted as pmax/psum.
    Returns (out [B,1,D], new_cache).
    """
    if cross_kv is not None:  # cross-attention over cached encoder KV
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        k = _repeat_kv(cross_kv["k"], cfg.n_heads)
        v = _repeat_kv(cross_kv["v"], cfg.n_heads)
        out = sdpa(q, k, v, scale=cfg.attention_multiplier)
        return jnp.einsum("bqhd,hdk->bqk", out, p["wo"]), cache

    capacity = cache["k"].shape[3]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k_new = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v_new = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    posv = jnp.reshape(pos, (1,)).astype(jnp.int32)
    if cfg.position_embedding == "rope":
        q = rope_apply(q, posv[None], cfg.rope_theta)
        k_new = rope_apply(k_new, posv[None], cfg.rope_theta)
    scale = cfg.attention_multiplier
    # [B,1,KV,dh] -> the cache's [B,KV,dh,1]
    k_new, v_new = (jnp.moveaxis(t, 1, 3) for t in (k_new, v_new))

    with scope("kv_cache"):
        if ctx is not None:  # context-parallel: write only if this shard owns pos
            slot_local = (pos - ctx["offset"]).astype(jnp.int32)
            owned = (slot_local >= 0) & (slot_local < capacity)
            safe = jnp.clip(slot_local, 0, capacity - 1)
            upd = lambda buf, val: jnp.where(
                owned, jax.lax.dynamic_update_slice_in_dim(
                    buf, val.astype(buf.dtype), safe, axis=3), buf)
            k_cache = upd(cache["k"], k_new)
            v_cache = upd(cache["v"], v_new)
            slot_pos = jnp.where(
                owned, jax.lax.dynamic_update_slice_in_dim(
                    cache["slot_pos"], posv, safe, axis=0), cache["slot_pos"])
        else:
            slot = jnp.where(window is None, pos,
                             pos % capacity).astype(jnp.int32)
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k_new.astype(cache["k"].dtype), slot, axis=3)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v_new.astype(cache["v"].dtype), slot, axis=3)
            slot_pos = jax.lax.dynamic_update_slice_in_dim(
                cache["slot_pos"], posv, slot, axis=0)
        new_cache = {"k": k_cache, "v": v_cache, "slot_pos": slot_pos}

        valid = (slot_pos >= 0) & (slot_pos <= pos)
        if window is not None:
            valid &= slot_pos > pos - window

    if ctx is not None:
        from repro.kernels import ref as kref
        b, _, h, dh = q.shape
        vm = jnp.broadcast_to(valid[None, :], (b, capacity))
        acc, m, l = kref.decode_attention(q, k_cache, v_cache, vm,
                                          scale=scale, return_stats=True)
        fab = ctx["fabric"]
        m_g = fab.pmax(m)
        scalev = jnp.exp(m - m_g)
        l_g = fab.all_reduce(l * scalev)
        acc_g = fab.all_reduce(acc * scalev[..., None])
        out = (acc_g / jnp.maximum(l_g, 1e-30)[..., None]) \
            .reshape(b, 1, h, dh).astype(q.dtype)  # (KV,R)-major == H order
    elif capacity >= 4096:  # long caches: blocked flash-decode, no repeat_kv
        from repro.kernels import ops
        vm = jnp.broadcast_to(valid[None, :], (q.shape[0], capacity))
        out = ops.decode_attention(q, k_cache, v_cache, vm, scale=scale)
    else:  # sdpa's [B,C,H,dh], made in the one copy the repeat makes
        k = _repeat_kv(jnp.moveaxis(k_cache, 3, 1), cfg.n_heads)
        v = _repeat_kv(jnp.moveaxis(v_cache, 3, 1), cfg.n_heads)
        mask = valid[None, None, None, :]  # [1,1,1,capacity]
        out = sdpa(q, k, v, mask=mask, scale=scale)
    return jnp.einsum("bqhd,hdk->bqk", out, p["wo"]), new_cache


def precompute_cross_kv(p, context, cfg: ModelConfig):
    """Cache encoder-side K/V once per request (enc-dec decode)."""
    return {
        "k": jnp.einsum("bsd,dhk->bshk", context, p["wk"]),
        "v": jnp.einsum("bsd,dhk->bshk", context, p["wv"]),
    }
