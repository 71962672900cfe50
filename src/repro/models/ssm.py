"""Mamba-2 (SSD, state-space duality) blocks.

Train/prefill uses the chunked dual form: intra-chunk attention-like einsums
(MXU-friendly) + an inter-chunk recurrence over states, which is the TPU
adaptation of the paper's SSD algorithm (matmul-rich, scan only over
S/chunk steps).  Decode uses the O(1) recurrent form carrying
(conv_state, ssm_state).

Shapes
  x        [B, S, D]
  d_inner  = expand * D;  H = d_inner / head_dim (SSD heads);  N = state_dim
  ssm head dim P = head_dim;  n_groups G shares B/C projections across heads.

``ssm_apply`` runs the chunk scan through ``repro.kernels.ops.ssd``: the
Pallas kernel (``repro.kernels.ssd_scan``) on TPU, ``ssd_chunked`` here
elsewhere; the two are asserted allclose in tests.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models.layers import dense_init, rms_norm, rms_norm_init
from repro.scopes import scope


def ssm_dims(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    assert d_inner % s.head_dim == 0, (d_inner, s.head_dim)
    n_heads = d_inner // s.head_dim
    return d_inner, n_heads, s.head_dim, s.state_dim


def ssm_init(key, cfg: ModelConfig, dtype):
    s = cfg.ssm
    d = cfg.d_model
    d_inner, h, p, n = ssm_dims(cfg)
    g = s.n_groups
    conv_ch = d_inner + 2 * g * n  # conv runs over (x, B, C) channels
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    # dt bias initialized so softplus(dt_bias) spans [1e-3, 1e-1] (mamba2 dflt)
    u = jax.random.uniform(k4, (h,), jnp.float32)
    dt0 = jnp.exp(u * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
    dt_bias = dt0 + jnp.log(-jnp.expm1(-dt0))  # inverse softplus
    return {
        # in_proj -> [z (gate), x, B, C, dt]
        "w_in": dense_init(k1, (d, 2 * d_inner + 2 * g * n + h), dtype,
                           in_axis_size=d),
        "conv_w": dense_init(k2, (s.conv_width, conv_ch), jnp.float32,
                             in_axis_size=s.conv_width),
        "conv_b": jnp.zeros((conv_ch,), jnp.float32),
        "a_log": jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
        "dt_bias": dt_bias,
        "d_skip": jnp.ones((h,), jnp.float32),
        "norm_w": rms_norm_init(d_inner),
        "w_out": dense_init(k5, (d_inner, d), dtype, in_axis_size=d_inner),
    }


def _split_proj(proj, cfg: ModelConfig):
    d_inner, h, p, n = ssm_dims(cfg)
    g = cfg.ssm.n_groups
    z, xbc, dt = jnp.split(proj, [d_inner, 2 * d_inner + 2 * g * n], axis=-1)
    return z, xbc, dt


def _causal_conv(xbc, conv_w, conv_b):
    """Depthwise causal conv over sequence. xbc [B,S,C], conv_w [W,C]."""
    w = conv_w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (w - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xbc.shape[1], :] * conv_w[i][None, None, :]
              for i in range(w))
    return jax.nn.silu(out + conv_b[None, None, :])


def _segsum(dA):
    """Stable segment-sum: out[..., i, j] = sum_{k=j+1..i} dA[..., k], causal.

    dA [..., L] -> [..., L, L] lower-triangular cumulative sums.
    """
    L = dA.shape[-1]
    x = jnp.repeat(dA[..., None], L, axis=-1)  # x[..., k, j] = dA[k]
    mask = jnp.tril(jnp.ones((L, L), bool), k=-1)  # keep k > j
    x = jnp.where(mask, x, 0.0)
    segsum = jnp.cumsum(x, axis=-2)  # [..., i, j] = sum_{k=j+1..i} dA[k]
    mask_out = jnp.tril(jnp.ones((L, L), bool), k=0)
    return jnp.where(mask_out, segsum, -jnp.inf)


def ssd_chunked(x, dt, a, b_mat, c_mat, chunk: int, h_init=None):
    """SSD dual-form over chunks.

    x [B,S,H,P] (pre-discretization), dt [B,S,H] (post-softplus),
    a [H] (negative reals), b_mat/c_mat [B,S,G,N].
    Returns (y [B,S,H,P], final_state [B,H,P,N]).
    """
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    rep = h // g
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    f32 = jnp.float32

    xc = x.reshape(bsz, nc, chunk, h, p).astype(f32)
    dtc = dt.reshape(bsz, nc, chunk, h).astype(f32)
    bc = jnp.repeat(b_mat.reshape(bsz, nc, chunk, g, n), rep, 3).astype(f32)
    cc = jnp.repeat(c_mat.reshape(bsz, nc, chunk, g, n), rep, 3).astype(f32)

    dA = dtc * a[None, None, None, :]          # [B,NC,L,H]
    dA = jnp.moveaxis(dA, -1, 2)               # [B,NC,H,L]
    dA_cs = jnp.cumsum(dA, axis=-1)            # [B,NC,H,L]

    # ---- intra-chunk (attention-like) ----
    L = jnp.exp(_segsum(dA))                   # [B,NC,H,L,L]
    xdt = xc * dtc[..., None]                  # [B,NC,L,H,P]
    y = jnp.einsum("bclhn,bcshn,bchls,bcshp->bclhp", cc, bc, L, xdt)

    # ---- chunk states ----
    decay_to_end = jnp.exp(dA_cs[..., -1:] - dA_cs)  # [B,NC,H,L]
    states = jnp.einsum("bcshn,bchs,bcshp->bchpn", bc, decay_to_end, xdt)

    # ---- inter-chunk recurrence (scan over chunks) ----
    chunk_decay = jnp.exp(dA_cs[..., -1])       # [B,NC,H]
    if h_init is None:
        h_init = jnp.zeros((bsz, h, p, n), f32)

    def step(prev, inp):
        st, dec = inp  # [B,H,P,N], [B,H]
        new = st + dec[..., None, None] * prev
        return new, prev  # emit state *entering* the chunk

    last, prev_states = jax.lax.scan(
        step, h_init.astype(f32),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)  # [B,NC,H,P,N]

    # ---- inter-chunk contribution ----
    in_decay = jnp.exp(dA_cs)                   # decay from chunk start to l
    y = y + jnp.einsum("bclhn,bchpn,bchl->bclhp", cc, prev_states, in_decay)
    return y.reshape(bsz, s, h, p), last


def ssm_apply(p, x, cfg: ModelConfig, *, h_init=None):
    """Full-sequence Mamba-2 block (train/prefill). x [B,S,D] -> [B,S,D]."""
    with scope("ssm"):
        return _ssm_apply(p, x, cfg, h_init)


def _ssm_apply(p, x, cfg: ModelConfig, h_init):
    s_cfg = cfg.ssm
    d_inner, h, pdim, n = ssm_dims(cfg)
    g = s_cfg.n_groups
    proj = jnp.einsum("bsd,de->bse", x, p["w_in"])
    z, xbc, dt = _split_proj(proj, cfg)
    xbc = _causal_conv(xbc.astype(jnp.float32), p["conv_w"], p["conv_b"])
    xin, b_mat, c_mat = jnp.split(xbc, [d_inner, d_inner + g * n], axis=-1)
    bsz, s, _ = x.shape
    xin = xin.reshape(bsz, s, h, pdim)
    b_mat = b_mat.reshape(bsz, s, g, n)
    c_mat = c_mat.reshape(bsz, s, g, n)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"][None, None, :])
    a = -jnp.exp(p["a_log"])
    # pad to a chunk multiple; dt=0 on padding keeps the state exact
    pad = (-s) % s_cfg.chunk_size
    if pad:
        zp = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        xin, dt, b_mat, c_mat = zp(xin), zp(dt), zp(b_mat), zp(c_mat)
    y, _ = ops.ssd(xin, dt, a, b_mat, c_mat, s_cfg.chunk_size, h_init=h_init)
    y = y[:, :s] + p["d_skip"][None, None, :, None] * xin[:, :s]
    y = y.reshape(bsz, s, d_inner)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)), p["norm_w"],
                 cfg.norm_eps)
    return jnp.einsum("bse,ed->bsd", y.astype(x.dtype), p["w_out"])


# ---------------------------------------------------------------------------
# decode (recurrent form)
# ---------------------------------------------------------------------------


def init_ssm_cache(cfg: ModelConfig, batch: int):
    s = cfg.ssm
    d_inner, h, pdim, n = ssm_dims(cfg)
    conv_ch = d_inner + 2 * s.n_groups * n
    return {
        "conv": jnp.zeros((batch, s.conv_width - 1, conv_ch), jnp.float32),
        "state": jnp.zeros((batch, h, pdim, n), jnp.float32),
    }


def ssm_decode(p, x, cache, cfg: ModelConfig):
    """Single-token recurrent step. x [B,1,D] -> (y [B,1,D], new_cache)."""
    with scope("ssm"):
        return _ssm_decode(p, x, cache, cfg)


def _ssm_decode(p, x, cache, cfg: ModelConfig):
    s_cfg = cfg.ssm
    d_inner, h, pdim, n = ssm_dims(cfg)
    g = s_cfg.n_groups
    proj = jnp.einsum("bsd,de->bse", x, p["w_in"])[:, 0]  # [B, E]
    z, xbc, dt = _split_proj(proj, cfg)

    # conv ring: window = [cache, current]
    win = jnp.concatenate([cache["conv"], xbc[:, None, :].astype(jnp.float32)],
                          axis=1)  # [B, W, C]
    conv_out = jnp.einsum("bwc,wc->bc", win, p["conv_w"]) + p["conv_b"]
    conv_out = jax.nn.silu(conv_out)
    new_conv = win[:, 1:]

    xin, b_mat, c_mat = jnp.split(conv_out, [d_inner, d_inner + g * n], -1)
    bsz = x.shape[0]
    xin = xin.reshape(bsz, h, pdim)
    b_mat = jnp.repeat(b_mat.reshape(bsz, g, n), h // g, 1)
    c_mat = jnp.repeat(c_mat.reshape(bsz, g, n), h // g, 1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"][None, :])
    a = -jnp.exp(p["a_log"])
    dA = jnp.exp(dt * a[None, :])  # [B,H]
    with scope("ssm_state"):
        # state' = dA * state + dt * x ⊗ B, read out as C · state'
        new_state = (dA[..., None, None] * cache["state"]
                     + jnp.einsum("bh,bhp,bhn->bhpn", dt, xin, b_mat))
        y = jnp.einsum("bhn,bhpn->bhp", c_mat, new_state)
    y = y + p["d_skip"][None, :, None] * xin
    y = y.reshape(bsz, d_inner)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)), p["norm_w"],
                 cfg.norm_eps)
    out = jnp.einsum("be,ed->bd", y.astype(x.dtype), p["w_out"])[:, None, :]
    return out, {"conv": new_conv, "state": new_state}
