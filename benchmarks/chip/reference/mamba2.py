"""Plain float32 reference of the Mamba-2 language model (arXiv:2405.21060).

Per layer: x + out_proj(gated_norm(SSD(conv(in_proj(norm(x)))))), with
in_proj giving [z, x, B, C, dt], a causal depthwise convolution and SiLU
over (x, B, C), dt = softplus(dt + dt_bias), A = -exp(A_log), the
selective state-space recurrence h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,
y_t = C_t h_t + D x_t, then RMS norm of y * silu(z).  Tied embeddings.

The recurrence is computed in its dual chunked form (arXiv:2405.21060,
section 6) with chunks of ``REF_CHUNK`` positions, a size of this file's
own, so that the reference shares no tiling with the kernel.  Departures
from the published model, as the repository defines it: RMS norms scale
by (1 + w) with w initialized to 0, and the vocabulary is laid out padded
to a multiple of 256 (the padding is masked out of every loss and logit).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.refcore import F32, dense, mm, padded_vocab, rms_norm, \
    token_losses

REF_CHUNK = 256


def dims(model: dict):
    s = model["ssm"]
    d_inner = s["expand"] * model["d_model"]
    h = d_inner // s["head_dim"]
    return d_inner, h, s["head_dim"], s["state_dim"], s["n_groups"]


def init(key, model: dict):
    """The parameters as stored, drawn from ``key`` leaf by leaf."""
    d, nl = model["d_model"], model["n_layers"]
    s = model["ssm"]
    d_inner, h, p, n, g = dims(model)
    conv_ch = d_inner + 2 * g * n
    wdt = jnp.dtype(model["dtype"])
    k_embed, k_layers = jax.random.split(key, 5)[:2]

    def layer(k):
        k1, k2, _, k4, k5 = jax.random.split(jax.random.split(k, 4)[0], 5)
        u = jax.random.uniform(k4, (h,), F32)
        dt0 = jnp.exp(u * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
        return {
            "norm1": jnp.zeros((d,), F32),
            "mixer": {
                "w_in": dense(k1, (d, 2 * d_inner + 2 * g * n + h), d, wdt),
                "conv_w": dense(k2, (s["conv_width"], conv_ch),
                                s["conv_width"], F32),
                "conv_b": jnp.zeros((conv_ch,), F32),
                "a_log": jnp.log(jnp.arange(1, h + 1, dtype=F32)),
                "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
                "d_skip": jnp.ones((h,), F32),
                "norm_w": jnp.zeros((d_inner,), F32),
                "w_out": dense(k5, (d_inner, d), d_inner, wdt),
            },
        }

    keys = jax.random.split(jax.random.split(k_layers, 1)[0], nl)
    return {
        "embed": dense(k_embed, (padded_vocab(model["vocab_size"]), d), d,
                       wdt),
        "layers": (jax.vmap(layer)(keys),),
        "final_norm": jnp.zeros((d,), F32),
    }


def ssd(x, dt, a, bm, cm, prec: str):
    """y [b,s,h,p] of the selective recurrence, chunked dual form.

    x [b,s,h,p], dt [b,s,h], a [h] (negative), bm/cm [b,s,g,n].
    """
    b, s, h, p = x.shape
    g, n = bm.shape[2:]
    lc = min(REF_CHUNK, s)
    nc = s // lc
    bm = jnp.repeat(bm, h // g, axis=2).reshape(b, nc, lc, h, n)
    cm = jnp.repeat(cm, h // g, axis=2).reshape(b, nc, lc, h, n)
    xd = (x * dt[..., None]).reshape(b, nc, lc, h, p)
    cs = jnp.cumsum((dt * a).reshape(b, nc, lc, h), axis=2)   # inclusive
    tri = jnp.tril(jnp.ones((lc, lc), bool))
    diff = cs[:, :, :, None, :] - cs[:, :, None, :, :]        # [b,c,l,s,h]
    decay = jnp.where(tri[None, None, :, :, None],
                      jnp.exp(jnp.where(tri[None, None, :, :, None], diff,
                                        0.0)), 0.0)
    scores = mm("bclhn,bcshn->bclsh", cm, bm, prec) * decay
    y = mm("bclsh,bcshp->bclhp", scores, xd, prec)
    # state each chunk adds, and the state entering each chunk
    to_end = jnp.exp(cs[:, :, -1:, :] - cs)                   # [b,c,l,h]
    add = mm("bclhn,bclhp->bchpn", bm * to_end[..., None], xd, prec)
    chunk_decay = jnp.exp(cs[:, :, -1, :])                    # [b,c,h]

    def carry(state, inp):
        st_add, dec = inp
        return dec[..., None, None] * state + st_add, state

    _, entering = jax.lax.scan(
        carry, jnp.zeros((b, h, p, n), F32),
        (jnp.moveaxis(add, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                   # [b,c,h,p,n]
    y = y + mm("bclhn,bchpn->bclhp", cm * jnp.exp(cs)[..., None], entering,
               prec)
    return y.reshape(b, s, h, p)


def mixer(lp, x, model: dict, prec: str):
    d_inner, h, p, n, g = dims(model)
    b, s, _ = x.shape
    proj = mm("bsd,de->bse", x, lp["w_in"], prec)
    z, xbc, dt = (proj[..., :d_inner], proj[..., d_inner:-h],
                  proj[..., -h:])
    w = lp["conv_w"].shape[0]
    pad = jnp.pad(xbc, ((0, 0), (w - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + s] * lp["conv_w"][i] for i in range(w))
    xbc = jax.nn.silu(conv + lp["conv_b"])
    xin = xbc[..., :d_inner].reshape(b, s, h, p)
    bm = xbc[..., d_inner:d_inner + g * n].reshape(b, s, g, n)
    cm = xbc[..., d_inner + g * n:].reshape(b, s, g, n)
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    y = ssd(xin, dt, -jnp.exp(lp["a_log"]), bm, cm, prec)
    y = (y + lp["d_skip"][:, None] * xin).reshape(b, s, d_inner)
    y = rms_norm(y * jax.nn.silu(z), lp["norm_w"], model["norm_eps"])
    return mm("bse,ed->bsd", y, lp["w_out"], prec)


def forward(params, tokens, model: dict, prec: str = "f32"):
    """(logits [b, s, padded vocab] f32, 0.0): params in float32; the
    second item, an MoE model's load-balance loss, is none here."""
    x = params["embed"][tokens]
    eps = model["norm_eps"]

    @jax.checkpoint
    def layer(x, lp):
        return x + mixer(lp["mixer"], rms_norm(x, lp["norm1"], eps), model,
                         prec), None

    x, _ = jax.lax.scan(layer, x, params["layers"][0])
    x = rms_norm(x, params["final_norm"], eps)
    return mm("bsd,vd->bsv", x, params["embed"], prec), jnp.float32(0.0)


def block_loss(params, tokens, targets, model: dict, prec: str = "f32"):
    """Summed per-token training loss of a block of rows."""
    logits, _ = forward(params, tokens, model, prec)
    return jnp.sum(token_losses(logits, targets, model["vocab_size"]))
