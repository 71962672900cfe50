"""Plain float32 reference of the Granite 4.0-H hybrid language model
(ibm-granite/granite-4.0-h-small, model type ``granitemoehybrid``).

Per layer, with r the residual multiplier:
    h   = x + r * mixer(norm(x))
    out = h + r * (moe(norm(h)) + shared(norm(h)))
The mixer is a Mamba-2 block (``mamba2.mixer``: in-projection, causal
convolution, the selective state-space recurrence in its chunked dual
form, gated norm, out-projection) or grouped-query attention with no
position embedding (NoPE), causal, softmax scale ``attention_multiplier``.
The layer kinds follow ``layer_pattern``, repeated over depth.  The MoE
layer routes each token by a softmax over all ``n_experts``, keeps the top
k, renormalizes their gates to sum to one (equal to a softmax over the k
chosen logits, as published) and adds the gated SwiGLU outputs of the
experts this chip holds, ids ``first_held`` to ``first_held + n_held``;
a choice of an expert held elsewhere adds nothing here, as in the program.
The shared expert is a SwiGLU of width ``n_shared_experts * d_expert``.
The embedding is scaled by ``embedding_multiplier`` and the tied logits
divided by ``logits_scaling``.

Plain forms, not fast ones: every held expert is computed for every token
and weighted 0 where not chosen, and attention forms each block of 512
queries' full score rows (so that 4096 positions fit beside the weights).
Serving never drops a choice.  Departures from the published model, as
the repository defines it: RMS norms scale by (1 + w) with w initialized
to 0, the vocabulary is laid out padded to a multiple of 256 (masked out
of every logit), and the SSD chunk is this reference's own
(``mamba2.REF_CHUNK``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import spec as sp
from chipbench.refcore import F32, dense, mm, padded_vocab, rms_norm

mamba2 = sp.reference("mamba2")

Q_BLOCK = 512


def _heads(model: dict):
    d = model["d_model"]
    dh = model.get("head_dim") or d // model["n_heads"]
    return model["n_heads"], model["n_kv_heads"], dh


def _held(model: dict):
    moe = model["moe"]
    n = moe.get("n_held") or moe["n_experts"]
    return moe.get("first_held", 0), n


def _pattern(model: dict) -> list:
    assert model["moe"].get("moe_every", 1) == 1, "an MoE after every mixer"
    return list(model["layer_pattern"])


def init(key, model: dict):
    """The parameters as stored, drawn from ``key`` leaf by leaf in the
    program's order: each position of the layer pattern a tree of its
    own, stacked over the periods."""
    d, nl = model["d_model"], model["n_layers"]
    hq, kv, dh = _heads(model)
    moe = model["moe"]
    de = moe["d_expert"]
    ds = moe["n_shared_experts"] * de
    _, held = _held(model)
    wdt = jnp.dtype(model["dtype"])
    pattern = _pattern(model)
    periods = nl // len(pattern)
    k_embed, k_layers = jax.random.split(key, 5)[:2]

    def attention_params(k):
        k1, k2, k3, k4 = jax.random.split(k, 4)
        return {"wq": dense(k1, (d, hq, dh), d, wdt),
                "wk": dense(k2, (d, kv, dh), d, wdt),
                "wv": dense(k3, (d, kv, dh), d, wdt),
                "wo": dense(k4, (hq, dh, d), hq * dh, wdt)}

    def ffn_params(k):
        kr, kg, ku, kd, ks = jax.random.split(k, 5)
        s1, s2, s3 = jax.random.split(ks, 3)
        return {"router": dense(kr, (d, moe["n_experts"]), d, F32),
                "w_gate": dense(kg, (held, d, de), d, wdt),
                "w_up": dense(ku, (held, d, de), d, wdt),
                "w_down": dense(kd, (held, de, d), de, wdt),
                "shared": {"w_gate": dense(s1, (d, ds), d, wdt),
                           "w_up": dense(s2, (d, ds), d, wdt),
                           "w_down": dense(s3, (ds, d), ds, wdt)}}

    def mamba_params(k):
        s = model["ssm"]
        d_inner, h, _, n, g = mamba2.dims(model)
        conv_ch = d_inner + 2 * g * n
        k1, k2, _, k4, k5 = jax.random.split(k, 5)
        u = jax.random.uniform(k4, (h,), F32)
        dt0 = jnp.exp(u * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
        return {"w_in": dense(k1, (d, 2 * d_inner + 2 * g * n + h), d, wdt),
                "conv_w": dense(k2, (s["conv_width"], conv_ch),
                                s["conv_width"], F32),
                "conv_b": jnp.zeros((conv_ch,), F32),
                "a_log": jnp.log(jnp.arange(1, h + 1, dtype=F32)),
                "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
                "d_skip": jnp.ones((h,), F32),
                "norm_w": jnp.zeros((d_inner,), F32),
                "w_out": dense(k5, (d_inner, d), d_inner, wdt)}

    def layer(k, kind):
        ks = jax.random.split(k, 4)
        mixer = (attention_params(ks[0]) if kind == "attn" else
                 mamba_params(ks[0]))
        return {"norm1": jnp.zeros((d,), F32), "mixer": mixer,
                "norm2": jnp.zeros((d,), F32), "ffn": ffn_params(ks[2])}

    pos_keys = jax.random.split(k_layers, len(pattern))
    layers = tuple(
        jax.vmap(lambda k, kind=kind: layer(k, kind))(
            jax.random.split(pos_keys[i], periods))
        for i, kind in enumerate(pattern))
    return {
        "embed": dense(k_embed, (padded_vocab(model["vocab_size"]), d), d,
                       wdt),
        "layers": layers,
        "final_norm": jnp.zeros((d,), F32),
    }


def attention(lp, x, model: dict, prec: str):
    """Causal grouped-query attention, no position embedding."""
    hq, kv, dh = _heads(model)
    b, s, _ = x.shape
    scale = model.get("attention_multiplier") or dh ** -0.5
    q = mm("bsd,dhk->bshk", x, lp["wq"], prec)
    k = jnp.repeat(mm("bsd,dhk->bshk", x, lp["wk"], prec), hq // kv, axis=2)
    v = jnp.repeat(mm("bsd,dhk->bshk", x, lp["wv"], prec), hq // kv, axis=2)
    qb = min(Q_BLOCK, s)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        scores = mm("bqhd,bkhd->bhqk", qi, k, prec) * scale
        rows = i * qb + jnp.arange(qb)[:, None]
        causal = jnp.arange(s)[None, :] <= rows
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return mm("bhqk,bkhd->bqhd", probs, v, prec)

    out = jax.lax.map(block, jnp.arange(s // qb))        # [n,b,qb,h,dh]
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, hq, dh)
    return mm("bqhd,hdk->bqk", out, lp["wo"], prec)


def swiglu(p, x, prec: str):
    hid = jax.nn.silu(mm("btd,df->btf", x, p["w_gate"], prec)) \
        * mm("btd,df->btf", x, p["w_up"], prec)
    return mm("btf,fd->btd", hid, p["w_down"], prec)


def moe(lp, x, model: dict, prec: str):
    """The held experts' part of the routed output, plus the shared
    expert; x [rows, tokens, d]."""
    cfg = model["moe"]
    first, held = _held(model)
    probs = jax.nn.softmax(mm("btd,de->bte", x, lp["router"], prec), -1)
    top, idx = jax.lax.top_k(probs, cfg["top_k"])
    gates = top / jnp.sum(top, -1, keepdims=True)
    weight = jnp.einsum("btk,btke->bte", gates,
                        jax.nn.one_hot(idx, cfg["n_experts"], dtype=F32))
    weight = weight[..., first:first + held]
    hid = jax.nn.silu(mm("btd,edf->btef", x, lp["w_gate"], prec)) \
        * mm("btd,edf->btef", x, lp["w_up"], prec)
    out = mm("btef,efd->bted", hid, lp["w_down"], prec)
    return jnp.einsum("bte,bted->btd", weight, out) \
        + swiglu(lp["shared"], x, prec)


def forward(params, tokens, model: dict, prec: str = "f32"):
    """(logits [b, s, padded vocab] f32, 0.0); the second item, the
    load-balance loss of training, is not computed here."""
    eps, r = model["norm_eps"], model.get("residual_multiplier", 1.0)
    pattern = _pattern(model)
    x = params["embed"][tokens].astype(F32) \
        * model.get("embedding_multiplier", 1.0)

    def layer(x, lp, kind):
        lp = jax.tree_util.tree_map(lambda w: w.astype(F32), lp)
        h = rms_norm(x, lp["norm1"], eps)
        h = (attention(lp["mixer"], h, model, prec) if kind == "attn" else
             mamba2.mixer(lp["mixer"], h, model, prec))
        x = x + r * h
        return x + r * moe(lp["ffn"], rms_norm(x, lp["norm2"], eps), model,
                           prec)

    def period(x, pp):
        for kind, lp in zip(pattern, pp):
            x = jax.checkpoint(lambda x, lp, kind=kind: layer(x, lp, kind))(
                x, lp)
        return x, None

    x, _ = jax.lax.scan(period, x, params["layers"])
    x = rms_norm(x, params["final_norm"], eps)
    logits = mm("bsd,vd->bsv", x, params["embed"], prec)
    return logits / model.get("logits_scaling", 1.0), jnp.float32(0.0)
