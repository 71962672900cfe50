"""Plain float32 reference of the Granite-3.0 MoE language model
(ibm-granite/granite-3.0-1b-a400m-base).

Per layer: x + attn(norm(x)), then x + moe(norm(x)).  Attention is
grouped-query (query heads share key/value heads in groups), causal, with
rotary embeddings on the two halves of each head and softmax scale
1/sqrt(head_dim).  The MoE layer routes each token by a softmax over all
experts, keeps the top k, renormalizes their gates to sum to one, and adds
the gated SwiGLU outputs of those experts.  Tied embeddings.

Every expert is computed for every token and the unselected ones are
weighted 0: the plainest form, not the fastest.  ``capacity`` reproduces
the training path's per-row expert capacity (choices beyond it, in
token-major priority order, are dropped); serving never drops.
Departures from the published model, as the repository defines it: no
muP multipliers (embedding, attention, residual, logits scaling), RMS
norms scale by (1 + w) with w initialized to 0, and the vocabulary is laid
out padded to a multiple of 256 (masked out of every loss and logit).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.refcore import F32, dense, mm, padded_vocab, rms_norm, \
    rope, token_losses


def _heads(model: dict):
    d = model["d_model"]
    dh = model.get("head_dim") or d // model["n_heads"]
    return model["n_heads"], model["n_kv_heads"], dh


def init(key, model: dict):
    """The parameters as stored, drawn from ``key`` leaf by leaf."""
    d, nl = model["d_model"], model["n_layers"]
    hq, kv, dh = _heads(model)
    moe = model["moe"]
    e, de = moe["n_experts"], moe["d_expert"]
    wdt = jnp.dtype(model["dtype"])
    k_embed, k_layers = jax.random.split(key, 5)[:2]

    def layer(k):
        ks = jax.random.split(k, 4)
        k1, k2, k3, k4 = jax.random.split(ks[0], 4)
        kr, kg, ku, kd, _ = jax.random.split(ks[2], 5)
        return {
            "norm1": jnp.zeros((d,), F32),
            "mixer": {"wq": dense(k1, (d, hq, dh), d, wdt),
                      "wk": dense(k2, (d, kv, dh), d, wdt),
                      "wv": dense(k3, (d, kv, dh), d, wdt),
                      "wo": dense(k4, (hq, dh, d), hq * dh, wdt)},
            "norm2": jnp.zeros((d,), F32),
            "ffn": {"router": dense(kr, (d, e), d, F32),
                    "w_gate": dense(kg, (e, d, de), d, wdt),
                    "w_up": dense(ku, (e, d, de), d, wdt),
                    "w_down": dense(kd, (e, de, d), de, wdt)},
        }

    keys = jax.random.split(jax.random.split(k_layers, 1)[0], nl)
    return {
        "embed": dense(k_embed, (padded_vocab(model["vocab_size"]), d), d,
                       wdt),
        "layers": (jax.vmap(layer)(keys),),
        "final_norm": jnp.zeros((d,), F32),
    }


def capacity(model: dict, tokens: int) -> int:
    """Expert capacity of a row of ``tokens`` in training: tokens x top_k x
    capacity factor / experts, at least top_k, rounded up to 4."""
    moe = model["moe"]
    c = int(tokens * moe["top_k"] * moe["capacity_factor"]
            / moe["n_experts"])
    return (max(c, moe["top_k"]) + 3) // 4 * 4


def attention(lp, x, model: dict, prec: str):
    hq, kv, dh = _heads(model)
    s = x.shape[1]
    pos = jnp.arange(s)[None]
    q = rope(mm("bsd,dhk->bshk", x, lp["wq"], prec), pos, model["rope_theta"])
    k = rope(mm("bsd,dhk->bshk", x, lp["wk"], prec), pos, model["rope_theta"])
    v = mm("bsd,dhk->bshk", x, lp["wv"], prec)
    k = jnp.repeat(k, hq // kv, axis=2)
    v = jnp.repeat(v, hq // kv, axis=2)
    scores = mm("bqhd,bkhd->bhqk", q, k, prec) / jnp.sqrt(jnp.float32(dh))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = mm("bhqk,bkhd->bqhd", probs, v, prec)
    return mm("bqhd,hdk->bqk", out, lp["wo"], prec)


def moe(lp, x, model: dict, prec: str, capacity=None):
    """(y, load-balance loss) of the routed experts; x [rows, tokens, d]."""
    cfg = model["moe"]
    e, k = cfg["n_experts"], cfg["top_k"]
    probs = jax.nn.softmax(mm("btd,de->bte", x, lp["router"], prec), -1)
    top, idx = jax.lax.top_k(probs, k)
    gates = top / jnp.sum(top, -1, keepdims=True)
    choice = jax.nn.one_hot(idx, e, dtype=F32)                # [b,t,k,e]
    aux = e * jnp.sum(jnp.mean(choice, (0, 1, 2)) * jnp.mean(probs, (0, 1)))
    if capacity is not None:
        b, t = idx.shape[:2]
        flat = choice.reshape(b, t * k, e)
        rank = (jnp.cumsum(flat, axis=1) - flat).reshape(b, t, k, e)
        kept = jnp.sum(choice * (rank < capacity), -1)        # [b,t,k]
        gates = gates * kept
    weight = jnp.einsum("btk,btke->bte", gates, choice)
    hid = jax.nn.silu(mm("btd,edf->btef", x, lp["w_gate"], prec)) \
        * mm("btd,edf->btef", x, lp["w_up"], prec)
    out = mm("btef,efd->bted", hid, lp["w_down"], prec)
    return jnp.einsum("bte,bted->btd", weight, out), aux


def forward(params, tokens, model: dict, prec: str = "f32", capacity=None):
    """(logits [b, s, padded vocab] f32, summed load-balance loss)."""
    x = params["embed"][tokens]
    eps = model["norm_eps"]

    @jax.checkpoint
    def layer(x, lp):
        x = x + attention(lp["mixer"], rms_norm(x, lp["norm1"], eps), model,
                          prec)
        y, aux = moe(lp["ffn"], rms_norm(x, lp["norm2"], eps), model, prec,
                     capacity)
        return x + y, aux

    x, aux = jax.lax.scan(layer, x, params["layers"][0])
    x = rms_norm(x, params["final_norm"], eps)
    return mm("bsd,vd->bsv", x, params["embed"], prec), jnp.sum(aux)


def block_loss(params, tokens, targets, model: dict, prec: str = "f32",
               aux_weight: float = 0.01):
    """Summed per-token training loss of the rows, at the training
    capacity, with the block's load-balance term weighted per token so
    that the mean over the batch of equal blocks is the step's loss."""
    logits, aux = forward(params, tokens, model, prec,
                          capacity(model, tokens.shape[1]))
    per_tok = token_losses(logits, targets, model["vocab_size"])
    return jnp.sum(per_tok) + aux_weight * aux * tokens.size
