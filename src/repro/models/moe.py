"""Mixture-of-experts FFN: shared + fine-grained routed experts (DeepSeek-MoE).

Dispatch is sort/scatter-based (Megablocks-style adapted to XLA): positions
of each routing choice inside its expert's capacity buffer are computed with
a stable argsort over expert ids, then tokens are scattered into a contiguous
[E, C, D] buffer and gathered back.  This never materializes the GShard
[T, E, C] one-hot, which is what keeps the memory roofline sane at
T = 4k..32k tokens per group.  ``make_dispatch`` keeps the einsum one-hot
around as a small-shape oracle for property tests.

Token grouping: callers pass ``x`` grouped [G, T, D] (G = batch rows or data
shards).  Dispatch/combine are per-group with per-group capacity, making the
E-axis resharding an all-to-all (expert parallelism) rather than a gather.
Per paper §7, EP AllToAll is confined to the scale-up (`model`) mesh axis;
rails never carry it.

The routed path follows DeepSeek-MoE: softmax router, top-k, gates
renormalized over the selected experts; shared experts always execute.
A Switch-style auxiliary load-balance loss is returned for training.

A layer may hold a share of its experts (``MoEConfig.n_held`` from
``first_held``), as one chip of an expert-parallel group does: it routes
over all of them and computes only its own experts' part of the result,
dropping no token for what it does not hold.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.layers import dense_init
from repro.scopes import scope


def moe_capacity(moe: MoEConfig, tokens_per_group: int) -> int:
    """Per-group expert capacity, padded to a multiple of 4 lanes."""
    c = int(tokens_per_group * moe.top_k * moe.capacity_factor / moe.n_experts)
    c = max(c, moe.top_k)
    return (c + 3) // 4 * 4


def moe_init(key, cfg: ModelConfig, dtype):
    moe = cfg.moe
    d = cfg.d_model
    de = moe.d_expert if moe.d_expert is not None else cfg.d_ff
    e = moe.held
    k_r, k_g, k_u, k_d, k_s = jax.random.split(key, 5)
    p = {
        # the router scores every expert, held here or not
        "router": dense_init(k_r, (d, moe.n_experts), jnp.float32),
        # the held experts, stacked on a leading E dim (sharded over `model`)
        "w_gate": dense_init(k_g, (e, d, de), dtype, in_axis_size=d),
        "w_up": dense_init(k_u, (e, d, de), dtype, in_axis_size=d),
        "w_down": dense_init(k_d, (e, de, d), dtype, in_axis_size=de),
    }
    if moe.n_shared_experts:
        ks1, ks2, ks3 = jax.random.split(k_s, 3)
        ds = de * moe.n_shared_experts
        p["shared"] = {
            "w_gate": dense_init(ks1, (d, ds), dtype),
            "w_up": dense_init(ks2, (d, ds), dtype),
            "w_down": dense_init(ks3, (ds, d), dtype),
        }
    return p


def router_topk(logits: jnp.ndarray, moe: MoEConfig, rng: Optional[jax.Array]):
    """logits [G,T,E] -> (gates [G,T,K] renormalized, idx [G,T,K], probs)."""
    if moe.router_jitter and rng is not None:
        logits = logits + moe.router_jitter * jax.random.normal(rng, logits.shape)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    vals, idx = jax.lax.top_k(probs, moe.top_k)  # [G,T,K]
    gates = vals / jnp.maximum(jnp.sum(vals, -1, keepdims=True), 1e-9)
    return gates, idx, probs


def choice_positions(idx: jnp.ndarray, n_experts: int) -> jnp.ndarray:
    """Position of each routing choice inside its expert's buffer.

    idx [G,T,K] -> pos [G,T,K]; choices are prioritized in flattened (T,K)
    order (GShard priority).  O(T·K·log) via stable argsort, no [T,E] blowup.
    """
    g, t, k = idx.shape
    flat = idx.reshape(g, t * k)

    def per_group(e_flat):
        order = jnp.argsort(e_flat, stable=True)           # [TK]
        sorted_e = e_flat[order]
        seg_start = jnp.searchsorted(sorted_e, jnp.arange(n_experts),
                                     side="left")           # [E]
        ranks = jnp.arange(e_flat.shape[0]) - seg_start[sorted_e]
        return jnp.zeros_like(e_flat).at[order].set(ranks)

    return jax.vmap(per_group)(flat).reshape(g, t, k)


def make_dispatch(idx, gates, moe: MoEConfig, capacity: int):
    """Einsum one-hot dispatch/combine — small-shape ORACLE for tests.

    idx [G,T,K], gates [G,T,K] -> dispatch/combine [G,T,E,C].
    """
    e = moe.n_experts
    pos = choice_positions(idx, e)
    fits = (pos < capacity).astype(jnp.float32)
    onehot_e = jax.nn.one_hot(idx, e, dtype=jnp.float32)
    onehot_c = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)
    disp = jnp.einsum("gtke,gtkc->gtec", onehot_e, onehot_c * fits[..., None])
    comb = jnp.einsum("gtk,gtke,gtkc->gtec", gates, onehot_e,
                      onehot_c * fits[..., None])
    return disp, comb


def load_balance_loss(probs, idx, moe: MoEConfig):
    """Switch-Transformer aux loss: E * sum_e f_e * P_e (1.0 when balanced)."""
    e = moe.n_experts
    f = jnp.mean(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=(0, 1, 2))
    p = jnp.mean(probs, axis=(0, 1))
    return e * jnp.sum(f * p)


def _expert_ffn(p, x, act: str):
    """x [E,C',D] stacked per-expert FFN."""
    gv = jnp.einsum("ecd,edf->ecf", x, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", x, p["w_up"])
    gv = jax.nn.gelu(gv, approximate=True) if act == "geglu" else jax.nn.silu(gv)
    return jnp.einsum("ecf,efd->ecd", gv * u, p["w_down"])


def scatter_dispatch(x, idx, pos, fits, n_experts: int, capacity: int):
    """x [G,T,D], idx/pos/fits [G,T,K] -> buffers [G,E,C,D]."""
    g, t, d = x.shape
    k = idx.shape[-1]

    def per_group(xg, ig, pg, fg):
        slot = (ig * capacity + pg).reshape(-1)             # [TK]
        # out-of-capacity choices are parked on a scratch row
        slot = jnp.where(fg.reshape(-1), slot, n_experts * capacity)
        src = jnp.repeat(xg, k, axis=0)                     # [TK, D]
        buf = jnp.zeros((n_experts * capacity + 1, d), x.dtype)
        buf = buf.at[slot].add(src)
        return buf[:-1].reshape(n_experts, capacity, d)

    return jax.vmap(per_group)(x, idx, pos, fits)


def gather_combine(buf, idx, pos, fits, gates):
    """buf [G,E,C,D], idx/pos/fits [G,T,K], gates [G,T,K] -> y [G,T,D].

    The gathered rows stay in the buffer dtype (bf16): with experts sharded
    over `model`, this gather is a model-axis collective — f32 rows would
    double its bytes (§Perf H2 iter 3).  Only the K-way weighted sum runs
    in f32.
    """
    g, e, c, d = buf.shape
    t, k = idx.shape[1], idx.shape[2]

    def per_group(bg, ig, pg, fg, gg):
        slot = (ig * c + pg).reshape(-1)                    # [TK]
        rows = bg.reshape(e * c, d)[jnp.minimum(slot, e * c - 1)]
        w = (gg * fg.astype(gg.dtype)).reshape(t, k, 1).astype(jnp.float32)
        return jnp.sum(rows.reshape(t, k, d).astype(jnp.float32) * w, axis=1)

    return jax.vmap(per_group)(buf, idx, pos, fits, gates)


def moe_apply(p, x, cfg: ModelConfig, *, rng: Optional[jax.Array] = None,
              ep_axis: Optional[str] = None,
              csp=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MoE FFN.  x [G,T,D] grouped tokens -> (y [G,T,D], aux_loss scalar).

    ep_axis: manual-mode mesh axis for expert parallelism (AllToAll on the
    scale-up axis).  csp: optional sharding-constraint hook,
    ``csp(array, *logical_dims)``, used in GSPMD mode to force the E dim onto
    the `model` axis (which makes GSPMD insert the same all-to-all).
    """
    moe = cfg.moe
    gdim, tdim, d = x.shape
    capacity = moe_capacity(moe, tdim)
    with scope("moe_route"):
        logits = jnp.einsum("gtd,de->gte", x.astype(jnp.float32),
                            p["router"].astype(jnp.float32))
        gates, idx, probs = router_topk(logits, moe, rng)
        aux = load_balance_loss(probs, idx, moe)
        pos = choice_positions(idx, moe.n_experts)
        fits = pos < capacity
        if moe.held < moe.n_experts:
            # a share of the experts: a choice of one held elsewhere is
            # not dispatched here and adds nothing; the gates stay those
            # of all k choices
            idx = idx - moe.first_held
            mine = (idx >= 0) & (idx < moe.held)
            idx, fits = jnp.where(mine, idx, 0), fits & mine

    with scope("moe_dispatch"):
        buf = scatter_dispatch(x, idx, pos, fits, moe.held, capacity)
        if csp is not None:
            buf = csp(buf, "groups", "experts", None, None)
        if ep_axis is not None:
            # manual EP: exchange expert shards over the scale-up axis.
            buf = jax.lax.all_to_all(buf, ep_axis, split_axis=1,
                                     concat_axis=2, tiled=True)
        e_eff = buf.shape[1]
        ebuf = jnp.transpose(buf, (1, 0, 2, 3)).reshape(e_eff, -1, d)
    with scope("moe_experts"):
        h = _expert_ffn({k_: v for k_, v in p.items()
                         if k_.startswith("w_")}, ebuf, cfg.mlp_act)
    with scope("moe_combine"):
        h = h.reshape(e_eff, gdim, -1, d).transpose(1, 0, 2, 3)  # [G,E',C',D]
        if ep_axis is not None:
            h = jax.lax.all_to_all(h, ep_axis, split_axis=2, concat_axis=1,
                                   tiled=True)
        if csp is not None:
            h = csp(h, "groups", "experts", None, None)
        y = gather_combine(h, idx, pos, fits, gates).astype(x.dtype)

    if moe.n_shared_experts:
        from repro.models.layers import mlp_apply
        with scope("moe_experts"):
            y = y + mlp_apply(p["shared"], x, cfg.mlp_act)
    return y, aux
