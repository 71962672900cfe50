"""Model stacks for all assigned families: dense / MoE / SSM / hybrid LMs,
enc-dec (audio), and VLM (prefix-LM over stubbed patch embeddings).

Layer stacking uses ``jax.lax.scan`` over *periods*: the smallest repeating
unit of (layer-pattern × MoE placement).  Each period position has its own
parameter tree whose leaves are stacked [n_periods, ...], so the HLO is
O(period) regardless of depth — essential to keep 88-layer dry-runs
compileable and remat policies uniform.

``layer_param_fn`` is the FSDP hook: in manual (photonic) mode the trainer
stores flat parameter shards and passes a gather function that is applied
*inside* the scan body, so each period's weights are ring-all-gathered just
in time and the AD transpose emits the matching ring reduce-scatter
(paper Fig 3 traffic falls out of the chain rule).
"""
from __future__ import annotations

import math
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import EncoderConfig, ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (cross_entropy, dense_init, mlp_apply,
                                 mlp_init, padded_vocab, rms_norm,
                                 rms_norm_init)
from repro.scopes import scope

ParamFn = Optional[Callable[[Any], Any]]


# ---------------------------------------------------------------------------
# period structure
# ---------------------------------------------------------------------------


def period_spec(cfg: ModelConfig) -> Tuple[Tuple[str, Optional[str]], ...]:
    """((mixer_kind, ffn_kind), ...) for one period.

    mixer_kind: "attn" | "mamba"; ffn_kind: "dense" | "moe" | None.
    """
    moe_every = cfg.moe.moe_every if cfg.moe else 1
    plen = math.lcm(len(cfg.pattern), moe_every)
    out = []
    for i in range(plen):
        kind = cfg.pattern[i % len(cfg.pattern)]
        if cfg.layer_has_moe(i):
            ffn = "moe"
        elif cfg.d_ff > 0:
            ffn = "dense"
        else:
            ffn = None
        out.append((kind, ffn))
    return tuple(out)


def n_periods(cfg: ModelConfig) -> int:
    plen = len(period_spec(cfg))
    assert cfg.n_layers % plen == 0, (cfg.name, cfg.n_layers, plen)
    return cfg.n_layers // plen


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_sublayer(key, cfg: ModelConfig, spec, dtype, cross: bool):
    kind, ffn = spec
    ks = jax.random.split(key, 4)
    p = {"norm1": rms_norm_init(cfg.d_model)}
    if kind == "attn":
        p["mixer"] = attn.attn_init(ks[0], cfg)
    else:
        p["mixer"] = ssm_mod.ssm_init(ks[0], cfg, dtype)
    if cross:
        p["norm_x"] = rms_norm_init(cfg.d_model)
        p["cross"] = attn.attn_init(ks[1], cfg, cross=True)
    if ffn is not None:
        p["norm2"] = rms_norm_init(cfg.d_model)
        if ffn == "moe":
            p["ffn"] = moe_mod.moe_init(ks[2], cfg, dtype)
        else:
            p["ffn"] = mlp_init(ks[2], cfg.d_model, cfg.d_ff, dtype)
    return p


def _init_stack(key, cfg: ModelConfig, dtype, cross: bool):
    specs = period_spec(cfg)
    np_ = n_periods(cfg)
    keys = jax.random.split(key, len(specs))
    layers = []
    for pos, spec in enumerate(specs):
        pkeys = jax.random.split(keys[pos], np_)
        layers.append(jax.vmap(
            lambda k, s=spec: _init_sublayer(k, cfg, s, dtype, cross))(pkeys))
    return tuple(layers)


def _enc_cfg(e: EncoderConfig, base: ModelConfig) -> ModelConfig:
    """View the encoder as a dense ModelConfig for layer reuse."""
    return base.replace(name=base.name + "-enc", family="dense",
                        n_layers=e.n_layers, d_model=e.d_model,
                        n_heads=e.n_heads, n_kv_heads=e.n_kv_heads,
                        d_ff=e.d_ff, moe=None, ssm=None, layer_pattern=None,
                        frontend=None, encoder=None, head_dim=None)


def init_lm(key, cfg: ModelConfig):
    """Full parameter tree for any family."""
    dtype = jnp.dtype(cfg.dtype)
    vp = padded_vocab(cfg)
    k_e, k_l, k_u, k_f, k_enc = jax.random.split(key, 5)
    params = {
        "embed": dense_init(k_e, (vp, cfg.d_model), dtype, in_axis_size=cfg.d_model),
        "layers": _init_stack(k_l, cfg, dtype, cross=cfg.family == "audio"),
        "final_norm": rms_norm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(k_u, (cfg.d_model, vp), dtype)
    if cfg.frontend is not None:
        params["frontend_proj"] = dense_init(
            k_f, (cfg.frontend.d_embed, cfg.d_model), dtype)
    if cfg.encoder is not None:
        ecfg = _enc_cfg(cfg.encoder, cfg)
        params["encoder"] = {
            "layers": _init_stack(k_enc, ecfg, dtype, cross=False),
            "final_norm": rms_norm_init(ecfg.d_model),
        }
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# sublayer application (full sequence)
# ---------------------------------------------------------------------------


def _residual(x, h, cfg: ModelConfig):
    """x + h, the sub-block's output ``h`` scaled by the config's residual
    multiplier where it sets one."""
    if cfg.residual_multiplier != 1.0:
        h = h * cfg.residual_multiplier
    return x + h


def _apply_sublayer(lp, x, positions, cfg: ModelConfig, spec, *,
                    causal: bool, mask=None, enc_out=None, csp=None,
                    prefix_len: int = 0):
    kind, ffn = spec
    h = rms_norm(x, lp["norm1"], cfg.norm_eps)
    if kind == "attn":
        h = attn.attention(lp["mixer"], h, positions, cfg, causal=causal,
                           window=cfg.sliding_window, mask=mask,
                           prefix_len=prefix_len)
    else:
        h = ssm_mod.ssm_apply(lp["mixer"], h, cfg)
    x = _residual(x, h, cfg)
    if "cross" in lp:
        h = rms_norm(x, lp["norm_x"], cfg.norm_eps)
        h = attn.attention(lp["cross"], h, positions, cfg, context=enc_out)
        x = _residual(x, h, cfg)
    aux = jnp.zeros((), jnp.float32)
    if ffn is not None:
        h = rms_norm(x, lp["norm2"], cfg.norm_eps)
        if ffn == "moe":
            h, aux = moe_mod.moe_apply(lp["ffn"], h, cfg, csp=csp)
        else:
            h = mlp_apply(lp["ffn"], h, cfg.mlp_act)
        x = _residual(x, h, cfg)
    return x, aux


def _remat_wrap(body, remat: str):
    if remat == "full":
        return jax.checkpoint(body)
    if remat == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return body


def stack_apply(layers, x, positions, cfg: ModelConfig, *, causal: bool = True,
                mask=None, enc_out=None, layer_param_fn: ParamFn = None,
                csp=None, prefix_len: int = 0):
    """Scan the period stack over x [B,S,D].  Returns (x, moe_aux_sum)."""
    specs = period_spec(cfg)

    def body(carry, per_params):
        with scope("layer"):
            h = carry
            pp = layer_param_fn(per_params) if layer_param_fn else per_params
            aux = jnp.zeros((), jnp.float32)
            for pos, spec in enumerate(specs):
                h, a = _apply_sublayer(pp[pos], h, positions, cfg, spec,
                                       causal=causal, mask=mask,
                                       enc_out=enc_out, csp=csp,
                                       prefix_len=prefix_len)
                aux = aux + a
            return h, aux

    body = _remat_wrap(body, cfg.remat)
    with scope("layer_scan"):
        x, auxs = jax.lax.scan(body, x, layers)
    return x, jnp.sum(auxs)


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------


def _embed_tokens(params, tokens, cfg: ModelConfig):
    with scope("embed"):
        x = params["embed"][tokens]
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        return x


def _unembed(params, x, cfg: ModelConfig, csp=None):
    if cfg.tie_embeddings:
        w = params["embed"]
        if csp is not None:
            # tied table is stored model-replicated (cheap lookups); shard
            # it on vocab just for the logits contraction — a local slice
            w = csp(w, "vocab", None)
        logits = jnp.einsum("...d,vd->...v", x, w)
    else:
        logits = jnp.einsum("...d,dv->...v", x, params["unembed"])
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def _prefix_inputs(params, batch, cfg: ModelConfig):
    """VLM/audio-frontend: build the input embedding sequence and meta.

    Returns (x [B,S_total,D], n_prefix, targets_mask-positions handled by
    caller via n_prefix).
    """
    tokens = batch["tokens"]
    x = _embed_tokens(params, tokens, cfg)
    n_prefix = 0
    if cfg.family == "vlm":
        patches = batch["patches"].astype(x.dtype)
        pre = jnp.einsum("bte,ed->btd", patches, params["frontend_proj"])
        x = jnp.concatenate([pre, x], axis=1)
        n_prefix = pre.shape[1]
    return x, n_prefix


def encode(params, frames, cfg: ModelConfig, *,
           layer_param_fn: ParamFn = None):
    """Audio/enc-dec encoder over stubbed frame embeddings [B,T,d_embed]."""
    ecfg = _enc_cfg(cfg.encoder, cfg)
    x = jnp.einsum("bte,ed->btd", frames.astype(jnp.dtype(cfg.dtype)),
                   params["frontend_proj"])
    positions = jnp.arange(x.shape[1])[None, :]
    x, _ = stack_apply(params["encoder"]["layers"], x, positions, ecfg,
                       causal=False, layer_param_fn=layer_param_fn)
    return rms_norm(x, params["encoder"]["final_norm"], cfg.norm_eps)


def lm_forward(params, batch, cfg: ModelConfig, *,
               layer_param_fn: ParamFn = None,
               layer_param_fn_enc: ParamFn = None, csp=None,
               last_only: bool = False):
    """Teacher-forced forward.  Returns (logits, moe_aux).

    batch: {"tokens" [B,S]} + family extras ("patches", "frames").
    last_only: emit logits for the final position only (prefill).
    """
    enc_out = None
    if cfg.family == "audio":
        enc_out = encode(params, batch["frames"], cfg,
                         layer_param_fn=layer_param_fn_enc)
    x, n_prefix = _prefix_inputs(params, batch, cfg)
    positions = jnp.arange(x.shape[1])[None, :]
    x, aux = stack_apply(params["layers"], x, positions, cfg, causal=True,
                         enc_out=enc_out, layer_param_fn=layer_param_fn,
                         csp=csp, prefix_len=n_prefix)
    with scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if n_prefix:
            x = x[:, n_prefix:]
        if last_only:
            x = x[:, -1:]
        logits = _unembed(params, x, cfg, csp=csp)
        if csp is not None:
            logits = csp(logits, "batch", None, "vocab")
    return logits, aux


def lm_loss(params, batch, cfg: ModelConfig, *, layer_param_fn: ParamFn = None,
            layer_param_fn_enc: ParamFn = None, csp=None,
            aux_weight: float = 0.01):
    """(loss, metrics) for a teacher-forced batch with 'targets'."""
    logits, aux = lm_forward(params, batch, cfg,
                             layer_param_fn=layer_param_fn,
                             layer_param_fn_enc=layer_param_fn_enc, csp=csp)
    with scope("lm_head"):
        loss, ce = cross_entropy(logits, batch["targets"], cfg.vocab_size)
    loss = loss + aux_weight * aux
    return loss, {"ce": ce, "moe_aux": aux}


# ---------------------------------------------------------------------------
# decode (serving)
# ---------------------------------------------------------------------------


def init_decode_state(cfg: ModelConfig, batch: int, capacity: int):
    """Per-period-position caches, leaves stacked [n_periods, ...]."""
    specs = period_spec(cfg)
    np_ = n_periods(cfg)
    dtype = jnp.dtype(cfg.dtype)
    caches = []
    for kind, _ in specs:
        if kind == "attn":
            cap = capacity
            if cfg.sliding_window is not None:
                cap = min(capacity, cfg.sliding_window)
            one = attn.init_kv_cache(cfg, batch, cap, dtype)
        else:
            one = ssm_mod.init_ssm_cache(cfg, batch)
        caches.append(jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (np_,) + x.shape), one))
    return tuple(caches)


def init_cross_state(params, enc_out, cfg: ModelConfig):
    """Precompute per-layer cross-attention KV over encoder output."""
    specs = period_spec(cfg)

    def per_period(per_params):
        return tuple(
            attn.precompute_cross_kv(per_params[pos]["cross"], enc_out, cfg)
            for pos in range(len(specs)))

    return jax.lax.map(per_period, params["layers"])


def decode_step(params, state, token, pos, cfg: ModelConfig, *,
                cross_state=None, layer_param_fn: ParamFn = None,
                ctx=None):
    """One decode step.  token [B,1] int32, pos scalar int32.

    ctx: optional context-parallel decode info ({"fabric", "offset"}) for
    caches sharded along the sequence dim over rails (long_500k cells).
    Returns (logits [B,1,V], new_state).
    """
    x = _embed_tokens(params, token, cfg)
    specs = period_spec(cfg)

    def body(carry, xs):
        with scope("layer"):
            return layer(carry, xs)

    def layer(carry, xs):
        h = carry
        if cross_state is not None:
            per_params, per_cache, per_cross = xs
        else:
            per_params, per_cache = xs
            per_cross = None
        pp = layer_param_fn(per_params) if layer_param_fn else per_params
        new_cache = []
        for i, (kind, ffn) in enumerate(specs):
            lp = pp[i]
            z = rms_norm(h, lp["norm1"], cfg.norm_eps)
            if kind == "attn":
                z, nc = attn.decode_attention(lp["mixer"], z, pos,
                                              per_cache[i], cfg,
                                              window=cfg.sliding_window,
                                              ctx=ctx)
            else:
                z, nc = ssm_mod.ssm_decode(lp["mixer"], z, per_cache[i], cfg)
            new_cache.append(nc)
            h = _residual(h, z, cfg)
            if "cross" in lp:
                z = rms_norm(h, lp["norm_x"], cfg.norm_eps)
                z, _ = attn.decode_attention(lp["cross"], z, pos, None, cfg,
                                             cross_kv=per_cross[i])
                h = _residual(h, z, cfg)
            if ffn is not None:
                z = rms_norm(h, lp["norm2"], cfg.norm_eps)
                if ffn == "moe":
                    z, _ = moe_mod.moe_apply(lp["ffn"], z, cfg)
                else:
                    z = mlp_apply(lp["ffn"], z, cfg.mlp_act)
                h = _residual(h, z, cfg)
        return h, tuple(new_cache)

    xs = (params["layers"], state) if cross_state is None else \
        (params["layers"], state, cross_state)
    with scope("layer_scan"):
        x, new_state = jax.lax.scan(body, x, xs)
    with scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = _unembed(params, x, cfg)
    return logits, new_state


def prefill(params, batch, cfg: ModelConfig, capacity: int, *,
            layer_param_fn: ParamFn = None, csp=None):
    """Run the full prompt, build decode caches, return last-token logits.

    Implemented as teacher-forced forward + cache construction from the
    projected K/V of each position (single extra pass per layer is folded
    into the forward via a dedicated scan in serve.step; here we return the
    last-token logits only — cache building for the *assigned shapes* is
    exercised through decode_32k/long_500k cells which start from
    ``init_decode_state``).
    """
    return lm_forward(params, batch, cfg, layer_param_fn=layer_param_fn,
                      csp=csp, last_only=True)
