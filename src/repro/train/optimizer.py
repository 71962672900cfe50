"""AdamW with ZeRO-sharded state and global-norm clipping.

Optimizer state leaves mirror the stored parameter leaves exactly, so under
either fabric mode they inherit the parameters' FSDP×TP sharding — the
optimizer step is purely elementwise and incurs no collective traffic except
the scalar global-norm reduction (the paper's "short AllReduce calls during
the optimizer step", Fig 3 — management-class traffic).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.scopes import scope


@dataclass(frozen=True)
class OptConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100


def adamw_init(params) -> Dict[str, Any]:
    # zeros_like keeps each parameter's sharding: the moments are built
    # shard by shard, never whole on one device
    zeros = lambda p: jnp.zeros_like(p, jnp.float32)
    return {
        "m": jax.tree_util.tree_map(zeros, params),
        "v": jax.tree_util.tree_map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def lr_at(cfg: OptConfig, step):
    warm = jnp.minimum(step.astype(jnp.float32) / max(cfg.warmup_steps, 1), 1.0)
    return cfg.lr * warm


def global_norm(grads):
    sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
             for g in jax.tree_util.tree_leaves(grads))
    return jnp.sqrt(sq)


def adamw_update(params, grads, opt, cfg: OptConfig):
    """Returns (new_params, new_opt, metrics)."""
    with scope("adamw"):
        return _adamw_update(params, grads, opt, cfg)


def _adamw_update(params, grads, opt, cfg: OptConfig):
    step = opt["step"] + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-9))
    lr = lr_at(cfg, step)
    b1c = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1.0 - cfg.b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m = cfg.b1 * m + (1.0 - cfg.b1) * g
        v = cfg.b2 * v + (1.0 - cfg.b2) * jnp.square(g)
        mh = m / b1c
        vh = v / b2c
        delta = mh / (jnp.sqrt(vh) + cfg.eps)
        if p.ndim >= 2:  # decoupled weight decay on matrices only
            delta = delta + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

    flat_p, td = jax.tree_util.tree_flatten(params)
    flat_g = jax.tree_util.tree_leaves(grads)
    flat_m = jax.tree_util.tree_leaves(opt["m"])
    flat_v = jax.tree_util.tree_leaves(opt["v"])
    new = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = jax.tree_util.tree_unflatten(td, [n[0] for n in new])
    new_m = jax.tree_util.tree_unflatten(td, [n[1] for n in new])
    new_v = jax.tree_util.tree_unflatten(td, [n[2] for n in new])
    return new_p, {"m": new_m, "v": new_v, "step": step}, {
        "grad_norm": gnorm, "lr": lr}
