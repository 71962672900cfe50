"""Plain ``jax.numpy`` pieces shared by the benchmark's references.

The references import nothing of the program.  They make the same weights
from the seed by the same recipe (a LeCun-normal draw per leaf, in the
order the published configuration's layers are laid out), compute in
float32 at full matmul precision, and keep parameters in the dtype the
configuration states between optimizer steps.

``mm``'s precision selects how every matrix product is computed: ``f32``
(the reference) or ``fp8`` (the control, the step below the
configurations' bfloat16 that a later change might be tempted to take:
fp8 training's hybrid recipe, e4m3 operands and an e5m2 gradient, each
with a per-tensor scale).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
E4M3, E5M2 = jnp.float8_e4m3fn, jnp.float8_e5m2


def _fp8(x, dtype):
    """x rounded to an fp8 format with a per-tensor scale, in f32."""
    x = x.astype(F32)
    top = float(jnp.finfo(dtype).max)
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(F32) / scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_fp8(eq, a, b):
    return jnp.einsum(eq, _fp8(a, E4M3), _fp8(b, E4M3), precision="highest")


def _mm_fp8_fwd(eq, a, b):
    qa, qb = _fp8(a, E4M3), _fp8(b, E4M3)
    return jnp.einsum(eq, qa, qb, precision="highest"), (qa, qb)


def _mm_fp8_bwd(eq, res, g):
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(eq, x, y, precision="highest"),
                     *res)
    return vjp(_fp8(g, E5M2))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def mm(eq: str, a, b, prec: str = "f32"):
    """einsum of two operands in float32 at full precision, or as fp8
    training computes it (``fp8``): operands in e4m3, the incoming
    gradient in e5m2, each with a per-tensor scale."""
    if prec == "fp8":
        return _mm_fp8(eq, a.astype(F32), b.astype(F32))
    return jnp.einsum(eq, a.astype(F32), b.astype(F32), precision="highest",
                      preferred_element_type=F32)


def dense(key, shape, fan_in: int, dtype):
    """The LeCun-normal draw: N(0, 1) / sqrt(fan_in), stored in dtype."""
    std = 1.0 / jnp.sqrt(jnp.float32(max(fan_in, 1)))
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def padded_vocab(vocab: int) -> int:
    """Rows of the embedding table as it is laid out: a multiple of 256."""
    return (vocab + 255) // 256 * 256


def rms_norm(x, w, eps: float):
    """x / rms(x) * (1 + w): zero-centred scale."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w.astype(F32))


def rope(x, positions, theta: float):
    """Rotary embedding on the two halves of the head dimension."""
    half = x.shape[-1] // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[..., None] * freq
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def token_losses(logits, targets, vocab: int, z_loss: float = 1e-4):
    """Per-token cross-entropy plus z-loss over the unpadded vocabulary."""
    lg = logits[..., :vocab].astype(F32)
    lse = jax.nn.logsumexp(lg, -1)
    gold = jnp.take_along_axis(lg, targets[..., None], -1)[..., 0]
    return lse - gold + z_loss * lse * lse


def applied_gradient(m, b1: float) -> dict:
    """{leaf path: host array} of the first step's gradient as AdamW
    applies it (clipped), from the first moment after that step."""
    flat = jax.tree_util.tree_flatten_with_path(m)[0]
    return {jax.tree_util.keystr(p): np.asarray(x) / (1.0 - b1)
            for p, x in flat}


def change_norms(after, before) -> dict:
    """{leaf path: norm of after - before}, in float32."""
    return leaf_norms(_diff(after, before))


_diff = jax.jit(lambda a, b: jax.tree_util.tree_map(
    lambda x, y: x.astype(F32) - y.astype(F32), a, b))


def leaf_norms(tree) -> dict:
    """{leaf path: float32 Frobenius norm}."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                                for x in xs])([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in
            zip(flat, norms)}


# ---------------------------------------------------------------------------
# AdamW, as the configuration's optimizer states it
# ---------------------------------------------------------------------------


def adamw(params, grads, m, v, step, opt: dict):
    """One AdamW step on stored parameters.

    Global-norm clipping, linear warm-up, bias correction, decoupled
    weight decay on every leaf of two or more dimensions as stored, and
    the parameters rounded back to their stored dtype.  ``step`` counts
    from 1.  Returns (params, m, v, global grad norm before clipping).
    """
    flat_p, tdef = jax.tree_util.tree_flatten(params)
    flat_g = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in flat_g))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    step = jnp.asarray(step, F32)
    lr = opt["lr"] * jnp.minimum(step / max(opt["warmup_steps"], 1), 1.0)
    b1, b2 = opt["b1"], opt["b2"]
    b1c, b2c = 1.0 - b1 ** step, 1.0 - b2 ** step

    def upd(p, g, m_, v_):
        g = g * scale
        m_ = b1 * m_ + (1.0 - b1) * g
        v_ = b2 * v_ + (1.0 - b2) * g * g
        delta = (m_ / b1c) / (jnp.sqrt(v_ / b2c) + opt["eps"])
        if p.ndim >= 2:
            delta = delta + opt["weight_decay"] * p.astype(F32)
        return (p.astype(F32) - lr * delta).astype(p.dtype), m_, v_

    new = [upd(*a) for a in zip(flat_p, flat_g, jax.tree_util.tree_leaves(m),
                                jax.tree_util.tree_leaves(v))]
    return tuple(jax.tree_util.tree_unflatten(tdef, [n[i] for n in new])
                 for i in range(3)) + (gnorm,)


def train_readings(init: Callable, block_loss: Callable, key, batches,
                   opt: dict, rows_per_block: int) -> dict:
    """Drive the reference through ``len(batches)`` optimizer steps.

    ``block_loss(params_f32, tokens, targets)`` returns the summed
    per-token loss of a block of rows; the step's loss is the mean over
    the batch.  Returns the losses, the global gradient norm of step 1,
    step 1's gradient as the optimizer applies it (after clipping, on the
    host) and the per-leaf norms of its raw gradient, and the per-leaf
    norms of the parameters' change over all the steps.
    """
    params = jax.jit(init)(key)
    p0 = params
    m = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, F32), params)
    v = m
    grad_fn = jax.jit(jax.value_and_grad(block_loss))
    step_fn = jax.jit(lambda p, g, m_, v_, s: adamw(p, g, m_, v_, s, opt))
    to_f32 = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: x.astype(F32), t))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    out = {"losses": []}
    for i, batch in enumerate(batches):
        rows = batch["tokens"].shape[0]
        n_tok = batch["tokens"].size
        pf = to_f32(params)
        total, grads = 0.0, None
        for r in range(0, rows, rows_per_block):
            lsum, g = grad_fn(pf, batch["tokens"][r:r + rows_per_block],
                              batch["targets"][r:r + rows_per_block])
            total += float(lsum)
            grads = g if grads is None else add(grads, g)
        del pf
        grads = jax.tree_util.tree_map(lambda g: g / n_tok, grads)
        params, m, v, gnorm = step_fn(params, grads, m, v,
                                      jnp.float32(i + 1))
        out["losses"].append(total / n_tok)
        if i == 0:
            out["grad_norm"] = float(gnorm)
            out["grad"] = applied_gradient(m, opt["b1"])
            out["raw_grad_leaves"] = leaf_norms(grads)
        del grads
    out["change_leaves"] = change_norms(params, p0)
    return out
