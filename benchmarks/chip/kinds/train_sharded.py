"""Training cells on several chips whose plain reference does not fit on
one: the ``train`` kind's runner, numbers and check, with the reference's
state spread over the cell's chips.

The program's step is built, driven and timed exactly as ``kinds/train.py``
does it.  The reference is driven through the same first steps on the same
batches by the same recipe as ``refcore.train_readings``, in blocks of
``ref_rows_per_block`` rows (a chip's rows in the program, so that each
block's load-balance term is the one a chip's rows give there).  Its
parameters, their float32 copy, gradients and AdamW moments are each split
over the chips along the heads of attention and the experts of an MoE
layer (the vocabulary for the embedding), so that each chip computes its
share of every block.  The arithmetic is the reference's own; only where
its numbers live changes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench import refcore, scopes
from chipbench import spec as sp
from chipbench import traffic as tg

_train = sp.kind("train")
Runner = _train.Runner
compare = _train.compare
attempted = _train.attempted
CHECK_STEPS = _train.CHECK_STEPS

# the scope readers compile a cell's step again by its kind's name; this
# kind's step and feed are the train kind's
scopes._FEEDS.setdefault("train_sharded", scopes._train_feed)

AXIS = "chips"


# the axis of each leaf, counted from its end, that the chips split: query
# and key/value heads, experts, the router's experts, the vocabulary; any
# other leaf (norms, scalars) is held whole on every chip
SPLIT = {"wq": -2, "wk": -2, "wv": -2, "wo": -3, "w_gate": -3, "w_up": -3,
         "w_down": -3, "router": -1, "embed": -2}


def _split(mesh, path, shape) -> NamedSharding:
    name = getattr(path[-1], "key", None)
    axis = SPLIT.get(name)
    if axis is None or len(shape) < -axis \
            or shape[axis] % mesh.devices.size:
        return NamedSharding(mesh, P())
    spec = [None] * len(shape)
    spec[axis] = AXIS
    return NamedSharding(mesh, P(*spec))


def sharded_train_readings(mesh, init, block_loss, key, batches, opt: dict,
                           rows_per_block: int) -> dict:
    """``refcore.train_readings`` with every piece of state split over
    ``mesh``: the same steps, the same readings."""
    tpl = jax.eval_shape(init, key)
    ps = jax.tree_util.tree_map_with_path(
        lambda p, x: _split(mesh, p, x.shape), tpl)
    whole = NamedSharding(mesh, P())
    params = jax.jit(init, out_shardings=ps)(key)
    p0 = params
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, refcore.F32), t), out_shardings=ps)
    m = zeros(params)
    v = zeros(params)
    grad_fn = jax.jit(jax.value_and_grad(block_loss),
                      out_shardings=(whole, ps))
    step_fn = jax.jit(lambda p, g, m_, v_, s: refcore.adamw(p, g, m_, v_, s,
                                                            opt),
                      out_shardings=(ps, ps, ps, whole))
    to_f32 = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: x.astype(refcore.F32), t), out_shardings=ps)
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  out_shardings=ps)
    scale = jax.jit(lambda t, n: jax.tree_util.tree_map(lambda g: g / n, t),
                    out_shardings=ps)
    out = {"losses": []}
    for i, batch in enumerate(batches):
        n_rows = batch["tokens"].shape[0]
        n_tok = batch["tokens"].size
        pf = to_f32(params)
        total, grads = 0.0, None
        for r in range(0, n_rows, rows_per_block):
            blk = [jax.device_put(np.asarray(batch[k][r:r + rows_per_block]),
                                  whole) for k in ("tokens", "targets")]
            lsum, g = grad_fn(pf, *blk)
            total += float(lsum)
            grads = g if grads is None else add(grads, g)
        del pf
        grads = scale(grads, jnp.float32(n_tok))
        params, m, v, gnorm = step_fn(params, grads, m, v,
                                      jnp.float32(i + 1))
        out["losses"].append(total / n_tok)
        if i == 0:
            out["grad_norm"] = float(gnorm)
            out["grad"] = refcore.applied_gradient(m, opt["b1"])
            out["raw_grad_leaves"] = refcore.leaf_norms(grads)
        del grads
    out["change_leaves"] = refcore.change_norms(params, p0)
    return out


def reference_readings(ctx, seed: int, prog: dict = None,
                       prec: str = "f32") -> dict:
    """The plain reference through the same first steps, same batches,
    its state spread over the cell's chips."""
    ref = sp.reference(ctx.conf["reference"])
    model, mix = ctx.model, ctx.mix
    mesh = Mesh(np.array(jax.devices()[:ctx.chips]), (AXIS,))
    batches = [tg.train_batch(mix, model["vocab_size"], seed, i)
               for i in range(CHECK_STEPS)]
    with jax.default_matmul_precision("highest"):
        return sharded_train_readings(
            mesh, lambda k: ref.init(k, model),
            lambda p, t, y: ref.block_loss(p, t, y, model, prec),
            tg.jax_key(seed), batches, mix["opt"], mix["ref_rows_per_block"])


def reference_gaps(ctx, seed: int, prog: dict, control: str = None) -> dict:
    """The numbers against the reference, and with ``control`` those of
    the reference computed in that precision, in the form ``calibrate.py``
    reads for a kind it does not drive as training."""
    ref = reference_readings(ctx, seed)
    out = compare(prog, ref)
    if control:
        out["control"] = compare(reference_readings(ctx, seed, prec=control),
                                 ref)
    return out
