"""Distributed train-step builders: photonic rails (manual rings) vs EPS.

Photonic mode (the paper's system):
  * ``shard_map`` manual over the rail axes; the scale-up ``model`` axis
    stays GSPMD-auto (TP/EP collectives are electrical, paper Fig 1).
  * Parameters are stored FSDP-sharded along each leaf's rail-divisible dim;
    inside the layer scan they are ring-all-gathered just in time
    (paper phase "DP AllGather") and the AD transpose emits the ring
    reduce-scatter for gradients (phase "DP ReduceScatter").
  * Scalar reductions (loss, metrics, grad-norm) are management traffic
    (paper Alg 1 line 2-4: CPU frontend network), emitted as psum.
  * Multi-pod: default is hierarchical FSDP over ("pod","data") — composed
    rings, fully circuit-legal.  ``hsdp=True`` switches to HSDP: shard over
    "data" only, replicate across pods, and synchronize with an explicit
    cross-pod ring AllReduce that supports int8 gradient compression with
    error feedback (beyond-paper optimization, EXPERIMENTS.md §Perf).

EPS mode (electrical baseline): identical math under plain GSPMD — params
carry the same FSDP×TP NamedShardings and XLA inserts its free-form
collectives (packet-switched all-to-all connectivity).

All sharding metadata (which dim is FSDP, which is TP) is derived ONCE from
the *global* parameter template — never from local shard shapes, whose dim
ranking can differ.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.fabric import Fabric
from repro.models import transformer as tf
from repro.parallel import sharding as sh
from repro.train.optimizer import OptConfig, adamw_init, adamw_update


@dataclass(frozen=True)
class TrainSetup:
    cfg: ModelConfig
    fabric: str = "photonic"           # "photonic" | "eps"
    hsdp: bool = False                 # pod-replicated params + explicit AR
    compress_pod_grads: bool = False   # int8 + error feedback on pod AR
    accum: int = 1                     # gradient accumulation microbatches
    # both ICI link directions per ring (beyond-paper, §Perf H3); False =
    # paper-faithful unidirectional rings
    bidirectional_rings: bool = False
    opt: OptConfig = field(default_factory=OptConfig)


def mesh_axes(mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def rail_axes_of(mesh, hsdp: bool) -> Tuple[str, ...]:
    ax = mesh_axes(mesh)
    if "pod" in ax and not hsdp:
        return ("pod", "data")
    return ("data",)


def dp_axes_of(mesh) -> Tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh_axes(mesh) else ("data",)


def _sizes(mesh, axes):
    ax = mesh_axes(mesh)
    return tuple(ax[a] for a in axes)


# ---------------------------------------------------------------------------
# sharding metadata from the GLOBAL parameter template
# ---------------------------------------------------------------------------


def meta_trees(params_tpl, *, rails, n_rails: int, model_size: int):
    """(fd_tree, td_tree) of per-leaf FSDP/TP dims over the global template."""
    fd = sh._walk(params_tpl, lambda pstr, leaf, st: sh.leaf_spec(
        pstr, leaf.shape, n_rails=n_rails, rail_axes=rails,
        model_size=model_size, stacked=st)[1])
    td = sh._walk(params_tpl, lambda pstr, leaf, st: sh.leaf_spec(
        pstr, leaf.shape, n_rails=n_rails, rail_axes=rails,
        model_size=model_size, stacked=st)[2])
    return fd, td


def specs_from_meta(params_tpl, fd_tree, td_tree, rails,
                    include_model: bool = True):
    ra = rails if len(rails) > 1 else rails[0]

    def fn(leaf, fd, td):
        spec = [None] * leaf.ndim
        if fd is not None:
            spec[fd] = ra
        if include_model and td is not None:
            spec[td] = sh.MODEL_AXIS
        return P(*spec)

    return jax.tree_util.tree_map(fn, params_tpl, fd_tree, td_tree,
                                  is_leaf=lambda x: x is None)


def _gather_with_meta(tree, fd_tree, td_tree, fab: Fabric, *, dim_off=0):
    """Ring-gather each sharded leaf; TP-constrain.  dim_off=-1 for period
    slices whose leading stack dim was consumed by the scan."""

    def fn(leaf, fd, td):
        if fd is not None:
            leaf = fab.all_gather(leaf, axis=fd + dim_off)
        if td is not None:
            cons = [None] * leaf.ndim
            cons[td + dim_off] = sh.MODEL_AXIS
            leaf = jax.lax.with_sharding_constraint(leaf, P(*cons))
        return leaf

    return jax.tree_util.tree_map(fn, tree, fd_tree, td_tree,
                                  is_leaf=lambda x: x is None)


def _fixup_grads(grads, fd_tree, fab: Fabric):
    """Ring-AllReduce cotangents of rail-replicated leaves (check_vma=False
    emits none automatically).  Paper-class: small optimizer-adjacent ARs."""

    def fn(g, fd):
        return fab.all_reduce(g) if fd is None else g

    return jax.tree_util.tree_map(fn, grads, fd_tree,
                                  is_leaf=lambda x: x is None)


# ---------------------------------------------------------------------------
# compressed cross-pod AllReduce (HSDP)
# ---------------------------------------------------------------------------


def compressed_pod_allreduce(grads, ef, pod_fab: Fabric):
    """int8 + error-feedback cross-pod gradient AllReduce.

    Returns (synced_grads_mean, new_ef).  Transport is int8 (4x fewer rail
    bytes than f32); quantization error accumulates into ``ef`` and is
    re-injected next step, keeping convergence unbiased (error feedback).
    """
    npod = pod_fab.n_shards

    def one(g, e):
        x = g.astype(jnp.float32) + e
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
        q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        deq = q.astype(jnp.float32) * scale
        new_e = x - deq
        qs = pod_fab.all_gather(q[None], axis=0)            # [npod, ...]
        ss = pod_fab.all_gather(scale.reshape(1, 1), axis=0)  # [npod, 1]
        # plain sum: the loss is already scaled by 1/n_dp_global, which
        # includes the pod factor
        summed = jnp.sum(qs.astype(jnp.float32)
                         * ss.reshape((npod,) + (1,) * g.ndim), axis=0)
        return summed.astype(g.dtype), new_e

    flat_g, td = jax.tree_util.tree_flatten(grads)
    flat_e = jax.tree_util.tree_leaves(ef)
    pairs = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return (jax.tree_util.tree_unflatten(td, [p[0] for p in pairs]),
            jax.tree_util.tree_unflatten(td, [p[1] for p in pairs]))


# ---------------------------------------------------------------------------
# batch specs
# ---------------------------------------------------------------------------


def build_batch_specs(cfg: ModelConfig, dp_axes):
    ba = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    specs = {"tokens": P(ba, None), "targets": P(ba, None)}
    if cfg.family == "vlm":
        specs["patches"] = P(ba, None, None)
    if cfg.family == "audio":
        specs["frames"] = P(ba, None, None)
    return specs


# ---------------------------------------------------------------------------
# train-step builders
# ---------------------------------------------------------------------------


def make_train_step(setup: TrainSetup, mesh, params_tpl):
    """step(params, opt, ef, batch) -> (params, opt, ef, metrics).

    ``params_tpl`` is a (Shape)DtypeStruct tree of the GLOBAL parameters —
    obtainable via ``jax.eval_shape(init_lm, ...)`` — used to fix the
    sharding metadata once.
    """
    if setup.fabric == "eps":
        return _make_eps_step(setup, mesh)

    cfg = setup.cfg
    ax = mesh_axes(mesh)
    model_size = ax[sh.MODEL_AXIS]
    dp_axes = dp_axes_of(mesh)
    n_dp = math.prod(_sizes(mesh, dp_axes))
    rails = rail_axes_of(mesh, setup.hsdp)
    fab = Fabric(rails, _sizes(mesh, rails), "photonic",
                 bidirectional=setup.bidirectional_rings)
    pod_fab = Fabric(("pod",), (ax["pod"],), "photonic") \
        if (setup.hsdp and "pod" in ax) else None
    manual_axes = set(dp_axes)

    fd_tree, td_tree = meta_trees(params_tpl, rails=rails,
                                  n_rails=fab.n_shards, model_size=model_size)
    pspecs = specs_from_meta(params_tpl, fd_tree, td_tree, rails,
                             include_model=False)
    csp = sh.make_csp(rails, manual_rails=True)

    top_keys = [k for k in params_tpl if k != "layers"]

    def gfn(period_params):  # decoder layers: leading stack dim consumed
        return _gather_with_meta(period_params, fd_tree["layers"],
                                 td_tree["layers"], fab, dim_off=-1)

    gfn_enc = None
    if "encoder" in params_tpl:
        def gfn_enc(period_params):
            return _gather_with_meta(period_params,
                                     fd_tree["encoder"]["layers"],
                                     td_tree["encoder"]["layers"], fab,
                                     dim_off=-1)

    def loss_fn(stored, batch):
        """LOCAL loss / n_dp — no psum in the differentiated path.

        With check_vma=False, psum is its own transpose, so a psum'd loss
        would scale every cotangent by n_dp.  Cross-device gradient
        accumulation instead happens exactly once, through the ring
        reduce-scatter that is the transpose of the parameter all-gather.
        """
        top = {k: stored[k] for k in top_keys}
        top = _gather_with_meta(top, {k: fd_tree[k] for k in top_keys},
                                {k: td_tree[k] for k in top_keys}, fab)
        if "encoder" in top:
            # encoder layer stacks stay stored; gathered per period by gfn_enc
            top["encoder"] = dict(top["encoder"],
                                  layers=stored["encoder"]["layers"])
        params = dict(top, layers=stored["layers"])
        loss, metrics = tf.lm_loss(params, batch, cfg, layer_param_fn=gfn,
                                   layer_param_fn_enc=gfn_enc, csp=csp)
        return loss / n_dp, {"ce": metrics["ce"], "moe_aux": metrics["moe_aux"]}

    def _globalize(local_loss_scaled, metrics):
        """Management traffic: scalar psums OUTSIDE the grad path."""
        loss_g = jax.lax.psum(local_loss_scaled, tuple(manual_axes))
        ce_g = jax.lax.psum(metrics["ce"], tuple(manual_axes)) / n_dp
        return {"loss": loss_g, "ce": ce_g, "moe_aux": metrics["moe_aux"]}

    def grads_fn(stored, batch):
        if setup.accum > 1:
            def micro(carry, mb):
                g_acc, l_acc = carry
                (l, m), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(stored, mb)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                return (g_acc, l_acc + l), m
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), stored)
            mbs = jax.tree_util.tree_map(
                lambda x: x.reshape((setup.accum, x.shape[0] // setup.accum)
                                    + x.shape[1:]), batch)
            (g, loss), ms = jax.lax.scan(micro, (zeros, jnp.float32(0)), mbs)
            g = jax.tree_util.tree_map(lambda x: x / setup.accum, g)
            metrics = _globalize(loss / setup.accum,
                                 jax.tree_util.tree_map(lambda x: x[-1], ms))
        else:
            (loss, m), g = jax.value_and_grad(
                loss_fn, has_aux=True)(stored, batch)
            metrics = _globalize(loss, m)
        g = _fixup_grads(g, fd_tree, fab)
        return g, metrics

    batch_specs = build_batch_specs(cfg, dp_axes)

    def step(params, opt, ef, batch):
        bspecs = {k: batch_specs[k] for k in batch}
        inner = jax.shard_map(
            grads_fn, mesh=mesh, in_specs=(pspecs, bspecs),
            out_specs=(pspecs, P()), axis_names=manual_axes, check_vma=False)
        grads, metrics = inner(params, batch)
        if pod_fab is not None:
            # params are pod-replicated in HSDP mode: manual over "pod" only;
            # the "data" sharding of each leaf stays GSPMD-auto inside.
            nospec = jax.tree_util.tree_map(lambda _: P(), grads)
            if setup.compress_pod_grads:
                sync = jax.shard_map(
                    lambda g, e: compressed_pod_allreduce(g, e, pod_fab),
                    mesh=mesh, in_specs=(nospec, nospec),
                    out_specs=(nospec, nospec),
                    axis_names={"pod"}, check_vma=False)
                grads, ef = sync(grads, ef)
            else:
                sync = jax.shard_map(
                    lambda g: jax.tree_util.tree_map(pod_fab.all_reduce, g),
                    mesh=mesh, in_specs=(nospec,), out_specs=nospec,
                    axis_names={"pod"}, check_vma=False)
                grads = sync(grads)
        params, opt, om = adamw_update(params, grads, opt, setup.opt)
        return params, opt, ef, {**metrics, **om}

    return step


def _make_eps_step(setup: TrainSetup, mesh):
    cfg = setup.cfg
    dp_axes = dp_axes_of(mesh)
    csp = sh.make_csp(dp_axes, manual_rails=False)

    def step(params, opt, ef, batch):
        def loss_fn(p):
            return tf.lm_loss(p, batch, cfg, csp=csp)
        (loss, m), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, opt, om = adamw_update(params, grads, opt, setup.opt)
        return params, opt, ef, {"loss": loss, **m, **om}

    return step


# ---------------------------------------------------------------------------
# state construction / placement
# ---------------------------------------------------------------------------


def state_specs(setup: TrainSetup, mesh, params_tpl):
    """PartitionSpec tree for the stored parameters (either mode)."""
    ax = mesh_axes(mesh)
    if setup.fabric == "eps":
        rails = dp_axes_of(mesh)
    else:
        rails = rail_axes_of(mesh, setup.hsdp)
    n_rails = math.prod(_sizes(mesh, rails))
    fd, td = meta_trees(params_tpl, rails=rails, n_rails=n_rails,
                        model_size=ax[sh.MODEL_AXIS])
    return specs_from_meta(params_tpl, fd, td, rails, include_model=True)


def init_sharded_params(setup: TrainSetup, mesh, rng):
    """Initialize the parameters directly in their production shardings:
    each device generates only its own shards, so no device ever holds
    the whole model."""
    cfg = setup.cfg
    tpl = jax.eval_shape(lambda: tf.init_lm(rng, cfg))
    shardings = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                       state_specs(setup, mesh, tpl))
    return jax.jit(lambda key: tf.init_lm(key, cfg),
                   out_shardings=shardings)(rng)


def init_sharded_state(setup: TrainSetup, mesh, rng):
    """Initialize (params, opt, ef) placed with production shardings."""
    params = init_sharded_params(setup, mesh, rng)
    opt = adamw_init(params)
    ef = {}
    if setup.hsdp and setup.compress_pod_grads:
        ef = jax.tree_util.tree_map(
            lambda p: jnp.zeros_like(p, jnp.float32), params)
    return params, opt, ef
