"""Serving steps: prefill and cached decode on the photonic mesh.

Three cell kinds from the assigned shape set:
  prefill_32k  — full-sequence forward (flash path), last-token logits.
                 Rail traffic: per-layer FSDP param AllGather rings only
                 (inference FSDP — params stay rail-sharded even in serving
                 so 100B+ archs fit; gathers are the same phase structure
                 Opus schedules for training fwd).
  decode_32k   — one token vs a batch-sharded KV cache.  No rail data-path
                 traffic at all for dense archs: batch is rail-local, TP is
                 scale-up.  (This is why the paper can keep serving on the
                 same photonic rails: the decode phase needs no circuits.)
  long_500k    — batch=1, 512k context: the KV cache itself is sharded
                 along the sequence dim across rails (context-parallel
                 decode); partial flash-decode stats merge with split-K
                 combines — small per-head scalars, management traffic.

SSM archs carry (conv, state) recurrent caches, which are rail-local; a
mamba decode step produces zero rail traffic (noted in DESIGN.md
§Arch-applicability — the technique has nothing to reconfigure there).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.fabric import Fabric
from repro.models import transformer as tf
from repro.parallel import sharding as sh
from repro.train import step as st


@dataclass(frozen=True)
class ServeSetup:
    cfg: ModelConfig
    fabric: str = "photonic"
    # batch >= n_dp: batch-shard the cache; else context-shard it (long_500k)
    context_shard: bool = False
    # weight-resident decode (§Perf H1): weights stay sharded in place
    # (FSDP x TP 2-D layout); matmuls reduce ACTIVATION-sized partials over
    # the rails instead of gathering WEIGHTS per token.  The rail collective
    # becomes one small static-ring AllReduce per projection — topology
    # never changes during decode (zero Opus reconfigurations).
    weight_resident: bool = False


def _cache_specs(cfg: ModelConfig, dp_axes, *, context_shard: bool):
    """PartitionSpec per cache leaf (stacked [n_periods, ...] layout).

    Attention caches are [n_periods, B, KV, dh, C]: batch-sharded on axis
    1, or context-sharded on the last, the cache position.
    """
    ba = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    specs = []
    for kind, _ in tf.period_spec(cfg):
        if kind == "attn":
            if context_shard:
                s = {"k": P(None, None, None, None, ba),
                     "v": P(None, None, None, None, ba),
                     "slot_pos": P(None, ba)}
            else:
                s = {"k": P(None, ba, None, None, None),
                     "v": P(None, ba, None, None, None),
                     "slot_pos": P(None, None)}
        else:  # ssm caches: batch-shard when possible, else replicate
            if context_shard:
                s = {"conv": P(), "state": P()}
            else:
                s = {"conv": P(None, ba, None, None),
                     "state": P(None, ba, None, None, None)}
        specs.append(s)
    return tuple(specs)


def init_serve_state(setup: ServeSetup, mesh, params, batch: int,
                     capacity: int):
    """Decode caches placed on the mesh.

    context_shard: each rail shard owns capacity/n_rails contiguous slots;
    the global array's seq dim is the FULL capacity, rail-sharded.
    """
    cfg = setup.cfg
    state = tf.init_decode_state(cfg, batch, capacity)
    dp_axes = st.dp_axes_of(mesh)
    specs = _cache_specs(cfg, dp_axes, context_shard=setup.context_shard)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), state,
        jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(state),
            jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))),
    )


def make_decode_step(setup: ServeSetup, mesh, params_tpl, *,
                     batch: int, capacity: int):
    """decode(params, state, token, pos) -> (logits, new_state)."""
    cfg = setup.cfg
    if setup.weight_resident:
        return _make_resident_decode_step(setup, mesh, params_tpl)
    ax = st.mesh_axes(mesh)
    model_size = ax[sh.MODEL_AXIS]
    dp_axes = st.dp_axes_of(mesh)
    n_dp = math.prod(st._sizes(mesh, dp_axes))
    rails = dp_axes
    fab = Fabric(rails, st._sizes(mesh, rails), setup.fabric)

    fd_tree, td_tree = st.meta_trees(params_tpl, rails=rails,
                                     n_rails=fab.n_shards,
                                     model_size=model_size)
    pspecs = st.specs_from_meta(params_tpl, fd_tree, td_tree, rails,
                                include_model=False)
    top_keys = [k for k in params_tpl if k != "layers"]

    def gfn(period_params):
        return st._gather_with_meta(period_params, fd_tree["layers"],
                                    td_tree["layers"], fab, dim_off=-1)

    cache_specs = _cache_specs(cfg, dp_axes,
                               context_shard=setup.context_shard)

    def body(stored, state, token, pos, cross):
        top = {k: stored[k] for k in top_keys}
        top = st._gather_with_meta(top, {k: fd_tree[k] for k in top_keys},
                                   {k: td_tree[k] for k in top_keys}, fab)
        params = dict(top, layers=stored["layers"])
        ctx = None
        if setup.context_shard:
            local_cap = capacity // n_dp
            ctx = {"fabric": fab,
                   "offset": fab.axis_index() * local_cap}
        logits, new_state = tf.decode_step(params, state, token, pos, cfg,
                                           layer_param_fn=gfn, ctx=ctx,
                                           cross_state=cross)
        return logits, new_state

    ba = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    token_spec = P() if setup.context_shard else P(ba, None)
    # enc-dec cross KV: [n_periods, B, S_enc, KV, dh] batch-sharded
    cross_spec = None
    if cfg.encoder is not None:
        cs = P() if setup.context_shard else P(None, ba, None, None, None)
        cross_spec = cs

    def step(params, state, token, pos, cross=None):
        cspec = None
        if cross is not None:
            cspec = jax.tree_util.tree_map(lambda _: cross_spec, cross)
        inner = jax.shard_map(
            body, mesh=mesh,
            in_specs=(pspecs, cache_specs, token_spec, P(), cspec),
            out_specs=((P(None, None, None) if setup.context_shard
                        else P(ba, None, None)), cache_specs),
            axis_names=set(dp_axes), check_vma=False)
        return inner(params, state, token, pos, cross)

    return step


def _make_resident_decode_step(setup: ServeSetup, mesh, params_tpl):
    """GSPMD weight-resident decode: no per-token parameter gathers.

    Params keep their stored FSDP x TP NamedShardings; XLA's SPMD
    partitioner reduces activation partial sums across the rail axis
    (a [B,1,d]-sized ring AllReduce per projection) instead of moving
    weights.  §Perf H1: for mistral-large decode_32k this removes ~all of
    the 7.7 GB/token rail traffic.
    """
    cfg = setup.cfg

    def step(params, state, token, pos, cross=None):
        return tf.decode_step(params, state, token, pos, cfg,
                              cross_state=cross)

    return step


def make_prefill_step(setup: ServeSetup, mesh, params_tpl):
    """prefill(params, batch) -> last-token logits (forward only)."""
    cfg = setup.cfg
    ax = st.mesh_axes(mesh)
    model_size = ax[sh.MODEL_AXIS]
    dp_axes = st.dp_axes_of(mesh)
    rails = dp_axes
    fab = Fabric(rails, st._sizes(mesh, rails), setup.fabric)

    fd_tree, td_tree = st.meta_trees(params_tpl, rails=rails,
                                     n_rails=fab.n_shards,
                                     model_size=model_size)
    pspecs = st.specs_from_meta(params_tpl, fd_tree, td_tree, rails,
                                include_model=False)
    top_keys = [k for k in params_tpl if k != "layers"]
    csp = sh.make_csp(rails, manual_rails=True)

    def gfn(period_params):
        return st._gather_with_meta(period_params, fd_tree["layers"],
                                    td_tree["layers"], fab, dim_off=-1)

    gfn_enc = None
    if "encoder" in params_tpl:
        def gfn_enc(period_params):
            return st._gather_with_meta(period_params,
                                        fd_tree["encoder"]["layers"],
                                        td_tree["encoder"]["layers"], fab,
                                        dim_off=-1)

    def body(stored, batch):
        top = {k: stored[k] for k in top_keys}
        top = st._gather_with_meta(top, {k: fd_tree[k] for k in top_keys},
                                   {k: td_tree[k] for k in top_keys}, fab)
        if "encoder" in top:
            top["encoder"] = dict(top["encoder"],
                                  layers=stored["encoder"]["layers"])
        params = dict(top, layers=stored["layers"])
        logits, _ = tf.lm_forward(params, batch, cfg, layer_param_fn=gfn,
                                  layer_param_fn_enc=gfn_enc, csp=csp,
                                  last_only=True)
        return logits

    batch_specs = st.build_batch_specs(cfg, dp_axes)
    ba = dp_axes if len(dp_axes) > 1 else dp_axes[0]

    def step(params, batch):
        bspecs = {k: batch_specs[k] for k in batch}
        inner = jax.shard_map(
            body, mesh=mesh, in_specs=(pspecs, bspecs),
            out_specs=P(ba, None, None),
            axis_names=set(dp_axes), check_vma=False)
        return inner(params, batch)

    return step
