"""Pipeline parallelism over a rail axis: GPipe schedule, ppermute Send/Recv.

The paper's PP traffic is point-to-point activation Send/Recv between
adjacent stages — on photonic rails this is exactly a one-hop circuit, i.e.
``jax.lax.ppermute`` with the +1 ring permutation (core/fabric.shift).  This
module runs a real pipelined forward/backward in JAX: stages are shards of
a ``pipe`` mesh axis, each owning n_periods/n_stages of the layer stack;
microbatches stream through a (n_micro + n_stages - 1)-tick schedule.

Used by the paper-eval configs (Table 2: TP×FSDP×PP) in tests and by the
Opus phase profiler — the production 40-cell dry-run uses FSDP×TP per the
rail-fabric default placement (DESIGN.md §4).  The asymmetric phase
structure Opus must handle (different stages in different phases at the
same instant, §4.2 "Handling Asymmetrical Parallelism") is visible here:
at tick t, stage s computes microbatch t-s while stage s+1 still waits.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.fabric import ring_perm
from repro.models import transformer as tf
from repro.models.layers import cross_entropy, rms_norm


def stage_layers(cfg: ModelConfig, n_stages: int) -> int:
    np_ = tf.n_periods(cfg)
    assert np_ % n_stages == 0, (cfg.name, np_, n_stages)
    return np_ // n_stages


def pipeline_loss(params, batch, cfg: ModelConfig, *, pipe_axis: str,
                  n_stages: int, n_micro: int):
    """GPipe forward+loss inside shard_map (pipe axis manual).

    params["layers"] leaves arrive stage-sliced: [n_periods/n_stages, ...].
    batch tokens [B, S] arrive replicated; microbatches are B/n_micro rows.
    Embed/unembed params are replicated across stages (stage 0 / last use
    them).  Returns the global mean loss (replicated).
    """
    stage = jax.lax.axis_index(pipe_axis)
    perm = ring_perm(n_stages, 1)
    tokens = batch["tokens"]
    targets = batch["targets"]
    bsz, seq = tokens.shape
    mb = bsz // n_micro
    d = cfg.d_model
    ticks = n_micro + n_stages - 1
    positions = jnp.arange(seq)[None, :]

    def stage_fn(x):
        h, _ = tf.stack_apply(params["layers"], x, positions, cfg)
        return h

    def tick(carry, t):
        x_prev, loss_acc, tok_acc = carry
        # Send/Recv: previous stage's output arrives (paper PP phase)
        x_recv = jax.lax.ppermute(x_prev, pipe_axis, perm)
        mb_in = jnp.clip(t - 0, 0, n_micro - 1)
        first_in = jax.lax.dynamic_slice_in_dim(tokens, mb_in * mb, mb, 0)
        x0 = tf._embed_tokens(params, first_in, cfg)
        x_in = jnp.where(stage == 0, x0, x_recv)
        active = (t - stage >= 0) & (t - stage < n_micro)
        x_out = jnp.where(active, stage_fn(x_in), x_recv)
        # last stage: loss for microbatch (t - (n_stages-1))
        mb_out = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
        h = rms_norm(x_out, params["final_norm"], cfg.norm_eps)
        logits = tf._unembed(params, h, cfg)
        tgt = jax.lax.dynamic_slice_in_dim(targets, mb_out * mb, mb, 0)
        l, _ = cross_entropy(logits, tgt, cfg.vocab_size)
        emit = (stage == n_stages - 1) & (t >= n_stages - 1)
        loss_acc = loss_acc + jnp.where(emit, l, 0.0)
        return (x_out, loss_acc, tok_acc), None

    x0 = jnp.zeros((mb, seq, d), jnp.dtype(cfg.dtype))
    (x_last, loss_sum, _), _ = jax.lax.scan(
        tick, (x0, jnp.float32(0), 0), jnp.arange(ticks))
    # only the last stage holds the loss; broadcast it (mgmt traffic)
    loss = jax.lax.psum(jnp.where(stage == n_stages - 1,
                                  loss_sum / n_micro, 0.0), pipe_axis)
    return loss


def make_pipeline_train_step(cfg: ModelConfig, mesh, *, pipe_axis: str,
                             n_micro: int, lr: float = 1e-3):
    """SGD pipeline step (demonstration/profiling; the production step is
    train.step).  params['layers'] leaves are sharded over the pipe axis on
    their stacked dim; embed/unembed replicated."""
    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[pipe_axis]

    def pspec_tree(params):
        def fn(path, leaf):
            pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", "")))
                            for k in path)
            if pstr.startswith("layers"):
                return P(pipe_axis)
            return P()
        flat, td = jax.tree_util.tree_flatten_with_path(params)
        return jax.tree_util.tree_unflatten(
            td, [fn(p, l) for p, l in flat])

    def step(params, batch):
        pspecs = pspec_tree(params)

        def inner(p, b):
            loss, g = jax.value_and_grad(
                lambda pp: pipeline_loss(pp, b, cfg, pipe_axis=pipe_axis,
                                         n_stages=n_stages,
                                         n_micro=n_micro))(p)
            # grads of replicated (non-stage) leaves need the pipe psum
            def fix(gl, sp):
                return jax.lax.psum(gl, pipe_axis) if sp == P() else gl
            g = jax.tree_util.tree_map(fix, g, pspecs,
                                       is_leaf=lambda x: isinstance(x, P))
            return loss, g

        bspec = {k: P() for k in batch}
        loss, grads = jax.shard_map(
            inner, mesh=mesh, in_specs=(pspecs, bspec),
            out_specs=(P(), pspecs), axis_names={pipe_axis},
            check_vma=False)(params, batch)
        params = jax.tree_util.tree_map(
            lambda p, g: (p.astype(jnp.float32)
                          - lr * g.astype(jnp.float32)).astype(p.dtype),
            params, grads)
        return params, loss

    return step
