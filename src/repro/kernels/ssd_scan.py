"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

TPU co-design (vs the paper's CUDA SSD kernel): the chunk dimension is the
grid's sequential axis and the [P, N] state is carried across chunks in a
VMEM scratch accumulator — the TPU analogue of the GPU version keeping state
in registers/shared memory across a threadblock loop.  All O(L^2) and
O(L*P*N) work inside a chunk is expressed as dense dots for the MXU:

    intra:  W = (C B^T) * exp(segsum) * dt      ->  Y_intra = W @ X
    inter:  Y_inter = (C @ state^T) * exp(cumsum dA)
    state:  state' = exp(sum dA) * state + (X * dt * decay)^T @ B

The group-to-head broadcast (n_groups G < H) happens through the B/C
BlockSpec index_map (head h reads group h // (H//G)) — never materialized.
Chunk decays use prefix-sum differences; the jnp oracle
(models.ssm.ssd_chunked) uses the masked-cumsum segment sum, and the two are
asserted allclose in tests over shape/dtype sweeps.  A carried-in state
(``h_init``, prefill continuation) seeds the VMEM accumulator.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.kernels.partition import on_mesh
from repro.scopes import scope

NEG_INF = -1e30


def _ssd_kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, h0_ref, y_ref, st_ref,
                state_scr, *, nc: int, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)        # [L, P]
    dt = dt_ref[0, 0].astype(jnp.float32)      # [L, 1]
    da = da_ref[0, 0].astype(jnp.float32)      # [L, 1] = dt * a_h
    bm = b_ref[0, 0].astype(jnp.float32)       # [L, N]
    cm = c_ref[0, 0].astype(jnp.float32)       # [L, N]
    state_in = state_scr[...]                  # [P, N]

    # Mosaic lowers neither cumsum nor a vector transpose of this shape, so
    # the prefix sum and the column -> row moves are masked reductions over
    # [L, L] (exact in f32: every masked-out term adds 0)
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    da_row = jnp.sum(jnp.where(li == lj, da, 0.0), axis=0, keepdims=True)
    cs = jnp.sum(jnp.where(lj <= li, da_row, 0.0), axis=1, keepdims=True)
    cs_row = jnp.sum(jnp.where(li == lj, cs, 0.0), axis=0, keepdims=True)
    total = jnp.sum(da, axis=0, keepdims=True)  # [1, 1] = cs[L-1]
    xdt = x * dt                               # [L, P]

    # ---- intra-chunk ----
    seg = jnp.where(li >= lj, cs - cs_row, NEG_INF)
    w = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [L, L]
    y = jax.lax.dot(w * jnp.exp(seg), xdt,
                    preferred_element_type=jnp.float32)          # [L, P]

    # ---- inter-chunk read of the carried state ----
    y = y + jax.lax.dot_general(cm, state_in, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
        * jnp.exp(cs)

    # ---- state update ----
    xw = xdt * jnp.exp(total - cs)             # [L, P]
    state_scr[...] = jnp.exp(total) * state_in + jax.lax.dot_general(
        xw, bm, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _flush():
        st_ref[0, 0] = state_scr[...].astype(st_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd(x, dt, a, b_mat, c_mat, h_init, chunk, interpret):
    return _ssd_fwd(x, dt, a, b_mat, c_mat, h_init, chunk, interpret)


def _ssd_vjp_fwd(x, dt, a, b_mat, c_mat, h_init, chunk, interpret):
    out = _ssd_fwd(x, dt, a, b_mat, c_mat, h_init, chunk, interpret)
    return out, (x, dt, a, b_mat, c_mat, h_init)


def _ssd_vjp_bwd(chunk, interpret, res, g):
    # pallas_call has no AD rule: recompute through the jnp oracle, whose
    # VJP is exact for the same math (tests assert fwd allclose)
    from repro.models.ssm import ssd_chunked
    with scope("ssd_bwd"):
        outs, vjp = jax.vjp(
            lambda x_, dt_, a_, b_, c_, h_: ssd_chunked(x_, dt_, a_, b_, c_,
                                                        chunk, h_init=h_),
            *res)
        g = tuple(gg.astype(oo.dtype) for gg, oo in zip(g, outs))
        return vjp(g)


_ssd.defvjp(_ssd_vjp_fwd, _ssd_vjp_bwd)


def ssd(x, dt, a, b_mat, c_mat, chunk: int, h_init=None,
        interpret: bool = False):
    """Pallas SSD.  Same contract as models.ssm.ssd_chunked.

    x [B,S,H,P], dt [B,S,H], a [H], b/c [B,S,G,N], h_init [B,H,P,N] or
    None (zeros) -> (y [B,S,H,P], final_state [B,H,P,N]).
    Differentiable: the backward pass recomputes through the oracle's
    VJP (the Pallas forward itself has no AD rule), so SSM archs train
    under ``REPRO_KERNELS=pallas`` instead of crashing in grad.
    """
    bsz, s, h, p = x.shape
    if s % chunk:
        raise ValueError(f"sequence {s} is not a multiple of chunk {chunk}")
    if h_init is None:
        h_init = jnp.zeros((bsz, h, p, b_mat.shape[3]), jnp.float32)
    return _ssd(x, dt, a, b_mat, c_mat, h_init, chunk, interpret)


def _ssd_fwd(x, dt, a, b_mat, c_mat, h_init, chunk, interpret):
    """``_ssd_call`` under the ambient mesh (``kernels.partition``)."""
    h, g = x.shape[2], b_mat.shape[2]

    def spec(b, m):
        mg = m if g > 1 else None  # one group is shared by every head
        return ((P(b, None, m, None), P(b, None, m), P(m),
                 P(b, None, mg, None), P(b, None, mg, None),
                 P(b, m, None, None)),
                (P(b, None, m, None), P(b, m, None, None)))

    # a head split must keep each head with its B/C group: one shared
    # group, or whole groups per shard
    heads = h if g == 1 else math.gcd(h, g)
    call = functools.partial(_ssd_call, chunk=chunk, interpret=interpret)
    return on_mesh(call, x.shape[0], heads, spec)(x, dt, a, b_mat, c_mat,
                                                   h_init)


def _ssd_call(x, dt, a, b_mat, c_mat, h_init, *, chunk, interpret):
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    rep = h // g
    nc = s // chunk

    xt = jnp.transpose(x, (0, 2, 1, 3))                     # [B,H,S,P]
    # per-step scalars as [B,H,S,1] columns: a (chunk, 1) tile meets
    # Mosaic's (8, 128) block rule where a (1, chunk) row would not
    dtt = jnp.transpose(dt, (0, 2, 1))[..., None]           # [B,H,S,1]
    dat = dtt * a[None, :, None, None]                      # [B,H,S,1]
    bt = jnp.transpose(b_mat, (0, 2, 1, 3))                 # [B,G,S,N]
    ct = jnp.transpose(c_mat, (0, 2, 1, 3))

    kernel = functools.partial(_ssd_kernel, nc=nc, chunk=chunk)
    y, st = pl.pallas_call(
        kernel,
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, ic: (b_, h_, ic, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b_, h_, ic: (b_, h_, ic, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b_, h_, ic: (b_, h_, ic, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda b_, h_, ic: (b_, h_ // rep, ic, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda b_, h_, ic: (b_, h_ // rep, ic, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b_, h_, ic: (b_, h_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, ic: (b_, h_, ic, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b_, h_, ic: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, s, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xt, dtt, dat, bt, ct, h_init)
    return jnp.transpose(y, (0, 2, 1, 3)), st
