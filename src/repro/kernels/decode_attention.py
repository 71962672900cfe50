"""Pallas TPU flash-decode: one query token against a long KV cache.

The grid is (batch row, kv head).  Each grid step loops over the cache
blocks of its row that hold at least one valid slot, bringing each
[dh, block_k] tile into VMEM and folding it into running (m, l, acc)
statistics, i.e. the classic flash-decoding split-K scheme mapped onto
the TPU memory hierarchy (HBM -> VMEM tiles -> VREG reductions).  A
block with no valid slot is neither copied nor computed: it would add
p = 0 and alpha = 1 to the statistics, so the result is the same.  The
wrapper lists each row's blocks from the mask (``valid_blocks``), so the
skip follows the mask alone: a prefix, a ring window, ragged rows.  A row
with no valid slot lists no block and gives zeros.  GQA is the grid's
kv-head axis, and the query block is the [rep, dh] bundle of query heads
sharing one kv head, so the MXU contractions are [rep, dh] @ [dh,
block_k] and [rep, block_k] @ [dh, block_k]^T.

The caches are the decode state's stacks [L, B, KV, dh, C] (the layout
``models.attention`` stores, cache position minor, so lane-dense and
unpadded), and the kernel attends over one layer of them, picked by an
index in SMEM.  K and V stay in HBM: the kernel DMAs the listed tiles
itself, double-buffered, each block starting the next one's copy, the
last of a grid step the next step's first.  So the layer scan hands the
kernel the stack it carries, and no layer's cache is sliced out or
relaid around the call.  The operands stay q, K, V, then the int32 mask,
then one SMEM vector holding the layer index followed by the block
table: the benchmark's ``decode_attention_roofline`` finds the kernel by
that list.

Used by the decode_32k / long_500k serve cells; validated against
``ref.decode_attention`` in interpret mode.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.kernels import ref
from repro.kernels.partition import on_mesh

NEG_INF = ref.NEG_INF


def _decode_kernel(q_ref, k_hbm, v_hbm, valid_ref, tab_ref, o_ref, k_buf,
                   v_buf, sem, seq_ref, *, scale: float, block_k: int,
                   tail: int, nb: int, nk: int):
    ib, g = pl.program_id(0), pl.program_id(1)
    nkv = pl.num_programs(1)
    # tab: the layer index, then valid_blocks' counts and block indices
    layer = tab_ref[0]

    def count(b_):
        return tab_ref[1 + b_]

    def listed(b_, i):
        return tab_ref[1 + nb + b_ * nk + i]

    n = count(ib)

    def copies(b_, g_, blk, slot, start: bool):
        def run(width):
            off = pl.multiple_of(blk * block_k, block_k)
            for j, (src, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                c = pltpu.make_async_copy(
                    src.at[layer, b_, g_, :, pl.ds(off, width)],
                    buf.at[slot, :, pl.ds(0, width)], sem.at[j, slot])
                c.start() if start else c.wait()
        if not tail:
            run(block_k)
            return
        pl.when(blk < nk - 1)(lambda: run(block_k))
        pl.when(blk == nk - 1)(lambda: run(tail))

    # the grid runs in order, one (row, kv head) a step, and each step
    # loops over its row's listed blocks only; ``seq`` counts the blocks
    # read so far, so block i's slot is (seq + i) % 2 and every copy
    # starts a block ahead, across (b, g) boundaries too
    @pl.when((ib == 0) & (g == 0))
    def _first():
        seq_ref[0] = 0

    base = seq_ref[0]
    # the previous step started this step's first copy unless it listed
    # no block: it is this row's, or the last row's when g == 0
    prev_n = jnp.where(g > 0, n, jnp.where(
        ib > 0, count(jnp.maximum(ib - 1, 0)), 0))

    @pl.when((n > 0) & (prev_n == 0))
    def _start():
        copies(ib, g, listed(ib, 0), base % 2, True)

    q = q_ref[0, 0].astype(jnp.float32) * scale              # [rep, dh]

    def body(i, carry):
        m_prev, l_prev, acc_prev = carry
        slot = (base + i) % 2
        blk = listed(ib, i)

        @pl.when(i + 1 < n)
        def _next_block():
            copies(ib, g, listed(ib, i + 1), 1 - slot, True)

        @pl.when(i + 1 == n)
        def _next_step():
            wrap = g + 1 == nkv
            b1 = jnp.minimum(jnp.where(wrap, ib + 1, ib), nb - 1)
            g1 = jnp.where(wrap, 0, g + 1)

            @pl.when(jnp.logical_not(wrap) | ((ib + 1 < nb)
                                              & (count(b1) > 0)))
            def _():
                copies(b1, g1, listed(b1, 0), 1 - slot, True)

        copies(ib, g, blk, slot, False)
        k = k_buf[slot].astype(jnp.float32)                  # [dh, bk]
        s = jax.lax.dot(q, k, preferred_element_type=jnp.float32)  # [rep, bk]
        off = pl.multiple_of(blk * block_k, block_k)
        vmask = valid_ref[0, :, pl.ds(off, block_k)] != 0   # [1, bk]
        s = jnp.where(vmask, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        v = v_buf[slot].astype(jnp.float32)                  # [dh, bk]
        if tail:  # past C the last block's buffer holds stale tiles or the
            v = jnp.where(vmask, v, 0.0)  # HBM buffer's padding: 0*NaN is NaN
        acc = acc_prev * alpha + jax.lax.dot_general(
            p, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [rep, dh]
        return m_new, l_prev * alpha + jnp.sum(p, -1, keepdims=True), acc

    rep, dh = q.shape
    _, l, acc = jax.lax.fori_loop(
        0, n, body, (jnp.full((rep, 1), NEG_INF, jnp.float32),
                     jnp.zeros((rep, 1), jnp.float32),
                     jnp.zeros((rep, dh), jnp.float32)))
    seq_ref[0] = base + n
    # a row with no valid slot lists no block: acc is 0, so is its output
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _decode(q, k_cache, v_cache, valid_mask, layer, scale, block_k,
            interpret):
    return _decode_fwd(q, k_cache, v_cache, valid_mask, layer, scale,
                       block_k, interpret)


def _decode_vjp_fwd(q, k_cache, v_cache, valid_mask, layer, scale, block_k,
                    interpret):
    out = _decode_fwd(q, k_cache, v_cache, valid_mask, layer, scale,
                      block_k, interpret)
    return out, (q, k_cache, v_cache, valid_mask, layer)


def _decode_vjp_bwd(scale, block_k, interpret, res, g):
    # pallas_call has no AD rule: recompute through the jnp oracle on the
    # indexed layer (exact same math, asserted allclose in tests); the mask
    # and the layer index are non-float
    import numpy as np
    q, k_cache, v_cache, valid_mask, layer = res

    def oracle(q_, k_, v_):
        k_, v_ = (jax.lax.dynamic_index_in_dim(t, layer, 0, keepdims=False)
                  for t in (k_, v_))
        return ref.decode_attention(q_, k_, v_, valid_mask, scale=scale)

    out, vjp = jax.vjp(oracle, q, k_cache, v_cache)
    dq, dk, dv = vjp(g.astype(out.dtype))
    return (dq, dk, dv, np.zeros(valid_mask.shape, jax.dtypes.float0),
            np.zeros(layer.shape, jax.dtypes.float0))


_decode.defvjp(_decode_vjp_fwd, _decode_vjp_bwd)


def decode_attention(q, k_cache, v_cache, valid_mask, layer, *,
                     scale: Optional[float] = None, block_k: int = 1024,
                     interpret: bool = False) -> jnp.ndarray:
    """q [B,1,H,dh]; k/v_cache [L,B,KV,dh,C], the stacked caches of L
    layers; valid_mask [B,C]; layer, an int32 scalar -> [B,1,H,dh].

    Attends over layer ``layer`` of the stack, which the kernel reads in
    place: a 4-D cache is passed as ``k[None]`` with layer 0.  Only the
    ``block_k`` blocks of a row that hold a valid slot are read; a row
    with no valid slot gives zeros (the oracle's output there is not an
    attention).  A capacity that ``block_k`` does not divide ends in a
    shorter block; only the mask is padded.  Differentiable: grads
    recompute through ``ref.decode_attention``'s VJP (the Pallas forward
    has no AD rule).
    """
    dh = q.shape[3]
    c = k_cache.shape[4]
    scale = scale if scale is not None else 1.0 / (dh ** 0.5)
    block_k = min(block_k, c)
    layer = jnp.asarray(layer, jnp.int32)
    return _decode(q, k_cache, v_cache, valid_mask, layer, scale, block_k,
                   interpret)


def _decode_fwd(q, k_cache, v_cache, valid_mask, layer, scale, block_k,
                interpret):
    """``_decode_call`` under the ambient mesh (``kernels.partition``);
    each shard lists its rows' blocks from its own rows of the mask."""
    def spec(b, m):
        return ((P(b, None, m, None), P(None, b, m, None, None),
                 P(None, b, m, None, None), P(b, None), P()),
                P(b, None, m, None))
    call = functools.partial(_decode_call, scale=scale, block_k=block_k,
                             interpret=interpret)
    return on_mesh(call, q.shape[0], math.gcd(q.shape[2], k_cache.shape[2]),
                   spec)(q, k_cache, v_cache, valid_mask, layer)


def valid_blocks(valid_mask, block_k: int):
    """The cache blocks of each row that hold a valid slot.

    valid_mask [B,C] -> (count [B], blocks [B,nk]) int32, with nk =
    ceil(C / block_k): ``count[b]`` blocks of row b hold at least one
    valid slot, and ``blocks[b, :count[b]]`` are their indices in
    ascending order (the rest of the row lists the other blocks).  The
    share of blocks the kernel reads is ``count.sum() / (B * nk)``.
    """
    b = valid_mask.shape[0]
    vm, _ = ref._pad_to(valid_mask, block_k, 1)
    hit = vm.reshape(b, -1, block_k).any(-1)                 # [B, nk]
    count = jnp.sum(hit, -1, dtype=jnp.int32)
    return count, jnp.argsort(~hit, -1, stable=True).astype(jnp.int32)


def _decode_call(q, k_cache, v_cache, valid_mask, layer, *, scale, block_k,
                 interpret):
    b, _, h, dh = q.shape
    kvh, c = k_cache.shape[2], k_cache.shape[4]
    rep = h // kvh
    nk = -(-c // block_k)

    qt = q.reshape(b, kvh, rep, dh)                         # [B,KV,rep,dh]
    # [B,1,C]: a row's (1, C) mask meets Mosaic's (8, 128) rule; padded to
    # whole blocks with invalid slots (the cache is not)
    vm, _ = ref._pad_to(valid_mask.astype(jnp.int32)[:, None, :], block_k, 2)
    count, blocks = valid_blocks(valid_mask, block_k)

    # the last block's copy when block_k does not divide C: its C % block_k
    # slots, rounded up to the 128-lane tile that Mosaic slices HBM by (the
    # tiled HBM buffer holds those lanes as padding, which the mask hides)
    tail = c % block_k
    if tail and not interpret:
        tail = min(-(-tail // 128) * 128, block_k)
    kernel = functools.partial(_decode_kernel, scale=scale, block_k=block_k,
                               tail=tail, nb=b, nk=nk)
    o = pl.pallas_call(
        kernel,
        grid=(b, kvh),
        in_specs=[
            pl.BlockSpec((1, 1, rep, dh), lambda b_, g: (b_, g, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 1, nk * block_k), lambda b_, g: (b_, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, dh), lambda b_, g: (b_, g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kvh, rep, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, dh, block_k), k_cache.dtype),
            pltpu.VMEM((2, dh, block_k), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
        # the cross-step prefetch needs the grid run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(qt, k_cache, v_cache, vm, jnp.concatenate(
        [jnp.reshape(layer, (1,)), count, blocks.reshape(-1)]))
    return o.reshape(b, 1, h, dh)
