"""Photonic-rail collectives: the paper's datapath, realized in JAX.

An OCS provides a *matching* between rail ports at any instant.  The only
collectives that are legal on such a fabric are chains of point-to-point
transfers along a ring — which in JAX is exactly ``jax.lax.ppermute`` inside
``shard_map``.  This module implements the rail datapath as ppermute rings:

  ring_all_gather      (FSDP fwd param gather; paper Fig 3 "AllGather")
  ring_reduce_scatter  (FSDP bwd gradient scatter; derived as the *linear
                        transpose* of ring_all_gather, so autodiff through a
                        fwd gather emits precisely this ring — the paper's
                        Fig 3 traffic falls out of the chain rule)
  ring_all_reduce      (optimizer-step sync ARs; RS + AG composition)
  ring_all_to_all      (ring-forwarded AllToAll, paper §7: O(N) hops —
                        provided for completeness; EP stays in scale-up)
  shift                (PP Send/Recv and hierarchical pod rings)

The electrical baseline (``EPSFabric``) exposes the same interface with
XLA's native free-form collectives (packet-switched all-to-all connectivity:
any algorithm is legal).  Both run under the same partial-manual shard_map:
rail axes are manual, the scale-up ``model`` axis stays GSPMD-auto.

A ``Fabric`` may span several rail axes (("pod", "data") in multi-pod mode);
gathers compose minor-to-major so the flat shard index is major-axis-first,
and reduce-scatter (being the transpose of the composition) automatically
runs major-to-minor — a hierarchical ring matching the paper's cross-pod DP.

This module imports jax at import time; ``repro.core.fabric`` (the one
blessed import surface) loads it lazily, so the jax-free simulator side
never pays for — or breaks on — the datapath's dependencies.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.scopes import scope


def ring_perm(n: int, shift: int = 1):
    return [(i, (i + shift) % n) for i in range(n)]


# ---------------------------------------------------------------------------
# single-axis rings
# ---------------------------------------------------------------------------


def _merge_axis(buf, axis: int):
    """[n, ...] -> merge the leading stack dim into dim `axis` of the rest."""
    n = buf.shape[0]
    rest = buf.shape[1:]
    moved = jnp.moveaxis(buf, 0, axis)  # [..., n, s, ...]
    new_shape = rest[:axis] + (n * rest[axis],) + rest[axis + 1:]
    return moved.reshape(new_shape)


def _ring_all_gather_one_dir(x, axis_name: str, axis_size: int,
                             direction: int = 1):
    """n-1 ppermute hops in one ring direction -> stacked [n, ...x]."""
    idx = jax.lax.axis_index(axis_name)
    perm = ring_perm(axis_size, direction)
    buf0 = jnp.zeros((axis_size,) + x.shape, x.dtype)
    buf0 = jax.lax.dynamic_update_slice_in_dim(buf0, x[None], idx, 0)

    def step(carry, k):
        shard, buf = carry
        shard = jax.lax.ppermute(shard, axis_name, perm)
        # after k hops along direction d, the resident shard originated at
        # rank (idx - d*k) mod n; + n^2 keeps the dividend positive
        src = jax.lax.rem(idx - direction * k + axis_size * axis_size,
                          axis_size)
        buf = jax.lax.dynamic_update_slice_in_dim(buf, shard[None], src, 0)
        return (shard, buf), None

    (_, buf), _ = jax.lax.scan(step, (x, buf0),
                               jnp.arange(1, axis_size, dtype=jnp.int32))
    return buf


def ring_all_gather(x, axis_name: str, axis_size: int, axis: int = 0,
                    bidirectional: bool = False):
    """Ring AllGather of shard ``x`` along dim ``axis`` (result n× larger).

    Circuit-legal: degree 2 (one neighbour each way).  With
    ``bidirectional=True`` the shard is split in half and the halves travel
    opposite ring directions concurrently, using BOTH ICI links — per-link
    bytes halve (§Perf H3; the unidirectional ring is the paper-faithful
    baseline, which leaves the second link dark).
    """
    with scope("ring_gather"):
        return _ring_all_gather(x, axis_name, axis_size, axis, bidirectional)


def _ring_all_gather(x, axis_name: str, axis_size: int, axis: int = 0,
                     bidirectional: bool = False):
    """``ring_all_gather`` outside any scope, for the collectives built
    from it (each under its own scope)."""
    if axis_size == 1:
        return x
    if bidirectional and x.shape[axis] % 2 == 0 and axis_size > 2:
        half = x.shape[axis] // 2
        lo = jax.lax.slice_in_dim(x, 0, half, axis=axis)
        hi = jax.lax.slice_in_dim(x, half, 2 * half, axis=axis)
        buf_lo = _ring_all_gather_one_dir(lo, axis_name, axis_size, 1)
        buf_hi = _ring_all_gather_one_dir(hi, axis_name, axis_size, -1)
        buf = jnp.concatenate([buf_lo, buf_hi], axis=axis + 1)
        return _merge_axis(buf, axis)
    buf = _ring_all_gather_one_dir(x, axis_name, axis_size, 1)
    return _merge_axis(buf, axis)


def ring_reduce_scatter(x, axis_name: str, axis_size: int, axis: int = 0):
    """Ring ReduceScatter: the linear transpose of ``ring_all_gather``.

    x full along dim ``axis`` -> summed shard (1/n size).  Deriving it as a
    transpose guarantees AG/RS are exact adjoints (gradient consistency).
    """
    with scope("ring_scatter"):
        return _ring_reduce_scatter(x, axis_name, axis_size, axis)


def _ring_reduce_scatter(x, axis_name: str, axis_size: int, axis: int = 0):
    if axis_size == 1:
        return x
    shard_shape = list(x.shape)
    assert shard_shape[axis] % axis_size == 0, (x.shape, axis, axis_size)
    shard_shape[axis] //= axis_size
    f = functools.partial(_ring_all_gather, axis_name=axis_name,
                          axis_size=axis_size, axis=axis)
    (out,) = jax.linear_transpose(
        f, jax.ShapeDtypeStruct(tuple(shard_shape), x.dtype))(x)
    return out


def ring_all_reduce(x, axis_name: str, axis_size: int):
    """Ring AllReduce = flat ReduceScatter + AllGather (bandwidth-optimal)."""
    if axis_size == 1:
        return x
    with scope("ring_all_reduce"):
        flat = x.reshape(-1)
        pad = (-flat.shape[0]) % axis_size
        if pad:
            flat = jnp.pad(flat, (0, pad))
        shard = _ring_reduce_scatter(flat, axis_name, axis_size)
        full = _ring_all_gather(shard, axis_name, axis_size)
        if pad:
            full = full[:-pad]
        return full.reshape(x.shape)


def ring_all_to_all(xstack, axis_name: str, axis_size: int):
    """Ring-forwarded AllToAll on stacked chunks [n, ...].

    Slot j of the result holds the chunk rank j addressed to this rank.
    Costs n-1 hops carrying the *whole* residual buffer — the ring
    bandwidth tax the paper notes in §7 (hence EP belongs in scale-up).
    """
    if axis_size == 1:
        return xstack
    with scope("ring_all_to_all"):
        return _ring_all_to_all(xstack, axis_name, axis_size)


def _ring_all_to_all(xstack, axis_name: str, axis_size: int):
    idx = jax.lax.axis_index(axis_name)
    perm = ring_perm(axis_size)
    own = jax.lax.dynamic_index_in_dim(xstack, idx, 0)
    out0 = jnp.zeros_like(xstack)
    out0 = jax.lax.dynamic_update_slice_in_dim(out0, own, idx, 0)

    def step(carry, k):
        buf, out = carry
        buf = jax.lax.ppermute(buf, axis_name, perm)
        # buf now came from rank (idx - k); its slot `idx` is for us
        contrib = jax.lax.dynamic_index_in_dim(buf, idx, 0)
        src = jax.lax.rem(idx - k + axis_size, axis_size)
        out = jax.lax.dynamic_update_slice_in_dim(out, contrib, src, 0)
        return (buf, out), None

    (_, out), _ = jax.lax.scan(step, (xstack, out0),
                               jnp.arange(1, axis_size, dtype=jnp.int32))
    return out


def shift(x, axis_name: str, axis_size: int, delta: int = 1):
    """Point-to-point ring shift (PP Send/Recv, pod rings)."""
    if axis_size == 1:
        return x
    return jax.lax.ppermute(x, axis_name, ring_perm(axis_size, delta))


# ---------------------------------------------------------------------------
# fabric interface (photonic rings vs electrical native)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Fabric:
    """Rail collectives over one or more mesh axes (major axis first)."""

    axes: Tuple[str, ...]
    sizes: Tuple[int, ...]
    kind: str = "photonic"  # "photonic" | "eps"
    bidirectional: bool = False  # use both ICI links per ring (§Perf H3)

    @property
    def n_shards(self) -> int:
        out = 1
        for s in self.sizes:
            out *= s
        return out

    # -- AllGather: minor axis first, so flat shard index is major-first --
    def all_gather(self, x, axis: int = 0):
        if self.kind == "photonic":
            with scope("ring_gather"):
                return self._ring_gather(x, axis)
        for name in reversed(self.axes):
            x = jax.lax.all_gather(x, name, axis=axis, tiled=True)
        return x

    def _ring_gather(self, x, axis: int):
        for name, size in zip(reversed(self.axes), reversed(self.sizes)):
            x = _ring_all_gather(x, name, size, axis=axis,
                                 bidirectional=self.bidirectional)
        return x

    def reduce_scatter(self, x, axis: int = 0):
        if self.kind == "photonic":
            shard_shape = list(x.shape)
            shard_shape[axis] //= self.n_shards
            f = functools.partial(self._ring_gather, axis=axis)
            with scope("ring_scatter"):
                (out,) = jax.linear_transpose(
                    f, jax.ShapeDtypeStruct(tuple(shard_shape), x.dtype))(x)
            return out
        for name in self.axes:  # major-to-minor (transpose order)
            x = jax.lax.psum_scatter(x, name, scatter_dimension=axis,
                                     tiled=True)
        return x

    def all_reduce(self, x):
        if self.kind == "photonic":
            for name, size in zip(self.axes, self.sizes):
                x = ring_all_reduce(x, name, size)
            return x
        return jax.lax.psum(x, self.axes)

    def pmax(self, x):
        """Small-stat max (decode merge); mgmt-class traffic."""
        return jax.lax.pmax(x, self.axes)

    def all_to_all(self, xstack):
        assert len(self.axes) == 1, "a2a spans a single rail axis"
        if self.kind == "photonic":
            return ring_all_to_all(xstack, self.axes[0], self.sizes[0])
        return jax.lax.all_to_all(xstack, self.axes[0], split_axis=0,
                                  concat_axis=0, tiled=False)

    def shift(self, x, delta: int = 1, axis_idx: int = -1):
        """Shift along one rail axis (default: minor axis)."""
        name = self.axes[axis_idx]
        size = self.sizes[axis_idx]
        if self.kind == "photonic":
            return shift(x, name, size, delta)
        return jax.lax.ppermute(x, name, ring_perm(size, delta))

    def axis_index(self):
        """Flat shard index (major axis first)."""
        idx = jnp.int32(0)
        for name, size in zip(self.axes, self.sizes):
            idx = idx * size + jax.lax.axis_index(name)
        return idx
