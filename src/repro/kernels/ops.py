"""Jit'd dispatch wrappers for the perf-critical kernels.

On TPU the Pallas kernels are used; everywhere else (a CPU host, and any
backend without Mosaic) the blocked pure-jnp implementations from
``ref.py`` run — same tiling structure, same memory behaviour, so roofline
terms derived from the dry-run match the kernel path.

Set ``REPRO_KERNELS=pallas_interpret`` to force the Pallas kernels in
interpret mode (used by the kernel tests on CPU), or ``REPRO_KERNELS=ref``
to force the oracles even on TPU.  Each dispatch logs the path it took
(``pallas``, ``interpret`` or ``ref``) on this module's logger, once per
trace.  Each kernel runs inside its named scope (``repro.scopes``) on
either path.
"""
from __future__ import annotations

import logging
import os
from typing import Optional

import jax

from repro.kernels import ref
from repro.scopes import scope

log = logging.getLogger(__name__)

_PATHS = {"pallas": "pallas", "pallas_interpret": "interpret", "ref": "ref"}


def _mode() -> str:
    env = os.environ.get("REPRO_KERNELS", "auto")
    if env == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    if env not in _PATHS:
        raise ValueError(f"REPRO_KERNELS={env!r}: expected auto, "
                         + ", ".join(_PATHS))
    return env


def _dispatch(kernel: str) -> str:
    mode = _mode()
    log.info("%s: %s", kernel, _PATHS[mode])
    return mode


def mha(q, k, v, *, causal: bool = True, window: Optional[int] = None,
        scale: Optional[float] = None, q_offset: int = 0):
    """Flash attention.  q [B,Sq,H,dh], k/v [B,Sk,KV,dh] -> [B,Sq,H,dh]."""
    mode = _dispatch("flash_attention")
    with scope("attn_flash"):
        if mode in ("pallas", "pallas_interpret"):
            from repro.kernels import flash_attention as fa
            return fa.flash_attention(
                q, k, v, causal=causal, window=window, scale=scale,
                q_offset=q_offset, interpret=(mode == "pallas_interpret"))
        return ref.mha(q, k, v, causal=causal, window=window, scale=scale,
                       q_offset=q_offset)


def decode_attention(q, k_cache, v_cache, valid_mask, *,
                     scale: Optional[float] = None):
    """Flash-decode.  q [B,1,H,dh], caches [B,KV,dh,C], valid [B,C].

    The cache position is minor, as the decode cache stores it, so the
    kernel reads the cache with no relayout.
    """
    mode = _dispatch("decode_attention")
    with scope("attn_decode"):
        if mode in ("pallas", "pallas_interpret"):
            from repro.kernels import decode_attention as da
            return da.decode_attention(
                q, k_cache, v_cache, valid_mask, scale=scale,
                interpret=(mode == "pallas_interpret"))
        return ref.decode_attention(q, k_cache, v_cache, valid_mask,
                                    scale=scale)


def ssd(x, dt, a, b_mat, c_mat, chunk: int, h_init=None):
    """Mamba-2 SSD chunked scan (see models.ssm for shapes)."""
    mode = _dispatch("ssd")
    with scope("ssd_fwd"):
        if mode in ("pallas", "pallas_interpret"):
            from repro.kernels import ssd_scan
            return ssd_scan.ssd(x, dt, a, b_mat, c_mat, chunk, h_init=h_init,
                                interpret=(mode == "pallas_interpret"))
        return ref.ssd_chunked(x, dt, a, b_mat, c_mat, chunk, h_init=h_init)
