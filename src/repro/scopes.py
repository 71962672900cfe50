"""Named scopes: the program's layers, as a device trace can name them.

``scope(name)`` is ``jax.named_scope`` for the names registered here and
refuses any other.  A scope is trace-time metadata: its name joins the
``op_name`` of every HLO instruction emitted inside it, fusions, Pallas
calls and a ``custom_vjp`` backward included, and survives compilation.
It adds no operation and costs no device time.

A device op belongs to the innermost registered scope on its ``op_name``
path, read through JAX's transform wrappers (``transpose(jvp(layer_scan))``
is ``layer_scan``); an op under none is unscoped.
"""
from __future__ import annotations

import jax

#: name -> where it is opened
SCOPES = {
    "embed": "models/transformer: token embedding lookup",
    "layer_scan": "models/transformer: the layer scan itself (slicing the "
                  "stacked params and caches per layer, stacking them out)",
    "layer": "models/transformer: the scan body (norms, residuals, "
             "projections no finer scope claims)",
    "lm_head": "models/transformer: final norm, unembedding and loss",
    "ssm": "models/ssm: a Mamba-2 block outside its chunk scan",
    "ssd_fwd": "kernels/ops.ssd: the SSD chunk scan, either path",
    "ssd_bwd": "kernels/ssd_scan: the SSD backward, recomputed through "
               "the oracle",
    "attn_flash": "kernels/ops.mha: flash attention, either path",
    "attn_decode": "kernels/ops.decode_attention: flash-decode, either path",
    "kv_cache": "models/attention.decode_attention: cache write and valid "
                "mask",
    "moe_route": "models/moe: router, top-k, load-balance loss, slot "
                 "positions",
    "moe_dispatch": "models/moe: scatter into the expert buffers",
    "moe_experts": "models/moe: the expert FFNs",
    "moe_combine": "models/moe: gather back and weighted sum",
    "adamw": "train/optimizer.adamw_update",
    "ring_gather": "core/_fabric_rings: ring all-gather",
    "ring_scatter": "core/_fabric_rings: ring reduce-scatter",
    "ring_all_reduce": "core/_fabric_rings: ring all-reduce",
    "ring_all_to_all": "core/_fabric_rings: ring all-to-all",
}


#: registered scopes that the benchmark's own copy of ``SCOPES``
#: (``benchmarks/chip/chipbench/scopes.NAMES``) does not list yet; its
#: readers of ``SCOPES`` charge their ops to the enclosing scope until it
#: does, and those that read them list them themselves
NOT_IN_BENCHMARK = {
    "ssm_state": "models/ssm.ssm_decode: the recurrent state update and "
                 "its readout",
}


def scope(name: str):
    """``jax.named_scope(name)`` for a registered name."""
    if name not in SCOPES and name not in NOT_IN_BENCHMARK:
        raise ValueError(f"unregistered scope {name!r}; add it to "
                         "repro.scopes.SCOPES")
    return jax.named_scope(name)
