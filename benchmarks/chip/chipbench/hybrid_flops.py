"""Operations and bytes that one decode step of a hybrid model requires:
Mamba-2 and attention mixers in the order of ``layer_pattern``, each
followed by an MoE layer of which this chip holds ``n_held`` experts, plus
a shared expert.  ``chipbench.flops`` counts a model as all attention or
all SSM; this counts each layer as what it is.

As there, what the mathematics needs and no more: every weight held here
read once, the recurrent state of every Mamba layer read and written once,
the valid cached positions of every attention layer read, and the routed
experts' work for the share of the choices that land on held experts, on
average ``top_k * n_held / n_experts`` a token.  A multiply-add is two
operations.
"""
from __future__ import annotations

from chipbench.flops import Cost


def _width(model: dict) -> int:
    """Bytes of a weight or cache element in the model's dtype."""
    return 2 if model.get("dtype", "bfloat16") in ("bfloat16", "float16") \
        else 4


def _dims(model: dict):
    s = model["ssm"]
    d_inner = s["expand"] * model["d_model"]
    h = d_inner // s["head_dim"]
    return s, d_inner, h, s["head_dim"], s["state_dim"], s["n_groups"]


def _mamba(model: dict, batch: int) -> Cost:
    """One Mamba-2 layer's step for ``batch`` tokens: projections, conv,
    the state update dA*state + dt*x*B and readout C*state, the gate."""
    d, w = model["d_model"], _width(model)
    s, d_inner, h, p, n, g = _dims(model)
    conv_ch = d_inner + 2 * g * n
    proj_out = 2 * d_inner + 2 * g * n + h
    flops = batch * (2 * d * proj_out + 2 * d_inner * d
                     + 2 * s["conv_width"] * conv_ch + 5 * h * p * n)
    weights = w * (d * proj_out + d_inner * d) \
        + 4 * (s["conv_width"] * conv_ch + conv_ch + 3 * h + d_inner)
    state = 2 * 4 * batch * (h * p * n + (s["conv_width"] - 1) * conv_ch)
    return Cost(flops, weights + state)


def _attention(model: dict, batch: int, valid: float) -> Cost:
    d, w = model["d_model"], _width(model)
    hq, kv = model["n_heads"], model["n_kv_heads"]
    dh = model.get("head_dim") or d // hq
    flops = batch * (2 * d * (hq + 2 * kv) * dh + 2 * hq * dh * d
                     + 4 * hq * dh * valid)
    weights = w * (d * (hq + 2 * kv) * dh + hq * dh * d)
    cache = w * 2 * batch * kv * dh * (valid + 1)   # read valid, write one
    return Cost(flops, weights + cache)


def _moe(model: dict, batch: int) -> Cost:
    d, w, moe = model["d_model"], _width(model), model["moe"]
    e, held = moe["n_experts"], moe.get("n_held") or moe["n_experts"]
    de = moe.get("d_expert") or model["d_ff"]
    ds = moe.get("n_shared_experts", 0) * de
    routed = moe["top_k"] * held / e
    flops = batch * (2 * d * e + routed * 6 * d * de + 6 * d * ds)
    weights = 4 * d * e + w * 3 * d * (held * de + ds)
    return Cost(flops, weights)


def decode_step(model: dict, batch: int, valid: float) -> Cost:
    """One decode step of ``batch`` tokens with ``valid`` cached positions
    per attention layer."""
    d, v = model["d_model"], model["vocab_size"]
    pattern = model["layer_pattern"]
    periods = model["n_layers"] // len(pattern)
    flops = batch * 2 * d * v
    nbytes = _width(model) * v * d + 4 * d          # embedding, final norm
    for kind in pattern:
        mixer = (_attention(model, batch, valid) if kind == "attn"
                 else _mamba(model, batch))
        moe = _moe(model, batch)
        flops += periods * (mixer.flops + moe.flops)
        nbytes += periods * (mixer.bytes + moe.bytes + 4 * 2 * d)
    return Cost(flops, nbytes)
