"""Operations and bytes that the algorithms require, from shapes alone.

These are the numerators of every utilization and roofline share the
benchmark reports.  They count what the mathematics needs, not what the
compiler emitted: no recomputation under remat, no padding of the
vocabulary or of MoE capacity, no masked cache slots.  A multiply-add is
two operations.  ``model`` is the ``"model"`` object of a configuration
file (``configs/<name>.json``).
"""
from __future__ import annotations


class Cost(tuple):
    """(flops, bytes) of one piece of work."""

    def __new__(cls, flops: float, nbytes: float):
        return super().__new__(cls, (float(flops), float(nbytes)))

    flops = property(lambda self: self[0])
    bytes = property(lambda self: self[1])

    def seconds(self, peak) -> float:
        """Least time on a chip with these peaks: the larger bound."""
        return max(self[0] / peak.flops, self[1] / peak.hbm_bytes)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def ssd_forward(b: int, s: int, h: int, p: int, g: int, n: int, chunk: int,
                itemsize: int = 4) -> Cost:
    """One chunked SSD forward: x [b,s,h,p], dt [b,s,h], B/C [b,s,g,n] in,
    y [b,s,h,p] and the final state [b,h,p,n] out.

    Per chunk of L positions: C B^T once per group (2 L^2 N), and per
    head the intra-chunk product with x (2 L^2 P), the read of the
    carried state (2 L N P) and the state update (2 L N P).
    """
    nc = s // chunk
    flops = b * nc * (g * 2 * chunk * chunk * n
                      + h * (2 * chunk * chunk * p + 4 * chunk * n * p))
    nbytes = itemsize * (2 * b * s * h * p + b * s * h + 2 * b * s * g * n
                         + b * h * p * n)
    return Cost(flops, nbytes)


def decode_attention(b: int, h: int, kv: int, dh: int, valid: float,
                     itemsize: int = 2) -> Cost:
    """One flash-decode call: q [b,1,h,dh] against ``valid`` cached
    positions of K and V [b,*,kv,dh].  Masked slots are not required."""
    flops = 4 * b * h * valid * dh
    nbytes = itemsize * (2 * b * valid * kv * dh + 2 * b * h * dh)
    return Cost(flops, nbytes)


# ---------------------------------------------------------------------------
# whole models
# ---------------------------------------------------------------------------


def _ssm_dims(model: dict) -> tuple:
    s = model["ssm"]
    d_inner = s["expand"] * model["d_model"]
    return d_inner, d_inner // s["head_dim"], s["head_dim"], s["state_dim"]


def _layer_forward_per_token(model: dict, context: float) -> float:
    """Forward FLOPs of one layer for one token; ``context`` is the mean
    number of positions a token attends to."""
    d = model["d_model"]
    if model["family"] == "ssm":
        s = model["ssm"]
        d_inner, h, p, n = _ssm_dims(model)
        g, w = s["n_groups"], s["conv_width"]
        proj = 2 * d * (2 * d_inner + 2 * g * n + h) + 2 * d_inner * d
        conv = 2 * w * (d_inner + 2 * g * n)
        chunk = s["chunk_size"]
        scan = ssd_forward(1, chunk, h, p, g, n, chunk).flops / chunk
        return proj + conv + scan
    hq, kv = model["n_heads"], model["n_kv_heads"]
    dh = model.get("head_dim") or d // hq
    attn = 2 * d * (hq + 2 * kv) * dh + 2 * hq * dh * d
    attn += 4 * hq * dh * context
    moe = model.get("moe")
    if moe:
        de = moe.get("d_expert") or model["d_ff"]
        ffn = 2 * d * moe["n_experts"] + moe["top_k"] * 6 * d * de
        ffn += moe.get("n_shared_experts", 0) * 6 * d * de
    else:
        ffn = 6 * d * model["d_ff"]
    return attn + ffn


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward and backward FLOPs per token (3x the forward), causal
    attention over ``seq`` positions, recomputation not counted."""
    fwd = model["n_layers"] * _layer_forward_per_token(model, (seq + 1) / 2)
    fwd += 2 * model["d_model"] * model["vocab_size"]
    return 3 * fwd


def param_bytes(model: dict) -> float:
    """Bytes of every weight as served: matrices in the model's dtype,
    norms, router and SSM scalars in float32, vocabulary unpadded."""
    w = 2 if model.get("dtype", "bfloat16") in ("bfloat16", "float16") else 4
    d, nl = model["d_model"], model["n_layers"]
    total = w * model["vocab_size"] * d + 4 * d          # embed, final norm
    if model["family"] == "ssm":
        s = model["ssm"]
        d_inner, h, p, n = _ssm_dims(model)
        conv_ch = d_inner + 2 * s["n_groups"] * n
        layer = w * (d * (2 * d_inner + 2 * s["n_groups"] * n + h)
                     + d_inner * d)
        layer += 4 * (s["conv_width"] * conv_ch + conv_ch + 3 * h + d_inner
                      + d)
        return total + nl * layer
    hq, kv = model["n_heads"], model["n_kv_heads"]
    dh = model.get("head_dim") or d // hq
    layer = w * (2 * d * hq * dh + 2 * d * kv * dh) + 4 * 2 * d
    moe = model.get("moe")
    if moe:
        de = moe.get("d_expert") or model["d_ff"]
        layer += 4 * d * moe["n_experts"] + w * moe["n_experts"] * 3 * d * de
        layer += w * moe.get("n_shared_experts", 0) * 3 * d * de
    else:
        layer += w * 3 * d * model["d_ff"]
    return total + nl * layer


def decode_step(model: dict, batch: int, valid: float) -> Cost:
    """One decode step of an attention model: every weight read once, the
    ``valid`` cached positions of every layer read, ``batch`` tokens."""
    d = model["d_model"]
    hq, kv = model["n_heads"], model["n_kv_heads"]
    dh = model.get("head_dim") or d // hq
    per_token = model["n_layers"] * _layer_forward_per_token(model, valid)
    per_token += 2 * d * model["vocab_size"]
    cache = model["n_layers"] * 2 * batch * valid * kv * dh * 2
    return Cost(batch * per_token, param_bytes(model) + cache)
