"""The FLOP and byte functions against hand counts at one shape each, and
the weight bytes against the program's own parameter tree."""
import json

import jax
import pytest
from bench_smoke import DATA
from chipbench import flops
from chipbench.peaks import PEAKS, peak


def test_ssd_forward_hand_count():
    # b 1, s 128 (two chunks of 64), h 2, p 4, g 1, n 8
    # per chunk: C B^T 2*64*64*8 = 65536; per head 2*64*64*4 = 32768
    # plus 4*64*8*4 = 8192 -> 2 heads 81920; chunk 147456; x2 = 294912
    # bytes f32: x and y 2*128*2*4, dt 128*2, B and C 2*128*8, state 2*4*8
    c = flops.ssd_forward(1, 128, 2, 4, 1, 8, 64)
    assert c.flops == 294912
    assert c.bytes == 4 * (2048 + 256 + 2048 + 64)


def test_decode_attention_hand_count():
    # QK and PV: 2 * 2 * b h valid dh = 4*2*4*10*8; K and V of the valid
    # slots 2*b*valid*kv*dh, q and out 2*b*h*dh, bf16
    c = flops.decode_attention(2, 4, 2, 8, 10)
    assert c.flops == 2560 and c.bytes == 2 * (640 + 128)


def test_mamba_train_flops_per_token_hand_count():
    model = json.loads((DATA / "mamba2_smoke.json").read_text())["model"]
    # d 64, d_inner 128, 8 heads of 16, n 16, one group, conv 4, chunk 8
    proj = 2 * (64 * (256 + 32 + 8) + 128 * 64)          # 54272
    conv = 2 * 4 * (128 + 32)                              # 1280
    scan = (2 * 8 * 8 * 16 + 8 * (2 * 8 * 8 * 16 + 4 * 8 * 16 * 16)) / 8
    per_token = 3 * (2 * (proj + conv + scan) + 2 * 64 * 512)
    assert per_token == 592896
    assert flops.train_flops_per_token(model, 64) == per_token


def test_attention_train_flops_count_causal_context():
    model = json.loads((DATA / "granite_smoke.json").read_text())["model"]
    # granite smoke: d 64, 4 heads / 2 kv of 16, 4 experts top 2 of 64
    attn = 2 * 64 * (4 + 4) * 16 + 2 * 4 * 16 * 64
    moe = 2 * 64 * 4 + 2 * 6 * 64 * 64
    seq = 9
    ctx = 4 * 4 * 16 * (seq + 1) / 2
    want = 3 * (2 * (attn + ctx + moe) + 2 * 64 * 499)
    assert flops.train_flops_per_token(model, seq) == want


@pytest.mark.parametrize("name", ["mamba2_smoke", "granite_smoke"])
def test_param_bytes_are_the_programs_weights(name):
    """Every leaf of the program's parameter tree, less the padding rows
    of the embedding table."""
    from chipbench.program import model_config
    conf = json.loads((DATA / f"{name}.json").read_text())
    model = conf["model"]
    from repro.models import transformer as tf
    tree = jax.eval_shape(lambda: tf.init_lm(jax.random.PRNGKey(0),
                                             model_config(conf)))
    total = sum(x.size * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(tree))
    vp = tree["embed"].shape[0]
    pad = (vp - model["vocab_size"]) * model["d_model"] * 2
    assert flops.param_bytes(model) == total - pad


def test_decode_step_is_weights_plus_valid_cache():
    model = json.loads((DATA / "granite_smoke.json").read_text())["model"]
    c = flops.decode_step(model, 3, 5)
    cache = 2 * 3 * 5 * 2 * 16 * 2 * 2          # layers K V b valid kv dh
    assert c.bytes == flops.param_bytes(model) + cache
    one = flops.train_flops_per_token(dict(model, n_layers=0), 1) / 3
    assert c.flops > 3 * one


def test_peaks_table_refuses_unknown_devices():
    assert peak("TPU v5 lite").flops == 197e12
    assert peak("TPU v5 lite").hbm_bytes == 819e9
    with pytest.raises(KeyError):
        peak("cpu")
    assert all(p.source for p in PEAKS.values())
