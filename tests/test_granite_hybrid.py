"""Granite 4.0-H's mechanisms in the program, at smoke size on the CPU: the
config accepts its fields, NoPE and each multiplier change the logits
(none is dead code), and a config that sets none of them lowers to the
programs it lowered to before they existed."""
import collections
import dataclasses
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ModelConfig, get_config
from repro.models import transformer as tf

SMOKE = get_config("granite_4_0_h_small", smoke=True)
# per-opcode counts of the lowered (StableHLO) decode step and forward of
# the default configs, recorded before the multipliers, NoPE and the held
# experts were added
OPCOUNTS = json.loads((Path(__file__).parent
                       / "default_config_opcounts.json").read_text())


def _opcounts(text: str) -> dict:
    return dict(sorted(collections.Counter(
        re.findall(r"= (?:\"?)([a-z_]+\.[a-z_]+)", text)).items()))


@pytest.mark.parametrize("arch", sorted(OPCOUNTS))
def test_default_config_lowers_as_before(arch):
    cfg = get_config(arch, smoke=True)
    p = jax.eval_shape(lambda: tf.init_lm(jax.random.PRNGKey(0), cfg))
    state = jax.eval_shape(lambda: tf.init_decode_state(cfg, 2, 16))
    decode = jax.jit(lambda p, s, t, q: tf.decode_step(p, s, t, q, cfg)).lower(
        p, state, jax.ShapeDtypeStruct((2, 1), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)).as_text()
    forward = jax.jit(lambda p, t: tf.lm_forward(p, {"tokens": t}, cfg)).lower(
        p, jax.ShapeDtypeStruct((2, 16), jnp.int32)).as_text()
    assert _opcounts(decode) == OPCOUNTS[arch]["decode"]
    assert _opcounts(forward) == OPCOUNTS[arch]["forward"]


def test_config_takes_a_list_pattern_and_hashes():
    cfg = ModelConfig(name="x", family="hybrid", n_layers=2, d_model=8,
                      n_heads=2, n_kv_heads=1, d_ff=8, vocab_size=16,
                      layer_pattern=["mamba", "attn"])
    assert cfg.layer_pattern == ("mamba", "attn") and hash(cfg)
    assert cfg.replace(n_layers=4).layer_pattern == ("mamba", "attn")


def test_defaults_apply_nothing():
    cfg = get_config("granite_moe_1b_a400m")
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling, cfg.attention_multiplier,
            cfg.position_embedding) == (1.0, 1.0, 1.0, None, "rope")
    assert cfg.moe.held == cfg.moe.n_experts == 32


def _logits(cfg, params, toks):
    """Teacher-forced forward logits and decode logits at every position."""
    full, _ = jax.jit(lambda p, t: tf.lm_forward(p, {"tokens": t}, cfg))(
        params, toks)
    step = jax.jit(lambda p, s, t, q: tf.decode_step(p, s, t, q, cfg))
    state = tf.init_decode_state(cfg, toks.shape[0], toks.shape[1])
    dec = []
    for t in range(toks.shape[1]):
        lg, state = step(params, state, toks[:, t:t + 1], jnp.int32(t))
        dec.append(lg[:, 0])
    return full, jnp.stack(dec, 1)


@pytest.mark.parametrize("change", [
    {"position_embedding": "rope"},
    {"embedding_multiplier": 1.0},
    {"residual_multiplier": 1.0},
    {"logits_scaling": 1.0},
    {"attention_multiplier": None},
], ids=lambda c: next(iter(c)))
def test_each_mechanism_moves_the_logits(change):
    """Undoing any one of NoPE or the four multipliers moves the logits of
    the forward and of decode: each is applied on both paths.  In float32,
    where the same computation gives the same bits, so any move is the
    mechanism's."""
    cfg = SMOKE.replace(dtype="float32")
    params = tf.init_lm(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                              cfg.vocab_size)
    base = _logits(cfg, params, toks)
    assert all(float(jnp.max(jnp.abs(a - b))) == 0.0
               for a, b in zip(base, _logits(cfg, params, toks)))
    other = _logits(cfg.replace(**change), params, toks)
    for a, b in zip(base, other):
        assert float(jnp.max(jnp.abs(a - b))) > 1e-4


def test_held_share_routes_over_all_experts():
    """A layer holding 3 of 8 experts keeps the router's 8 outputs and its
    top-3; its expert weights hold only its share."""
    p = tf.init_lm(jax.random.PRNGKey(0), SMOKE)
    ffn = p["layers"][0]["ffn"]
    assert ffn["router"].shape[-1] == SMOKE.moe.n_experts == 8
    assert ffn["w_gate"].shape[1] == SMOKE.moe.held == 3
    whole = SMOKE.replace(moe=dataclasses.replace(SMOKE.moe, n_held=None))
    assert tf.init_lm(jax.random.PRNGKey(0), whole)["layers"][0]["ffn"][
        "w_gate"].shape[1] == 8
