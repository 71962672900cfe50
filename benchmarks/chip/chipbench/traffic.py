"""The one traffic generator: everything it makes comes from ``--seed``.

A traffic mix is a JSON file of parameters (``traffic/<name>.json``); the
generator reads it and the seed, and nothing of the program.  The token
stream follows the repository's ``train/data.py`` ``synth_batch``, copied
here so that a program change cannot move the traffic: uniform ids,
``seq + 1`` per row split into inputs and next-token targets, one
``numpy`` generator per (seed, stream, step).  Every seed gives every step
the same shapes; only the ids differ.
"""
from __future__ import annotations

import numpy as np


def seed_words(seed: int) -> tuple:
    """Seeds run to a little over 2**31 and beyond 32 bits: split a seed
    into the two 32-bit words a JAX key is made from."""
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    return seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF


def jax_key(seed: int, stream: int = 0):
    """The JAX key for weights (stream 0) and other device-made inputs."""
    import jax
    lo, hi = seed_words(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    return jax.random.fold_in(key, stream) if stream else key


def _rng(seed: int, step: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream, step])


def train_batch(mix: dict, vocab: int, seed: int, step: int) -> dict:
    """Global batch of step ``step``: {"tokens", "targets"} int32
    [batch, seq] numpy arrays."""
    toks = _rng(seed, step, 0).integers(
        0, vocab, (mix["batch"], mix["seq"] + 1), dtype=np.int32)
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}


def prompts(mix: dict, vocab: int, seed: int) -> np.ndarray:
    """Decode prompts: int32 [batch, prompt_len] uniform ids."""
    return _rng(seed, 0, 1).integers(
        0, vocab, (mix["batch"], mix["prompt_len"]), dtype=np.int32)


def sample_rows(n_rows: int, n_sample: int, seed: int) -> list:
    """Rows drawn from the seed for the check after the window."""
    rng = _rng(seed, 0, 2)
    return sorted(rng.choice(n_rows, size=min(n_sample, n_rows),
                             replace=False).tolist())
