"""A run's ``correct`` at smoke sizes on the CPU, under the cells' own
limits: sound runs pass; the control (the reference in float8 in the
program's place) and each fault the cell can have, planted in the timed
path underneath a whole run, fail.

Every run goes through ``harness.run_cell`` past the look for a chip, with
the kernels on their CPU path."""
import time

import jax
import jax.numpy as jnp
import pytest
from bench_smoke import ctx as smoke_ctx
from chipbench import harness
from chipbench import spec as sp

SEEDS = (2**31 + 5, 7)


def run(kind: str, seed: int) -> dict:
    c = smoke_ctx(kind)
    return harness.run_cell(sp.load_spec(), c, seed, 0.3, False,
                            jax.devices()[:c.chips], sp.limits(c.cell),
                            time.perf_counter(), pallas=False)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("kind", ["train", "decode"])
def test_sound_run_is_correct(kind, seed):
    res = run(kind, seed)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert all(m["value"] > 0 for m in res["metrics"].values())


class _SlowTracer:
    """Stands in for the profiler: starting and stopping it takes time
    that is not the window's."""
    steps = 2

    def __init__(self):
        self.calls = 0

    def begin(self):
        time.sleep(0.25)

    def end(self):
        self.calls += 1
        time.sleep(0.25)


@pytest.mark.parametrize("kind", ["train", "decode"])
def test_profiler_is_off_the_window_clock(kind):
    c = smoke_ctx(kind)
    runner = sp.kind(c.mix["kind"]).Runner(c)
    runner.prepare(SEEDS[1])
    tracer = _SlowTracer()
    win = runner.window(0.3, tracer)
    steps = sum(win["step_times"])
    assert tracer.calls >= 1 and win["steps"] > tracer.steps
    assert steps <= win["elapsed_s"] < steps + 0.2
    rest = win["step_times"][tracer.steps:]
    if kind == "train":
        assert win["untraced_tokens_per_s"] == pytest.approx(
            len(rest) * win["tokens_per_step"] / sum(rest))
    else:
        assert win["untraced_step_s_mean"] == pytest.approx(
            sum(rest) / len(rest))


def _wrap_train_step(monkeypatch, fault):
    from repro.train import step as st
    real = st.make_train_step

    def make(setup, mesh, tpl):
        step = real(setup, mesh, tpl)

        def broken(params, opt, ef, batch):
            if fault == "half_batch":   # the mean over the rest
                half = batch["tokens"].shape[0] // 2
                return step(params, opt, ef,
                            {k: v[:half] for k, v in batch.items()})
            new = step(params, opt, ef, batch)
            return (params, opt, ef, new[3])     # state returned unchanged
        return broken

    monkeypatch.setattr(st, "make_train_step", make)


def _wrap_decode_step(monkeypatch):
    from repro.serve import step as ss
    real = ss.make_decode_step

    def make(*a, **k):
        step = real(*a, **k)

        def broken(params, state, token, pos):
            logits, state = step(params, state, token, pos)
            return logits.at[..., 7].add(1e3), state   # token 7, always
        return broken

    monkeypatch.setattr(ss, "make_decode_step", make)


@pytest.mark.parametrize("kind,fault", [
    ("train", "unchanged_state"), ("train", "half_batch"),
    ("decode", "token_altered")])
def test_fault_is_not_correct(monkeypatch, kind, fault):
    if fault == "token_altered":
        _wrap_decode_step(monkeypatch)
    else:
        _wrap_train_step(monkeypatch, fault)
    res = run(kind, SEEDS[0])
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("kind", ["train", "decode"])
def test_control_is_not_correct(kind):
    """The reference with float8 matmul operands, in the program's place."""
    c = smoke_ctx(kind)
    k = sp.kind(c.mix["kind"])
    seed = SEEDS[0]
    limits = sp.limits(c.cell)
    if kind != "decode":
        ref = k.reference_readings(c, seed)
        numbers = k.compare(k.reference_readings(c, seed, prec="fp8"), ref)
    else:
        runner = k.Runner(c)
        runner.prepare(seed)
        runner.window(0.3)
        prog = runner.readings()
        numbers = k.reference_gaps(c, seed, prog, control="fp8")["control"]
    correct, checks = harness.check_numbers(numbers, limits)
    assert not correct, checks
    assert set(limits) <= set(numbers)
    assert any(numbers[n] > lim for n, lim in limits.items()), checks
    assert jnp.isfinite(jnp.asarray(list(numbers.values()))).all()
