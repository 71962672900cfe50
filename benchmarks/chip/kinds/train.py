"""Training cells: the program's train step, built and driven as
``repro.launch.train`` builds and drives it.

Set-up makes the state on the device from the seed, then runs the first
``CHECK_STEPS`` steps through the window's own call and feed: they compile
the step and give the readings the check compares.  The window then runs
whole steps, one new batch made on the host before each, until its time
is up.  The check drives the plain reference through the same steps on
the same batches.
"""
from __future__ import annotations

import math
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from chipbench import refcore
from chipbench import spec as sp
from chipbench import traffic as tg
from chipbench.harness import Clock

CHECK_STEPS = 3
# leaves whose reference gradient is under this share of the median
# leaf's move by round-off alone; their change is not compared
ZERO_GRAD_SHARE = 1e-3

class Runner:
    def __init__(self, ctx):
        from repro.launch.train import parse_mesh
        from repro.models import transformer as tf
        from repro.train import step as st
        from repro.train.optimizer import OptConfig

        self.ctx, self.mix = ctx, ctx.mix
        self.cfg = ctx.cfg.replace(remat=self.mix["remat"])
        self.mesh = parse_mesh(self.mix["mesh"])
        self.opt = OptConfig(**self.mix["opt"])
        self.setup = st.TrainSetup(cfg=self.cfg, fabric=self.mix["fabric"],
                                   opt=self.opt)
        self._st = st
        tpl = jax.eval_shape(lambda: tf.init_lm(jax.random.PRNGKey(0),
                                                self.cfg))
        with jax.set_mesh(self.mesh):
            self.step = jax.jit(st.make_train_step(self.setup, self.mesh,
                                                   tpl))
        self.tokens_per_step = self.mix["batch"] * self.mix["seq"]
        self.state = None

    # -- the window's own call and feed ------------------------------------

    def _run_step(self, state, i: int):
        params, opt, ef = state
        with TraceAnnotation("bench.make_batch"):
            batch = {k: jnp.asarray(v) for k, v in tg.train_batch(
                self.mix, self.cfg.vocab_size, self.seed, i).items()}
        with TraceAnnotation("bench.dispatch"):
            out = self.step(params, opt, ef, batch)
        with TraceAnnotation("bench.wait"):
            params, opt, ef, metrics = jax.block_until_ready(out)
        return (params, opt, ef), metrics

    def prepare(self, seed: int) -> None:
        self.seed = seed
        with jax.set_mesh(self.mesh):
            state = self._st.init_sharded_state(self.setup, self.mesh,
                                                tg.jax_key(seed))
            losses = []
            for i in range(CHECK_STEPS):
                state, m = self._run_step(state, i)
                losses.append(float(m["loss"]))
                if i == 0:
                    grad_norm = float(m["grad_norm"])
                    grad = refcore.applied_gradient(state[1]["m"],
                                                    self.opt.b1)
            # the initial parameters again, from the seed: holding them
            # through the steps would cost the memory of a copy
            p0 = self._st.init_sharded_params(self.setup, self.mesh,
                                              tg.jax_key(seed))
            change = refcore.change_norms(state[0], p0)
            del p0
        self.state, self.next_step = state, CHECK_STEPS
        self.prog = {"losses": losses, "grad_norm": grad_norm,
                     "grad": grad, "change_leaves": change}

    def window(self, seconds: float, tracer=None) -> dict:
        times, failed, n = [], 0, 0
        # the window holds one state, as a job does, not also set-up's
        state, self.state = self.state, None
        traced = tracer.steps if tracer is not None else 0
        with jax.set_mesh(self.mesh):
            clock = Clock()
            while True:
                if n == 0 and tracer is not None:
                    clock.pause(tracer.begin)
                ts = time.perf_counter()
                state, m = self._run_step(state, self.next_step + n)
                times.append(time.perf_counter() - ts)
                failed += not math.isfinite(float(m["loss"]))
                n += 1
                if n == traced:
                    clock.pause(tracer.end)
                if clock.elapsed() >= seconds:
                    break
            elapsed = clock.elapsed()
        if tracer is not None:
            tracer.end()
        self.state = state
        # the steps the profiler did not watch, for readers that want the
        # job's rate without the profiler's cost
        rest = times[traced:]
        return {"train_tokens_per_s": n * self.tokens_per_step / elapsed,
                "untraced_tokens_per_s": (
                    len(rest) * self.tokens_per_step / sum(rest)
                    if rest else None),
                "steps": n, "failed": failed, "elapsed_s": elapsed,
                "step_times": times,
                "tokens_per_step": self.tokens_per_step}

    def readings(self) -> dict:
        return self.prog

    def release(self) -> None:
        self.state = None


def reference_readings(ctx, seed: int, prog: dict = None,
                       prec: str = "f32") -> dict:
    """The plain reference through the same first steps, same batches
    (the program's readings are not needed to drive it)."""
    ref = sp.reference(ctx.conf["reference"])
    model, mix = ctx.model, ctx.mix
    batches = [{k: jnp.asarray(v) for k, v in
                tg.train_batch(mix, model["vocab_size"], seed, i).items()}
               for i in range(CHECK_STEPS)]
    with jax.default_matmul_precision("highest"):
        return refcore.train_readings(
            lambda k: ref.init(k, model),
            lambda p, t, y: ref.block_loss(p, t, y, model, prec),
            tg.jax_key(seed), batches, mix["opt"], mix["ref_rows_per_block"])


def _worst_leaf(got: dict, want: dict, skip=()) -> float:
    """max over leaves of |norm_got - norm_want| / max(norm_want, median
    of norm_want over the leaves)."""
    keys = [k for k in want if k not in skip]
    med = statistics.median(want[k] for k in keys)
    return max(abs(got[k] - want[k]) / max(want[k], med, 1e-30)
               for k in keys)


def _norm(x) -> float:
    return float(np.linalg.norm(x.ravel().astype(np.float64)))


def compare(prog: dict, ref: dict) -> dict:
    """The numbers read against the reference, each a relative gap.

    ``grad_error`` is the worst leaf's norm of the difference between the
    program's step-1 gradient (as AdamW applies it) and the reference's,
    over the larger of the reference leaf's norm and the median leaf's:
    the norms of the leaves alone (``grad_leaf_gap``) barely move under
    unbiased rounding, which is what a lower precision adds."""
    raw = ref["raw_grad_leaves"]
    med = statistics.median(raw.values())
    silent = {k for k, x in raw.items() if x < ZERO_GRAD_SHARE * med}
    grad_ref = {k: _norm(x) for k, x in ref["grad"].items()}
    grad_med = statistics.median(grad_ref.values())
    return {
        "loss_gap": max(abs(p - r) / abs(r) for p, r in
                        zip(prog["losses"], ref["losses"])),
        "grad_norm_gap": abs(prog["grad_norm"] - ref["grad_norm"])
        / ref["grad_norm"],
        "grad_leaf_gap": _worst_leaf(
            {k: _norm(x) for k, x in prog["grad"].items()}, grad_ref),
        "grad_error": max(_norm(prog["grad"][k] - ref["grad"][k])
                          / max(grad_ref[k], grad_med, 1e-30)
                          for k in grad_ref),
        "update_leaf_gap": _worst_leaf(prog["change_leaves"],
                                       ref["change_leaves"], silent),
    }


def attempted(win: dict) -> int:
    return win["steps"]
