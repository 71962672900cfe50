"""Decode cells: the program's cached decode step, on the path of
``repro.launch.serve``, serving a closed batch of greedy streams.

Set-up makes the weights on the device from the seed and teacher-forces
each row's seeded prompt through the program's own decode step (the
program has no prefill that writes the cache).  The window then decodes
greedily, one token per row per step; the host reads every step's tokens
back, as a server streaming to its users does.  When a row's position
reaches the cache's capacity it starts a new request at the end of the
prompt, sharing the prompt's cached prefix (slots past the position are
masked until overwritten).  The check runs the plain reference once over
the prompt and served tokens of sampled rows and reads, for each served
token, how far its reference logit lies below the reference's best.
"""
from __future__ import annotations

import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from chipbench import spec as sp
from chipbench import traffic as tg
from chipbench.harness import Clock


class Runner:
    def __init__(self, ctx):
        from repro.launch.train import parse_mesh
        from repro.models import transformer as tf
        from repro.serve.step import ServeSetup, make_decode_step
        from repro.train.step import TrainSetup

        self.ctx, self.mix = ctx, ctx.mix
        self.cfg = ctx.cfg
        self.mesh = parse_mesh(self.mix["mesh"])
        self.batch, self.cap = self.mix["batch"], self.mix["capacity"]
        self.prompt_len = self.mix["prompt_len"]
        self.train_setup = TrainSetup(cfg=self.cfg, fabric=self.mix["fabric"])
        self.serve_setup = ServeSetup(cfg=self.cfg, fabric=self.mix["fabric"])
        tpl = jax.eval_shape(lambda: tf.init_lm(jax.random.PRNGKey(0),
                                                self.cfg))
        vocab = self.cfg.vocab_size
        with jax.set_mesh(self.mesh):
            decode = make_decode_step(self.serve_setup, self.mesh, tpl,
                                      batch=self.batch, capacity=self.cap)

        def serve_step(params, state, token, pos):
            """Greedy: each row's next token and its logit."""
            logits, state = decode(params, state, token, pos)
            lg = logits[:, -1, :vocab].astype(jnp.float32)
            nxt = jnp.argmax(lg, -1).astype(jnp.int32)
            return nxt[:, None], jnp.max(lg, -1), state

        self.step = jax.jit(serve_step)
        self.state = None

    def prepare(self, seed: int) -> None:
        from repro.serve.step import init_serve_state
        from repro.train.step import init_sharded_params
        self.seed = seed
        self.prompts = tg.prompts(self.mix, self.cfg.vocab_size, seed)
        with jax.set_mesh(self.mesh):
            params = init_sharded_params(self.train_setup, self.mesh,
                                         tg.jax_key(seed))
            cache = init_serve_state(self.serve_setup, self.mesh, params,
                                     self.batch, self.cap)
            for t in range(self.prompt_len):
                # one step in flight at a time, as in the window: every
                # queued step would hold a cache of its own
                tok, top, cache = jax.block_until_ready(self.step(
                    params, cache, jnp.asarray(self.prompts[:, t:t + 1]),
                    np.int32(t)))
            # warm the window's own call, whose token comes from a step
            # (placed as a step places it): the undonated state stays as
            # it was, so the result is dropped
            jax.block_until_ready(self.step(params, cache, tok,
                                            np.int32(self.prompt_len)))
        self.first = (tok, np.asarray(tok)[:, 0], np.asarray(top))
        # requests[row] = [tokens, logits] of each request, as served
        self.requests = [[([int(x)], [float(y)])] for x, y in
                         zip(self.first[1], self.first[2])]
        self.state = (params, cache, tok, self.prompt_len)

    def window(self, seconds: float, tracer=None) -> dict:
        # the window holds one cache, as a server does, not also set-up's
        (params, cache, tok, pos), self.state = self.state, None
        times, positions, n = [], [], 0
        traced = tracer.steps if tracer is not None else 0
        with jax.set_mesh(self.mesh):
            clock = Clock()
            while True:
                if n == 0 and tracer is not None:
                    clock.pause(tracer.begin)
                ts = time.perf_counter()
                with TraceAnnotation("bench.dispatch"):
                    tok, top, cache = self.step(params, cache, tok,
                                                np.int32(pos))
                with TraceAnnotation("bench.readback"):
                    host, host_top = np.asarray(tok), np.asarray(top)
                times.append(time.perf_counter() - ts)
                positions.append(pos)
                for row in range(self.batch):
                    toks, logits = self.requests[row][-1]
                    toks.append(int(host[row, 0]))
                    logits.append(float(host_top[row]))
                pos += 1
                n += 1
                if pos == self.cap:   # a new request on the cached prompt
                    pos, tok = self.prompt_len, self.first[0]
                    for row in range(self.batch):
                        self.requests[row].append(
                            ([int(self.first[1][row])],
                             [float(self.first[2][row])]))
                if n == traced:
                    clock.pause(tracer.end)
                if clock.elapsed() >= seconds:
                    break
            elapsed = clock.elapsed()
        if tracer is not None:
            tracer.end()
        self.state = (params, cache, tok, pos)
        # the steps the profiler watched, and those it did not
        seen, rest = positions[:traced], slice(traced, None)
        return {"decode_tokens_per_s": n * self.batch / elapsed,
                "tpot_p95_ms": float(np.percentile(times, 95)) * 1e3,
                "steps": n, "failed": 0, "elapsed_s": elapsed,
                "step_times": times,
                "untraced_step_s_mean": (statistics.fmean(times[rest])
                                         if times[rest] else None),
                # cached positions each step attends to (its own included)
                "untraced_valid_mean": (statistics.fmean(positions[rest]) + 1
                                        if positions[rest] else None),
                "traced_valid_mean": (statistics.fmean(seen) + 1
                                      if seen else None),
                "batch": self.batch}

    def readings(self) -> dict:
        rows = tg.sample_rows(self.batch, self.mix["check_rows"], self.seed)
        return {"prompts": self.prompts,
                "requests": {r: self.requests[r] for r in rows}}

    def release(self) -> None:
        self.state = None


def _sequences(prog: dict, cap: int):
    """(tokens, positions, served, logits, mask), each [cap], of every
    checked request: the prompt then the served tokens as inputs, the
    positions whose logits predict the served tokens, the served tokens
    and the program's logits of them, all padded to the capacity so that
    every request runs one compiled reference."""
    for row, reqs in prog["requests"].items():
        prompt = prog["prompts"][row]
        for toks, logits in reqs:
            if len(toks) < 2 and len(reqs) > 1:
                continue
            n = len(toks)
            pad = lambda a, t=np.int32: np.pad(np.asarray(a, t),
                                               (0, cap - len(a)))
            yield (pad(np.concatenate([prompt, toks[:-1]])),
                   pad(np.arange(len(prompt) - 1, len(prompt) - 1 + n)),
                   pad(toks), pad(logits, np.float32), np.arange(cap) < n)


def reference_gaps(ctx, seed: int, prog: dict, control: str = None) -> dict:
    """Readings of every checked served token against the f32 reference's
    logits at its position: the gap between the reference's best logit
    and its logit of the served token (mean, widest, and the share of
    tokens whose gap is not 0), and ``logit_error``, the mean of
    |program's logit - reference's logit| of the served token over the
    spread (standard deviation) of the reference's logits there.  With
    ``control``, the same of the tokens, and their logits, that the
    reference in that precision would have served instead."""
    ref = sp.reference(ctx.conf["reference"])
    model, cap = ctx.model, ctx.mix["capacity"]
    vocab = model["vocab_size"]

    def logits_at(p, toks, at, prec):
        return ref.forward(p, toks[None], model, prec)[0][0][at, :vocab]

    def readings(lg, picked, logit, mask):
        at = jnp.take_along_axis(lg, picked[:, None], -1)[:, 0]
        gap = jnp.where(mask, jnp.max(lg, -1) - at, 0.0)
        err = jnp.where(mask, jnp.abs(logit - at) / jnp.std(lg, -1), 0.0)
        return jnp.stack([jnp.sum(gap), jnp.max(gap), jnp.sum(gap > 0),
                          jnp.sum(err)])

    def control_readings(p, toks, at, mask):
        lg = logits_at(p, toks, at, control)
        pick = jnp.argmax(lg, -1)
        return readings(logits_at(p, toks, at, "f32"), pick,
                        jnp.max(lg, -1), mask)

    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), ref.init(k, model)))(
            tg.jax_key(seed))
        served = jax.jit(lambda p, t, a, s, lo, m: readings(
            logits_at(p, t, a, "f32"), s, lo, m))
        picked = jax.jit(control_readings)
        acc, acc_ctl, n = np.zeros(4), np.zeros(4), 0

        def add(a, got):
            return np.array([a[0] + got[0], max(a[1], got[1]),
                             a[2] + got[2], a[3] + got[3]])

        for toks, at, tok, logits, mask in _sequences(prog, cap):
            args = [jnp.asarray(x) for x in (toks, at, tok, logits, mask)]
            acc = add(acc, np.asarray(served(params, *args)))
            n += int(mask.sum())
            if control:
                acc_ctl = add(acc_ctl, np.asarray(picked(
                    params, args[0], args[1], args[4])))

    def summary(a):
        return {"logit_error": float(a[3] / n),
                "mean_logit_gap": float(a[0] / n),
                "widest_logit_gap": float(a[1]),
                "mismatch_share": float(a[2] / n)}
    out = dict(summary(acc), tokens_checked=n)
    if control:
        out["control"] = summary(acc_ctl)
    return out


def reference_readings(ctx, seed: int, prog: dict) -> dict:
    return reference_gaps(ctx, seed, prog)


def compare(prog: dict, ref: dict) -> dict:
    """The readings of the served tokens against the reference."""
    return {k: ref[k] for k in ("logit_error", "mean_logit_gap",
                                "widest_logit_gap", "mismatch_share")}


def attempted(win: dict) -> int:
    return win["steps"] * win["batch"]
