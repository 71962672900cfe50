"""Run one cell once and print its result line.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The flow: refuse anything but enough TPU chips; build the program for the
cell; set up (weights from the seed, warm-up of every shape the window
uses, the first steps or the prompt fill) and time that as ``setup_s``;
run the window; read the device memory peak; free the program's state;
check what the window produced against the plain reference; print the
compared numbers with their limits on standard error and the result as
the last line of standard output.  ``--trace 1`` profiles the first steps
of the window and reports the cell's per-layer metrics instead of its
end-to-end ones.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import shutil
import statistics
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Optional

from chipbench import spec as sp
from chipbench import trace as tr
from chipbench.peaks import Peak, peak
from chipbench.program import CompileLog, import_program, kernel_paths, \
    model_config


class NoChip(Exception):
    pass


@dataclass
class Ctx:
    """What a kind's runner and a metric's reader get to know."""
    cell: str
    conf: dict          # configs/<config>.json
    model: dict         # its "model"
    mix: dict           # traffic/<traffic>.json
    chips: int
    cfg: object = None  # the program's ModelConfig
    peak: Optional[Peak] = None


class Tracer:
    """Profiles the first ``steps`` steps of a window inside the host span
    ``bench.window``; ``end`` is idempotent."""

    def __init__(self, steps: int):
        self.steps = steps
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self._span = None

    def begin(self):
        import jax
        jax.profiler.start_trace(self.dir)
        self._span = jax.profiler.TraceAnnotation(tr.WINDOW_SPAN)
        self._span.__enter__()

    def end(self):
        if self._span is None:
            return
        import jax
        self._span.__exit__(None, None, None)
        self._span = None
        jax.profiler.stop_trace()

    def load(self) -> tr.Trace:
        try:
            return tr.load(tr.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


class Clock:
    """A window's clock on the host, stopped while it ``pause``s for work
    that is not the window's, such as starting or stopping the profiler."""

    def __init__(self):
        self.t0 = time.perf_counter()
        self.paused = 0.0

    def pause(self, fn) -> None:
        t = time.perf_counter()
        fn()
        self.paused += time.perf_counter() - t

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0 - self.paused


def window_report(times: list, elapsed: float) -> str:
    """One line on where a window's time went: its steps (median, slowest,
    and those over twice the median) and the host's time between steps."""
    med = statistics.median(times)
    slow = sorted(((t, i) for i, t in enumerate(times) if t > 2 * med),
                  reverse=True)
    return (f"steps: median {med * 1e3:.3f} ms, slowest "
            f"{max(times) * 1e3:.3f} ms; {len(slow)} over twice the median, "
            f"{sum(t - med for t, _ in slow):.3f} s above it, at steps "
            f"{[i for _, i in slow[:8]]}; between steps "
            f"{elapsed - sum(times):.3f} s")


def make_ctx(cell: str, spec: dict = None) -> Ctx:
    spec = spec or sp.load_spec()
    w = sp.workload(spec, cell)
    conf = sp.config(w["config"])
    return Ctx(cell=cell, conf=conf, model=conf["model"],
               mix=sp.traffic(w["traffic"]), chips=w["chips"],
               cfg=model_config(conf))


def require_chips(chips: int):
    """The devices of the cell; exits without a result if JAX finds no
    TPU or fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def use_cache() -> None:
    """JAX's persistent compile cache at the program's fixed place inside
    the checkout (or ``$JAX_COMPILATION_CACHE_DIR``), every program in it."""
    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def check_numbers(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {value, limit}}): every number that has a limit
    within it.  The others are readings shown beside them (limit null)."""
    out = {name: {"value": numbers.get(name), "limit": limit}
           for name, limit in limits.items()}
    ok = bool(limits) and all(
        c["value"] is not None and math.isfinite(c["value"])
        and c["value"] <= c["limit"] for c in out.values())
    out.update({name: {"value": value, "limit": None}
                for name, value in numbers.items() if name not in limits})
    return ok, out


def per_layer_metrics(spec: dict, ctx: Ctx, win: dict, trace) -> dict:
    out = {}
    for m in sp.per_layer(spec, ctx.cell):
        value = sp.metric_reader(m["name"]).read(ctx, win, trace)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def breakdown(trace) -> dict:
    return {"device_ops": tr.top_ops(trace), "idle_gaps": tr.idle_gaps(trace)}


def run_cell(spec: dict, ctx: Ctx, seed: int, seconds: float, trace: bool,
             devices, limits: dict, t_start: float,
             pallas: bool = True) -> dict:
    """Set up, run the window and check one cell; the result object.

    ``pallas``: refuse a set-up in which a kernel the traffic names took
    another path than the Pallas kernel (a chip run always asks it)."""
    kind = sp.kind(ctx.mix["kind"])
    log = CompileLog()
    try:
        runner = kind.Runner(ctx)
        with kernel_paths() as paths:
            runner.prepare(seed)
        want = set(ctx.mix["kernels"])
        if pallas and ({k for k in paths if k in want} != want or any(
                paths[k] != {"pallas"} for k in want)):
            raise RuntimeError(f"kernel paths {paths}: want pallas for "
                               f"{sorted(want)}")
        setup_s = time.perf_counter() - t_start
        print(f"set-up {setup_s:.3f} s: compile {log.seconds:.3f} s in "
              f"{log.compiles} programs, {log.cache_hits} persistent-cache "
              "hits", file=sys.stderr)

        tracer = Tracer(ctx.mix["trace_steps"]) if trace else None
        before = log.compiles
        win = runner.window(seconds, tracer)
        print(f"window {win['elapsed_s']:.3f} s, {win['steps']} steps, "
              f"{log.compiles - before} compiles inside it", file=sys.stderr)
        print(window_report(win["step_times"], win["elapsed_s"]),
              file=sys.stderr)
        stats = [d.memory_stats() or {} for d in devices]
        memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
        reduced = tracer.load() if tracer else None

        prog = runner.readings()
        runner.release()
        del runner
        gc.collect()
        t_ref = time.perf_counter()
        numbers = kind.compare(prog, kind.reference_readings(ctx, seed, prog))
        print(f"reference check {time.perf_counter() - t_ref:.3f} s",
              file=sys.stderr)
    finally:
        log.close()
    correct, checks = check_numbers(numbers, limits)

    if trace:
        metrics = per_layer_metrics(spec, ctx, win, reduced)
    else:
        raw = dict(win, setup_s=setup_s)
        metrics = {m["name"]: {"value": raw[m["name"]], "unit": m["unit"]}
                   for m in sp.end_to_end(spec, ctx.cell)}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": correct,
              "attempted": kind.attempted(win), "failed": win["failed"],
              "metrics": metrics, "device": device}
    if reduced is not None:
        busy = tr.busy_seconds(reduced)
        device["busy_s"] = sum(busy.values()) / max(len(busy), 1)
        device["window_s"] = reduced.window_s
        result["breakdown"] = breakdown(reduced)
    result["checks"] = checks
    return result


def run(argv=None, t_start: float = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = sp.load_spec()
    import_program()
    ctx = make_ctx(args.workload, spec)
    try:
        devices = require_chips(ctx.chips)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    ctx.peak = peak(devices[0].device_kind)
    use_cache()
    result = run_cell(spec, ctx, args.seed, args.seconds, bool(args.trace),
                      devices, sp.limits(ctx.cell), t_start)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0
