#!/usr/bin/env python3
"""Record device traces that carry the program's named scopes, on a TPU.

    python benchmarks/chip/tests/record_scoped_trace.py [--out DIR]
    python benchmarks/chip/tests/record_scoped_trace.py --workload <cell> \
        --seed <n> --seconds <s> --out DIR

Without ``--workload``: one step each of the smoke-size train step and
decode step (the SSD and flash-decode kernels on their Pallas paths), in
host spans ``bench.train`` and ``bench.decode`` of one ``bench.window``.
Writes the trace (``scoped_trace.xplane.pb.gz``) and the compiled texts
of the steps that ran (``scoped_trace_hlo.json.gz``, {module: text}),
gzipped, for the scope tests.

With ``--workload``: the cell's traced run, as ``run.py --trace 1`` makes
it (its result line is printed), keeping its trace and the texts of the
executables that ran under ``--out``, gzipped; then prints, as JSON, the
seconds per scope from those executables and from the ones the readers
compile again, the clocks' offset bound, the idle gaps on the tied
clock, the unscoped op families, and the traced and untraced step times.
"""
from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[0]))
sys.path.insert(0, str(HERE.parents[2] / "src"))

SEED = 2147483901
TRACE = "scoped_trace.xplane.pb.gz"
TEXTS = "scoped_trace_hlo.json.gz"


def live_texts(path: str) -> dict:
    """{module: compiled text} of the live executables that ran in the
    trace at ``path``."""
    import jax
    from chipbench import clocks
    ran = {ev.name.split("(")[0]
           for evs in clocks.read(path).modules.values() for ev in evs}
    return {m.name: m.to_string()
            for ex in jax.devices()[0].client.live_executables()
            for m in ex.hlo_modules() if m.name in ran}


def _gzip(src: str, dst: Path) -> None:
    with open(src, "rb") as f, gzip.open(dst, "wb") as g:
        shutil.copyfileobj(f, g)


def record_smoke(out: Path) -> None:
    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    import bench_smoke
    from chipbench import spec as sp
    from chipbench import trace as tr

    train_ctx, dec_ctx = bench_smoke.ctx("train"), bench_smoke.ctx("decode")
    dec_ctx.mix["capacity"] = 4096      # the flash-decode kernel's path
    train = sp.kind("train").Runner(train_ctx)
    train.prepare(SEED)
    dec = sp.kind("decode").Runner(dec_ctx)
    dec.prepare(SEED)
    params, cache, tok, pos = dec.state
    # a small file: no Python frames and no HLO in the trace (the texts
    # are kept beside it)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.enable_hlo_proto = 0, False
    log_dir = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        with TraceAnnotation(tr.WINDOW_SPAN):
            with jax.set_mesh(train.mesh), TraceAnnotation("bench.train"):
                train._run_step(train.state, train.next_step)
            with TraceAnnotation("bench.pause"):
                time.sleep(0.005)
            with jax.set_mesh(dec.mesh), TraceAnnotation("bench.decode"):
                with TraceAnnotation("bench.dispatch"):
                    tok, _, _ = dec.step(params, cache, tok, np.int32(pos))
                with TraceAnnotation("bench.readback"):
                    np.asarray(tok)
        jax.profiler.stop_trace()
        src = tr.find_xplane(log_dir)
        out.mkdir(parents=True, exist_ok=True)
        _gzip(src, out / TRACE)
        with gzip.open(out / TEXTS, "wt") as f:
            json.dump(live_texts(src), f)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    print({p.name: p.stat().st_size for p in (out / TRACE, out / TEXTS)})


def _unscoped_families(trace, modules, n: int = 15) -> list:
    """[[op family, op_name or None, seconds], ...] of the first device's
    unscoped ops, largest first."""
    from chipbench import scopes as sc
    from chipbench import trace as tr
    op_names = {k: i.op_name for m in modules for k, i in m.instrs.items()}
    acc = {}
    for o in tr.matching(trace, sorted(trace.devices)[0], lambda o: True):
        if sc.scope_of(o, modules) in (sc.UNSCOPED, None) and \
                not tr.CONTAINER.match(tr._family(o)):
            key = (tr._family(o), op_names.get(o.name))
            acc[key] = acc.get(key, 0.0) + (o.end - o.start) * 1e-9
    return [[f, op, s] for (f, op), s in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def record_cell(cell: str, seed: int, seconds: float, out: Path) -> int:
    from chipbench import clocks, harness
    from chipbench import scopes as sc
    from chipbench import trace as tr

    out.mkdir(parents=True, exist_ok=True)
    kept = {}
    load, per_layer = harness.Tracer.load, harness.per_layer_metrics

    def keep(tracer):
        src = tr.find_xplane(tracer.dir)
        _gzip(src, out / "trace.xplane.pb.gz")
        kept["texts"] = live_texts(src)
        kept["raw"] = clocks.read(src)
        return load(tracer)

    def seen(spec, ctx, win, trace):
        kept.update(ctx=ctx, win=win, trace=trace)
        return per_layer(spec, ctx, win, trace)

    harness.Tracer.load, harness.per_layer_metrics = keep, seen
    rc = harness.run(["--workload", cell, "--seed", str(seed), "--seconds",
                      str(seconds), "--trace", "1"])
    if rc or "trace" not in kept:
        return rc or 1
    with gzip.open(out / "texts.json.gz", "wt") as f:
        json.dump(kept["texts"], f)
    trace, win, ctx = kept["trace"], kept["win"], kept["ctx"]
    live = [sc.parse_module(t) for t in kept["texts"].values()]
    again = [sc.parse_module(t) for t in sc.step_texts(ctx)]
    times, traced = win["step_times"], ctx.mix["trace_steps"]
    lo, hi = clocks.host_offset(kept["raw"])
    report = {
        "window_s": trace.window_s,
        "busy_s": tr.busy_seconds(trace),
        "scopes_live": sc.charge(trace, live),
        "scopes_again": sc.charge(trace, again),
        "modules_live": sorted(m.name for m in live),
        "host_offset_ms": [lo * 1e-6, hi * 1e-6],
        "host_at_gaps": clocks.host_at_gaps(kept["raw"]),
        "idle_gaps": tr.idle_gaps(trace),
        "unscoped": _unscoped_families(trace, live),
        "traced_step_s": times[:traced],
        "untraced_step_s_median": sorted(times[traced:])[
            len(times[traced:]) // 2] if times[traced:] else None,
    }
    print(json.dumps(report))
    (out / "report.json").write_text(json.dumps(report, indent=1))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=str(HERE / "data"))
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=SEED)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_scoped_trace: needs a TPU")
    if args.workload:
        return record_cell(args.workload, args.seed, args.seconds,
                           Path(args.out))
    record_smoke(Path(args.out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
