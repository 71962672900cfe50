#!/usr/bin/env python3
"""Readings that set a cell's limits: the program and the control, on
many seeds, in one process.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 11,12,13 [--seconds 40] [--out FILE]

For every seed: set the program up as a run does (and, for serving, run
a window of ``--seconds`` at the cell's own load), read the numbers that
decide ``correct`` against the float32 reference, then read the control
(the reference computed with float8 matmul operands, in the program's
place) and, for training, the fault of half the batch left out (planted
in the reference).  One JSON line per seed on standard output and in
``--out``.  Needs the chips of the cell; the benchmark's runs never call
this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from chipbench import harness
    from chipbench import spec as sp
    from chipbench.peaks import peak
    from chipbench.program import import_program

    import_program()
    ctx = harness.make_ctx(args.workload)
    devices = harness.require_chips(ctx.chips)
    ctx.peak = peak(devices[0].device_kind)
    harness.use_cache()
    kind = sp.kind(ctx.mix["kind"])
    runner = kind.Runner(ctx)
    out = open(args.out, "a") if args.out else None
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            runner.prepare(seed)
            if ctx.mix["kind"] != "train":
                runner.window(args.seconds)
            prog = runner.readings()
            runner.release()
            gc.collect()
            row = {"seed": seed}
            if ctx.mix["kind"] == "train":
                ref = kind.reference_readings(ctx, seed)
                row["program"] = kind.compare(prog, ref)
                row["control"] = kind.compare(
                    kind.reference_readings(ctx, seed, prec="fp8"), ref)
                half = harness.Ctx(**dict(vars(ctx), mix=dict(
                    ctx.mix, batch=ctx.mix["batch"] // 2)))
                row["half_batch"] = kind.compare(
                    kind.reference_readings(half, seed), ref)
                row["losses"] = {"program": prog["losses"],
                                 "reference": ref["losses"]}
            else:
                gaps = kind.reference_gaps(ctx, seed, prog, control="fp8")
                row["control"] = gaps.pop("control")
                row["program"] = gaps
            row["seconds"] = time.perf_counter() - t0
            line = json.dumps(row)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    finally:
        if out:
            out.close()


if __name__ == "__main__":
    main()
