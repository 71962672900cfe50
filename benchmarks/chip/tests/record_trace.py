#!/usr/bin/env python3
"""Record the small chip trace that the trace-reduction tests read.

    python benchmarks/chip/tests/record_trace.py [--out DIR]

Runs on one TPU: the program's three Pallas kernels at small shapes, each
inside a ``bench.*`` host span, within one ``bench.window`` span, with a
host-side pause between them so that the trace holds idle gaps.  Writes
``small_trace.xplane.pb`` and a summary of every device op to ``--out``.
"""
from __future__ import annotations

import argparse
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[0]))
sys.path.insert(0, str(HERE.parents[2] / "src"))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=str(HERE / "data"))
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from chipbench import trace as tr
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: needs a TPU")
    from repro.kernels import decode_attention as da
    from repro.kernels import flash_attention as fa
    from repro.kernels import ssd_scan

    ks = iter(jax.random.split(jax.random.PRNGKey(0), 16))
    bf = jnp.bfloat16

    def normal(shape, dtype=jnp.float32):
        return jax.random.normal(next(ks), shape).astype(dtype)

    calls = {
        "flash": (jax.jit(lambda q, k, v: fa.flash_attention(q, k, v)),
                  (normal((1, 1024, 16, 64), bf), normal((1, 1024, 8, 64), bf),
                   normal((1, 1024, 8, 64), bf))),
        "decode": (jax.jit(lambda q, k, v, m: da.decode_attention(q, k, v, m)),
                   (normal((4, 1, 16, 64), bf), normal((4, 4096, 8, 64), bf),
                    normal((4, 4096, 8, 64), bf),
                    jnp.arange(4096)[None].repeat(4, 0) < 1000)),
        "ssd": (jax.jit(lambda x, dt, a, b, c: ssd_scan.ssd(x, dt, a, b, c,
                                                             64)[0]),
                (normal((1, 512, 32, 64)),
                 jax.nn.softplus(normal((1, 512, 32)) - 4.0),
                 -jnp.arange(1, 33, dtype=jnp.float32),
                 normal((1, 512, 1, 128)), normal((1, 512, 1, 128)))),
    }
    for fn, a in calls.values():
        jax.block_until_ready(fn(*a))
    log_dir = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(log_dir)
        with TraceAnnotation(tr.WINDOW_SPAN):
            for name, (fn, a) in calls.items():
                with TraceAnnotation(f"bench.{name}"):
                    jax.block_until_ready(fn(*a))
                with TraceAnnotation("bench.pause"):
                    time.sleep(0.005)
        jax.profiler.stop_trace()
        src = tr.find_xplane(log_dir)
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        shutil.copy(src, out / "small_trace.xplane.pb")
        from jax.profiler import ProfileData
        with open(out / "small_trace_summary.txt", "w") as f:
            for plane in ProfileData.from_file(src).planes:
                f.write(f"PLANE {plane.name}\n")
                for line in plane.lines:
                    evs = list(line.events)
                    f.write(f"  LINE {line.name} ({len(evs)} events)\n")
                    for ev in evs[:60]:
                        f.write(f"    {ev.name} start={ev.start_ns} "
                                f"dur={ev.duration_ns} "
                                f"{[(k, str(v)[:200]) for k, v in ev.stats]}\n")
        t = tr.load(str(out / "small_trace.xplane.pb"))
        print("devices", list(t.devices), "window_s", t.window_s,
              "busy", tr.busy_seconds(t))
        print("top", tr.top_ops(t))
        print("gaps", tr.idle_gaps(t))
        for k in ("_ssd_kernel", "_fa_kernel", "_decode_kernel"):
            print(k, tr.kernel(t, k))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
