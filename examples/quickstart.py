"""Quickstart: the paper's pipeline in five minutes on one CPU.

1. Build a reduced LM and train it for a few steps on the photonic fabric
   (ring collectives on the rails, TP in scale-up).
2. Extract its communication schedule and show the Opus phase table.
3. Simulate one iteration under EPS vs Opus vs Opus+Provisioning.
4. Print the cost/power advantage of replacing rail switches with OCSes.

    PYTHONPATH=src python examples/quickstart.py [--scheduler per_collective]
"""
import argparse
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: F401  (import after XLA_FLAGS is set)

from repro.configs.base import get_config
from repro.core.phases import (JobConfig, build_phase_table, count_reconfigs,
                               iteration_schedule)
from repro.launch.train import main as train_main
from repro.sim.costmodel import compare
from repro.sim.opus_sim import SimParams, simulate
from repro.sim.workload import build


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scheduler", default="phase_boundary",
                    choices=["phase_boundary", "per_collective"],
                    help="circuit-scheduling granularity for the opus "
                         "modes (DESIGN.md §13)")
    args = ap.parse_args()

    print("=== 1. train a reduced yi-9b on photonic rails (4 rails x TP2) ===")
    loss = train_main([
        "--arch", "yi_9b", "--smoke", "--steps", "10", "--mesh", "4x2",
        "--fabric", "photonic", "--batch", "8", "--seq", "64",
        "--lr", "3e-3",
    ])["losses"][-1]
    print(f"final loss: {loss:.4f}")

    print("\n=== 2. Opus phase table for the paper's Config 1 ===")
    job = JobConfig(model=get_config("llama3_8b"), tp=4, fsdp=2, pp=2,
                    global_batch=16, seq_len=8192)
    ops = iteration_schedule(job)
    for p in build_phase_table(ops):
        print(f"  phase {p.dim:5s} ops [{p.start_idx:4d}..{p.end_idx:4d}] "
              f"ways={p.ways}")
    print(f"  -> {count_reconfigs(ops, job.pp)} reconfigurations/step "
          f"(paper: 6)")

    print("\n=== 3. one iteration under each fabric mode ===")
    wl = build(job, "a100")
    last = None
    for mode in ("native", "oneshot", "opus", "opus_prov"):
        # the scheduler axis applies to the reconfiguring modes only —
        # static fabrics have no circuit rounds to schedule
        sched = args.scheduler if mode in ("opus", "opus_prov") else None
        r = simulate(wl, SimParams(mode=mode, ocs_latency=0.05,
                                   scheduler=sched))
        print(f"  {mode:10s} step={r.step_time:7.3f}s "
              f"reconfigs={r.n_reconfigs}  engine={r.engine}")
        last = r
    # the opus numbers above came out of the REAL control plane — the
    # simulator drove per-rank Shims, the Controller barrier and the OCS
    # drivers (repro.core.plane.ControlPlane); here is their telemetry:
    t = last.telemetry["measured"]
    print(f"  control plane (per iteration): {t['n_barriers']} barriers, "
          f"{t['n_dispatches']} dispatches, "
          f"{t['n_ports_programmed']} ports programmed")

    print("\n=== 4. why bother: the rail fabric bill ===")
    c = compare(512, 8, "eps_400g")
    print(f"  512 H200 GPUs: cost {c['cost_ratio']:.2f}x cheaper, "
          f"power {c['power_ratio']:.1f}x lower with photonic rails")


if __name__ == "__main__":
    main()
