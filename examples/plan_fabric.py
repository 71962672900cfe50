"""Which fabric should a datacenter buy?  The capacity planner
(DESIGN.md §12) answers with a Pareto frontier: it sweeps a grid of
FabricSpec cells (switch technology x sub-switch radix x shared ports
per rail x allocator policy), prices every cell through the REAL
control plane — a 512-GPU training job, a contended multi-tenant
cluster mix, a disaggregated serving fleet — and the Fig-14 bill, then
keeps the non-dominated cells over cost/GPU, power/GPU, training
overhead, cluster queueing, and serving p99 TTFT.

    PYTHONPATH=src python examples/plan_fabric.py
    PYTHONPATH=src python examples/plan_fabric.py --headline
    PYTHONPATH=src python examples/plan_fabric.py --ports 64 128 \
        --gpu gb200 --all-cells

``--headline`` additionally runs the two scale points the fast-forwarding
event engine (DESIGN.md §12) makes affordable on a laptop: one
100,000-GPU training job, and 256 jobs arriving across a simulated
week — each in seconds of wall clock.
"""
import argparse
import math

from repro.sim.planner import OBJECTIVES, PlannerConfig, plan


def fmt_row(row):
    if not row["feasible"]:
        return (f"  x {row['cell']:38s} infeasible: "
                f"{row['reason']}")
    o = row["objectives"]
    q = o["queueing_delay_s"]
    p99 = o["p99_ttft_s"]
    na = lambda v: v is None or math.isnan(v)    # noqa: E731
    star = "*" if row["on_frontier"] else " "
    return (f"  {star} {row['cell']:38s} ${o['cost_per_gpu']:8.2f}/GPU "
            f"{o['power_per_gpu']:6.3f} W/GPU  "
            f"train {100 * o['train_overhead']:+5.2f}%  "
            f"queue {'  n/a ' if na(q) else f'{q:5.3f}s'}  "
            f"p99 {'  n/a' if na(p99) else f'{1e3 * p99:4.0f}ms'}")


def main():
    ap = argparse.ArgumentParser(
        description="Sweep the fabric design space, print the Pareto "
                    "frontier")
    ap.add_argument("--gpu", default="h200",
                    choices=("a100", "h200", "gb200"))
    ap.add_argument("--ports", type=int, nargs="+", default=None,
                    help="shared ports per rail to sweep (default 64 96)")
    ap.add_argument("--ocs-latency", type=float, default=0.01)
    ap.add_argument("--bill-gpus", type=int, default=16384,
                    help="reference fleet size the bill prices")
    ap.add_argument("--all-cells", action="store_true",
                    help="print every cell, not just the frontier")
    ap.add_argument("--headline", action="store_true",
                    help="also run the 100k-GPU job and the 256-job "
                         "week-long trace")
    args = ap.parse_args()

    cfg = PlannerConfig(gpu=args.gpu, ocs_latency=args.ocs_latency,
                        bill_gpus=args.bill_gpus)
    if args.ports:
        cfg = PlannerConfig(gpu=args.gpu, ocs_latency=args.ocs_latency,
                            bill_gpus=args.bill_gpus,
                            ports_per_rail=tuple(args.ports))
    res = plan(cfg, headline=args.headline)

    n_frontier = len(res.frontier_rows())
    print(f"evaluated {len(res.rows)} fabric cells in {res.wall_s:.2f}s "
          f"({n_frontier} on the Pareto frontier over "
          f"{', '.join(OBJECTIVES)})\n")
    shown = res.rows if args.all_cells else [
        r for r in res.rows if r["on_frontier"] or not r["feasible"]]
    for row in shown:
        print(fmt_row(row))
    print("\n  * = Pareto-optimal; x = the probe job cannot be wired "
          "on that radix")

    if args.headline:
        sj = res.headline["single_job_100k"]
        wk = res.headline["week_trace_256"]
        print(f"\n100k-GPU single job ({sj['engine']} engine): "
              f"{sj['wall_s']}s wall, "
              f"{100 * sj['overhead_vs_native']:.2f}% overhead vs "
              f"native, {sj['n_ports_programmed']} ports programmed")
        print(f"256-job week trace: {wk['wall_s']}s wall, "
              f"{wk['n_done']}/{wk['n_jobs']} jobs done over "
              f"{wk['makespan_days']:.1f} simulated days "
              f"(peak utilization {wk['peak_utilization']:.2f}, "
              f"mean queueing {wk['mean_queueing_delay_s']:.0f}s)")


if __name__ == "__main__":
    main()
