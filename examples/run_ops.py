"""Operations scenarios on photonic rails: faults that heal, drains that
migrate, and a fleet you can diff (DESIGN.md §14).

    PYTHONPATH=src python examples/run_ops.py --scenario flap
    PYTHONPATH=src python examples/run_ops.py --scenario drain --twin-out /tmp/ops
    PYTHONPATH=src python examples/run_ops.py --scenario defrag
    PYTHONPATH=src python examples/run_ops.py                  # all of them

``flap``    one tenant rides transient link flaps: a short flap is
            absorbed by the retry/backoff budget; a long one demotes to
            the giant ring, then REPAIRS — the requested topology is
            restored, the replay cache re-promotes, and the event
            engine's fast-forward re-arms.
``drain``   a scheduled maintenance window reserves half the port space;
            resident tenants checkpoint-restart onto surviving ports
            (default) or live-migrate via evacuate circuit copies
            (--migrate), and the ports return when the window closes.
``defrag``  long-lived tenants pin scattered holes; the defrag policy
            watches allocator fragmentation and compacts by live
            migration, turning a fragmentation-blocked big job's
            multi-second queueing delay into zero.
``twin-out`` writes digital-twin JSONL inventories (switches, ports,
            circuits, owners per event tick) for the baseline and the
            scenario, and prints their row diff.
"""
import argparse

from repro.configs.base import get_config
from repro.core.faults import FaultModel, LinkFlap
from repro.core.phases import JobConfig
from repro.sim.cluster import ClusterJobSpec, ClusterParams
from repro.sim.ops import (DefragPolicy, DrainWindow, ScenarioEngine,
                           diff_twin, run_scenario, write_twin_jsonl)
from repro.sim.opus_sim import EventEngine, SimParams
from repro.sim.workload import build

CFG = get_config("llama3_8b")
SMALL = JobConfig(model=CFG.replace(n_layers=4), tp=2, fsdp=4, pp=2,
                  global_batch=32, seq_len=2048)     # 8 scale-out ranks
TINY = JobConfig(model=CFG.replace(n_layers=2), tp=2, fsdp=2, pp=1,
                 global_batch=16, seq_len=2048)      # 2 scale-out ranks


def scenario_flap():
    wl = build(SMALL, "h200")
    params = SimParams(mode="opus_prov", ocs_latency=0.01)
    # short flap: one retry (+1s timeout) outlives the 0.4s outage
    fm = FaultModel(flaps=(LinkFlap(rail=-1, start=2.0, duration=0.4),))
    eng = EventEngine(wl, params, ocs_fail=fm, iterations=8)
    eng.run()
    fs = eng.plane.fault_stats()
    print(f"flap (0.4s, inside retry budget): {fs['n_retries']} retries, "
          f"{fs['n_flaps_survived']} survived, "
          f"{fs['n_demotions']} demotions")
    # long flap: budget exhausted -> giant ring; repair restores the
    # requested topology and fast-forward re-arms past the flap horizon
    fm = FaultModel(flaps=(LinkFlap(rail=-1, start=2.0, duration=5.0),))
    eng = EventEngine(wl, params, ocs_fail=fm, iterations=30)
    eng.run()
    fs = eng.plane.fault_stats()
    print(f"flap (5s, budget exhausted): {fs['n_demotions']} demotion, "
          f"{fs['n_recoveries']} recovery, fallback now "
          f"{fs['fallback_active']}, "
          f"{eng.fastforwarded_iterations} iterations fast-forwarded "
          f"after repair")


def _drain_fleet():
    return [ClusterJobSpec(f"job{i}", SMALL, arrival=0.5 * i, iterations=6)
            for i in range(3)], ClusterParams(n_ports=32, ocs_latency=0.01)


def scenario_drain(migrate, twin_out=None):
    specs, params = _drain_fleet()
    window = DrainWindow(start=1.0, duration=3.0, ports=(0, 16),
                        migrate=migrate)
    ops = ScenarioEngine(drains=(window,))
    res, sim = run_scenario(specs, params, ops=ops, twin=twin_out is not None)
    how = "live-migrate" if migrate else "checkpoint-restart"
    print(f"drain ({window.label}, {how}): "
          f"{ops.stats['n_restarted']} restarted, "
          f"{ops.stats['n_migrated']} migrated; per-tenant:")
    for r in res.jobs:
        print(f"  {r.spec.name}: {r.status}, drains {r.n_drains}, "
              f"migrations {r.n_migrations}, "
              f"queued {r.queueing_delay:.2f}s")
    if twin_out is not None:
        res0, sim0 = run_scenario(specs, params, twin=True)
        a, b = f"{twin_out}_base.jsonl", f"{twin_out}_drain.jsonl"
        write_twin_jsonl(sim0.twin(), a)
        write_twin_jsonl(sim.twin(), b)
        d = diff_twin(sim0.twin(), sim.twin())
        print(f"  twin: {a} ({d.n_rows_a} rows) vs {b} ({d.n_rows_b} "
              f"rows): {d.n_differing_rows} rows differ "
              f"({d.n_diffs} cells)")


def scenario_defrag():
    specs = []
    for i in range(8):
        long = i % 2 == 0
        specs.append(ClusterJobSpec(
            f"t{i}_{'long' if long else 'short'}", TINY, arrival=0.0,
            iterations=40 if long else 2))
    specs.append(ClusterJobSpec("big", SMALL, arrival=1.0, iterations=4))
    params = ClusterParams(n_ports=16, ocs_latency=0.01)
    base, _ = run_scenario(specs, params)
    ops = ScenarioEngine(defrag=DefragPolicy(threshold=0.2, max_moves=4))
    res, _ = run_scenario(specs, params, ops=ops)
    big0 = next(r for r in base.jobs if r.spec.name == "big")
    big1 = next(r for r in res.jobs if r.spec.name == "big")
    print(f"defrag: {ops.stats['n_defrag_moves']} compaction moves over "
          f"{ops.stats['n_defrag_checks']} checks; big job queued "
          f"{big0.queueing_delay:.2f}s -> {big1.queueing_delay:.2f}s, "
          f"mean {base.summary()['mean_queueing_delay']:.2f}s -> "
          f"{res.summary()['mean_queueing_delay']:.2f}s")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="all",
                    choices=["flap", "drain", "defrag", "all"])
    ap.add_argument("--migrate", action="store_true",
                    help="drain via live migration instead of "
                         "checkpoint-restart")
    ap.add_argument("--twin-out", default=None,
                    help="path prefix for digital-twin JSONL exports "
                         "(drain scenario)")
    args = ap.parse_args()
    if args.scenario in ("flap", "all"):
        scenario_flap()
    if args.scenario in ("drain", "all"):
        scenario_drain(args.migrate, args.twin_out)
    if args.scenario in ("defrag", "all"):
        scenario_defrag()


if __name__ == "__main__":
    main()
