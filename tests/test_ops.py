"""Operations scenario pack (DESIGN.md §14): flaps that heal, drains
that migrate, defrag that acts, and a fleet you can diff.

The recovery contract tested here is the tentpole: after a flap
repairs, the plane is back on the REQUESTED topology (not the giant
ring it demoted to), the replay cache re-promotes, the engine's
fast-forward re-arms, and the next steady iteration's integer counters
match a never-faulted run exactly: fast-forwarding, walked live, and on
the uncollapsed plane.
"""
import json
import math
import subprocess
import sys
from dataclasses import replace
from pathlib import Path

import pytest

from repro.configs.base import get_config
from repro.core.faults import (FaultModel, LinkFlap, MigrationContractError,
                               PortOwnershipError, pick_victim)
from repro.core.orchestrator import PortAllocator
from repro.core.phases import JobConfig
from repro.core.plane import ControlPlane
from repro.sim.cluster import (ClusterJobSpec, ClusterParams, ClusterSim,
                               simulate_cluster)
from repro.sim.ops import (DefragPolicy, DrainWindow, ScenarioEngine,
                           diff_twin, run_scenario, write_twin_jsonl)
from repro.sim.opus_sim import SHIM_MODE, EventEngine, SimParams, simulate
from repro.sim.workload import build

CFG = get_config("llama3_8b")
SMALL = JobConfig(model=CFG.replace(n_layers=4), tp=2, fsdp=4, pp=2,
                  global_batch=32, seq_len=2048)     # 8 scale-out ranks
TINY = JobConfig(model=CFG.replace(n_layers=2), tp=2, fsdp=2, pp=1,
                 global_batch=16, seq_len=2048)      # 2 scale-out ranks
P = SimParams(mode="opus_prov", ocs_latency=0.01)



def _live(fm):
    """An injector that keeps the engine walking every iteration live: a
    faultable plane never fast-forwards before its fault horizon.  A
    clean run gets one that never fires; a flap schedule gets an empty
    outage window far past the run, which moves the horizon and nothing
    else."""
    if fm is None:
        return lambda attempt: False
    return replace(fm, flaps=fm.flaps + (
        LinkFlap(rail=-1, start=1e9, duration=0.0),))


ENGINES = {
    "event": lambda wl, fm, n: EventEngine(wl, P, ocs_fail=fm,
                                           iterations=n),
    "live": lambda wl, fm, n: EventEngine(wl, P, ocs_fail=_live(fm),
                                          iterations=n),
    "event_full": lambda wl, fm, n: EventEngine(wl, P, ocs_fail=fm,
                                                collapse=False,
                                                iterations=n),
}


def _ints(d):
    """Recursively keep the integer-valued leaves of a telemetry dict."""
    out = {}
    for k, v in d.items():
        if isinstance(v, dict):
            out[k] = _ints(v)
        elif isinstance(v, bool) or isinstance(v, int):
            out[k] = v
    return out


# ---------------------------------------------------------------------------
# the deterministic fault model itself
# ---------------------------------------------------------------------------


def test_flap_schedule_deterministic_and_windows():
    a = FaultModel.flap_storm(5, mean_gap=2.0, mean_repair=0.3)
    b = FaultModel.flap_storm(5, mean_gap=2.0, mean_repair=0.3)
    assert a.flaps == b.flaps                      # fixed LCG, no RNG state
    for prev, nxt in zip(a.flaps, a.flaps[1:]):
        assert prev.end <= nxt.start               # non-overlapping
    f = LinkFlap(rail=0, start=1.0, duration=0.5)
    assert f.covers(0, 1.0) and f.covers(0, 1.49)
    assert not f.covers(0, 1.5) and not f.covers(1, 1.2)
    assert LinkFlap(rail=-1, start=0.0, duration=1.0).covers(7, 0.5)
    assert a.horizon == a.flaps[-1].end


def test_pick_victim_deterministic():
    names = [f"job{i}" for i in range(6)]
    assert pick_victim(names) == pick_victim(names)
    assert pick_victim(names, seed=1) in names
    assert pick_victim(names, seed=2) in names


# ---------------------------------------------------------------------------
# flaps: retry budget absorbs short outages, no giant-ring demotion
# ---------------------------------------------------------------------------


def test_short_flap_survives_within_retry_budget():
    wl = build(SMALL, "h200")
    fm = FaultModel(flaps=(LinkFlap(rail=-1, start=2.0, duration=0.4),))
    clean = EventEngine(wl, P, iterations=8)
    clean.run()
    eng = EventEngine(wl, P, ocs_fail=fm, iterations=8)
    eng.run()
    fs = eng.plane.fault_stats()
    assert fs["n_retries"] >= 1
    assert fs["n_flaps_survived"] >= 1
    assert fs["n_demotions"] == 0 and not fs["fallback_active"]
    # the survived run's measured iteration is counter-identical to clean
    assert _ints(eng.result.telemetry["measured"]) == \
        _ints(clean.result.telemetry["measured"])


def test_budget_exhaustion_matches_legacy_persistent_failure_exactly():
    """FaultModel with backoff=1.0 covering every attempt must reproduce
    the legacy ``lambda attempt: True`` §4.2 path bit for bit: same step
    time, same telemetry, same failure log."""
    wl = build(SMALL, "h200")
    fm = FaultModel(flaps=(LinkFlap(rail=-1, start=0.0, duration=1e9),),
                    recovery=False, backoff=1.0)
    legacy = simulate(wl, P, ocs_fail=lambda attempt: True)
    new = simulate(wl, P, ocs_fail=fm)
    assert new.step_time == legacy.step_time
    assert new.telemetry == legacy.telemetry
    assert new.telemetry["fallback_giant_ring"]
    assert any("giant ring" in s for s in new.telemetry["failure_log"])


# ---------------------------------------------------------------------------
# the tentpole: demote -> repair -> requested topology restored ->
# replay cache re-promotes -> fast-forward re-arms -> bit-exact steady
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", list(ENGINES))
def test_recovery_bit_exact_counters_all_engines(name):
    wl = build(SMALL, "h200")
    make = ENGINES[name]
    clean = make(wl, None, 30)
    clean.run()
    fm = FaultModel(flaps=(LinkFlap(rail=-1, start=2.0, duration=5.0),))
    eng = make(wl, fm, 30)
    eng.run()
    fs = eng.plane.fault_stats()
    assert fs["n_demotions"] == 1
    assert fs["n_recoveries"] == 1
    assert not fs["fallback_active"]               # repaired, not demoted
    assert not eng.plane.controller.pending_topo   # nothing left to restore
    # every integer counter delta of the measured steady iteration is
    # EXACTLY the never-faulted run's
    assert _ints(eng.result.telemetry["measured"]) == \
        _ints(clean.result.telemetry["measured"])
    # step time matches to absolute-clock float noise (the recovered
    # iteration runs at a different wall offset; (t0+d)-t0 != d in
    # binary floats)
    assert math.isclose(eng.result.step_time, clean.result.step_time,
                        rel_tol=0.0, abs_tol=1e-9)
    assert not eng.result.telemetry["fallback_giant_ring"]


def test_recovery_rearms_fast_forward():
    wl = build(SMALL, "h200")
    fm = FaultModel(flaps=(LinkFlap(rail=-1, start=2.0, duration=5.0),))
    eng = EventEngine(wl, P, ocs_fail=fm, iterations=30)
    eng.run()
    assert eng.plane.fault_stats()["n_recoveries"] == 1
    assert eng.fastforwarded_iterations > 0        # re-armed after repair
    # without recovery the demoted plane never fast-forwards (§4.2)
    eng2 = EventEngine(wl, P, ocs_fail=lambda attempt: True, iterations=30)
    eng2.run()
    assert eng2.fastforwarded_iterations == 0


def test_recovery_engine_parity():
    """The recovered steady state agrees fast-forwarding, walked live,
    and on the uncollapsed plane."""
    wl = build(SMALL, "h200")
    fm = FaultModel(flaps=(LinkFlap(rail=-1, start=2.0, duration=5.0),))
    measured = {}
    for name, make in ENGINES.items():
        eng = make(wl, fm, 30)
        eng.run()
        measured[name] = _ints(eng.result.telemetry["measured"])
        if name == "live":
            assert eng.fastforwarded_iterations == 0
    assert measured["event"] == measured["live"]
    # the full engine dispatches per rank; its equivalence-classed
    # counters still match
    assert measured["live"] == measured["event_full"]


# ---------------------------------------------------------------------------
# maintenance drains re-place every victim, zero ownership violations
# ---------------------------------------------------------------------------


def _fleet():
    return ([ClusterJobSpec(f"job{i}", SMALL, arrival=0.5 * i, iterations=6)
             for i in range(3)],
            ClusterParams(n_ports=32, ocs_latency=0.01))


def test_drain_checkpoint_restart_replaces_all_victims():
    specs, params = _fleet()
    window = DrainWindow(start=1.0, duration=3.0, ports=(0, 16))
    ops = ScenarioEngine(drains=(window,))
    res, sim = run_scenario(specs, params, ops=ops, twin=True)
    assert ops.stats["n_restarted"] == 2
    assert ops.stats["n_drain_starts"] == ops.stats["n_drain_ends"] == 1
    by = {r.spec.name: r for r in res.jobs}
    assert all(r.status == "done" for r in res.jobs)
    assert by["job0"].n_drains == 1 and by["job1"].n_drains == 1
    assert by["job2"].n_drains == 0
    drained = set(range(*window.ports))
    saw_window = False
    for row in sim.twin():
        owned = [set(v) for v in row["owners"].values()]
        # cross-tenant ownership is disjoint on every event tick
        for i, a in enumerate(owned):
            for b in owned[i + 1:]:
                assert not (a & b), row
        if row["reserved"]:
            saw_window = True
            assert set(row["reserved"]) == drained
            # nobody owns drained ports once the window's evictions ran
            if row["event"] not in ("drain_start", "drain_evict"):
                for a in owned:
                    assert not (a & drained), row
    assert saw_window


def test_drain_live_migration_preserves_progress():
    specs, params = _fleet()
    ops = ScenarioEngine(drains=(DrainWindow(start=1.0, duration=3.0,
                                             ports=(0, 16), migrate=True),))
    res, _ = run_scenario(specs, params, ops=ops)
    rst = ScenarioEngine(drains=(DrainWindow(start=1.0, duration=3.0,
                                             ports=(0, 16)),))
    res_rst, _ = run_scenario(specs, params, ops=rst)
    assert ops.stats["n_migrated"] == 2 and ops.stats["n_restarted"] == 0
    assert all(r.status == "done" for r in res.jobs)
    by = {r.spec.name: r for r in res.jobs}
    assert by["job0"].n_migrations == 1 and by["job1"].n_migrations == 1
    # live migration beats checkpoint-restart: no reload stall, no lost
    # iterations
    assert res.summary()["makespan"] < res_rst.summary()["makespan"]


def test_drain_untouched_tenant_unaffected():
    """job2 admits after the window on high ports; its result must be
    byte-identical to the undisturbed run."""
    specs, params = _fleet()
    base, _ = run_scenario(specs, params)
    ops = ScenarioEngine(drains=(DrainWindow(start=1.0, duration=3.0,
                                             ports=(0, 16), migrate=True),))
    res, _ = run_scenario(specs, params, ops=ops)
    b = {r.spec.name: r for r in base.jobs}["job2"]
    r = {r.spec.name: r for r in res.jobs}["job2"]
    assert _ints(r.result.telemetry["measured"]) == \
        _ints(b.result.telemetry["measured"])


def test_cluster_without_ops_is_byte_identical_to_pre_ops_path():
    """ops=None and twin off must change nothing: the six committed
    BENCH baselines ride this invariant."""
    specs, params = _fleet()
    a = simulate_cluster(specs, params)
    sim = ClusterSim(params)
    for s in specs:
        sim.submit(s)
    b = sim.run()
    assert a.summary() == b.summary()
    assert [r.result.step_time for r in a.jobs] == \
        [r.result.step_time for r in b.jobs]
    assert a.events == b.events


# ---------------------------------------------------------------------------
# defragmentation that ACTS on the allocator's telemetry
# ---------------------------------------------------------------------------


def _frag_trace():
    specs = []
    for i in range(8):
        long = i % 2 == 0
        specs.append(ClusterJobSpec(
            f"t{i}_{'long' if long else 'short'}", TINY, arrival=0.0,
            iterations=40 if long else 2))
    specs.append(ClusterJobSpec("big", SMALL, arrival=1.0, iterations=4))
    return specs, ClusterParams(n_ports=16, ocs_latency=0.01)


def test_defrag_unblocks_fragmentation_stuck_job():
    specs, params = _frag_trace()
    base, _ = run_scenario(specs, params)
    ops = ScenarioEngine(defrag=DefragPolicy(threshold=0.2, max_moves=4))
    res, _ = run_scenario(specs, params, ops=ops)
    assert ops.stats["n_defrag_moves"] > 0
    big0 = next(r for r in base.jobs if r.spec.name == "big")
    big1 = next(r for r in res.jobs if r.spec.name == "big")
    assert big0.queueing_delay > 3.0               # frag-blocked baseline
    assert big1.queueing_delay == 0.0              # compaction admits it
    assert res.summary()["mean_queueing_delay"] < \
        base.summary()["mean_queueing_delay"]


# ---------------------------------------------------------------------------
# multi-job fault isolation on shared rails
# ---------------------------------------------------------------------------


def test_cluster_flap_victim_isolated_from_other_tenants():
    specs, params = _fleet()
    clean = simulate_cluster(specs, params)
    victim = pick_victim([s.name for s in specs])
    fm = FaultModel.flap_storm(8, mean_gap=0.8, mean_repair=0.5)
    res = simulate_cluster(specs, params, ocs_fail_by_job={victim: fm})
    vrec = next(r for r in res.jobs if r.spec.name == victim)
    fs = vrec.plane.fault_stats()
    assert fs["n_retries"] > 0                     # the storm actually hit
    clean_by = {r.spec.name: r for r in clean.jobs}
    for r in res.jobs:
        if r.spec.name == victim:
            continue
        assert r.result.telemetry["measured"] == \
            clean_by[r.spec.name].result.telemetry["measured"]
        assert not r.result.telemetry["failure_log"]


# ---------------------------------------------------------------------------
# typed contract exceptions: catchable, and alive under python -O
# ---------------------------------------------------------------------------


def test_typed_exceptions_are_assertion_subclasses():
    assert issubclass(PortOwnershipError, AssertionError)
    assert issubclass(MigrationContractError, AssertionError)


def test_allocator_move_contract_and_ownership_errors():
    a = PortAllocator(8, "contiguous")
    a.allocate("x", 4)
    a.allocate("y", 4)
    with pytest.raises(MigrationContractError):
        a.move("x", (4, 5, 6))                     # 4 held vs 3 destination
    with pytest.raises(PortOwnershipError):
        a.move("x", (4, 5, 6, 7))                  # y's ports
    a.release("y")
    old = a.move("x", (4, 5, 6, 7))
    assert old == (0, 1, 2, 3)
    assert a.owner.get(4) == "x" and a.owner.get(0) is None


def test_allocator_reserve_and_peek():
    a = PortAllocator(8, "contiguous")
    before = a.stats()
    assert a.peek(4) == (0, 1, 2, 3)
    assert a.stats() == before                     # peek never mutates
    a.reserve(range(0, 4))
    assert a.allocate("x", 8) is None              # reserved space blocks
    assert a.peek(4) == (4, 5, 6, 7)
    assert a.peek(4, below=4) is None
    a.unreserve(range(0, 4))
    assert a.allocate("x", 8) is not None


def test_orchestrator_evacuate_contract_errors():
    params = ClusterParams(n_ports=16, ocs_latency=0.01)
    sim = ClusterSim(params)
    plane = ControlPlane(SMALL, mode=SHIM_MODE["opus_prov"], job_id="a",
                         spec=sim.spec, collapse=True,
                         orchestrators=sim.rails, ports=tuple(range(8)))
    orch = sim.rails[0]
    with pytest.raises(MigrationContractError):
        orch.evacuate("a", tuple(range(8, 11)))    # 8 src vs 3 dst
    with pytest.raises(PortOwnershipError):
        orch.evacuate("a", tuple(range(4, 12)))    # overlaps a's own home
    with pytest.raises(PortOwnershipError):
        ControlPlane(SMALL, mode=SHIM_MODE["opus_prov"], job_id="b",
                     spec=sim.spec, collapse=True,
                     orchestrators=sim.rails, ports=tuple(range(4, 12)))
    plane.release()


def test_ownership_checks_survive_python_O():
    """The dispatch-path contract checks are real raises, not ``assert``
    statements -O strips — scenario code can rely on them in optimized
    runs."""
    code = (
        "from repro.core.faults import PortOwnershipError, "
        "MigrationContractError\n"
        "from repro.core.orchestrator import PortAllocator\n"
        "assert True is None, 'asserts must be stripped under -O'\n"
        "a = PortAllocator(8, 'contiguous')\n"
        "a.allocate('x', 4); a.allocate('y', 4)\n"
        "try:\n"
        "    a.move('x', (4, 5, 6, 7))\n"
        "except PortOwnershipError:\n"
        "    pass\n"
        "else:\n"
        "    raise SystemExit('ownership check vanished under -O')\n"
        "try:\n"
        "    a.move('x', (4, 5))\n"
        "except MigrationContractError:\n"
        "    pass\n"
        "else:\n"
        "    raise SystemExit('contract check vanished under -O')\n"
        "print('SURVIVED')\n"
    )
    src = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run([sys.executable, "-O", "-c", code],
                         capture_output=True, text=True,
                         env={"PYTHONPATH": src})
    assert out.returncode == 0, out.stderr
    assert "SURVIVED" in out.stdout


# ---------------------------------------------------------------------------
# digital twin: export, determinism, diffability
# ---------------------------------------------------------------------------


def test_twin_rows_deterministic_and_jsonl_roundtrip(tmp_path):
    specs, params = _fleet()
    _, sim_a = run_scenario(specs, params, twin=True)
    _, sim_b = run_scenario(specs, params, twin=True)
    d = diff_twin(sim_a.twin(), sim_b.twin())
    assert d.identical                             # same scenario, same fleet
    path = tmp_path / "twin.jsonl"
    n = write_twin_jsonl(sim_a.twin(), str(path))
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    assert n == len(rows) == len(sim_a.twin())
    assert rows == json.loads(json.dumps(sim_a.twin()))  # tuples -> lists
    row = rows[0]
    for key in ("t", "event", "job", "owners", "reserved", "running",
                "queued", "switches", "circuits"):
        assert key in row
    sw = row["switches"][0]
    for key in ("rail", "technology", "n_circuits", "n_program_calls",
                "n_ports_programmed", "busy_until"):
        assert key in sw


def test_twin_diff_surfaces_scenario_divergence():
    specs, params = _fleet()
    _, sim_a = run_scenario(specs, params, twin=True)
    ops = ScenarioEngine(drains=(DrainWindow(start=1.0, duration=3.0,
                                             ports=(0, 16)),))
    _, sim_b = run_scenario(specs, params, ops=ops, twin=True)
    d = diff_twin(sim_a.twin(), sim_b.twin())
    assert not d.identical
    assert d.n_rows_b > d.n_rows_a                 # evict/drain event rows
    assert d.n_differing_rows > 0 and d.n_diffs >= d.n_differing_rows
    assert d.samples and all({"row", "key", "a", "b"} <= set(s)
                             for s in d.samples)
    assert sim_a.twin()[0] == sim_b.twin()[0]      # identical until t=1.0
