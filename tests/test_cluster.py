"""Multi-job cluster simulator (DESIGN.md §9): port allocation policies,
the port-isolation invariant (including mid-barrier fault demotion on
shared rails), single-job bit-exactness against the single-job engine,
FIFO queueing, determinism, and the <10 s acceptance scale point."""
import math
import time

import pytest

from repro.configs.base import get_config
from repro.core.fabric import CrossbarOCS
from repro.core.orchestrator import (PortAllocator,
                                     RailOrchestrator)
from repro.core.phases import JobConfig
from repro.core.plane import ControlPlane, build_placement
from repro.core.shim import PROVISIONING
from repro.core.topo import TopoId
from repro.sim.cluster import (ClusterJobSpec, ClusterParams, catalog_jobs,
                               exp_trace, simulate_cluster)
from repro.sim.opus_sim import EventEngine, SimParams, simulate
from repro.sim.workload import build

CFG = get_config("llama3_8b")
SMALL = JobConfig(model=CFG.replace(n_layers=4), tp=2, fsdp=4, pp=2,
                  global_batch=32, seq_len=2048)   # 8 scale-out ranks


# ---------------------------------------------------------------------------
# PortAllocator
# ---------------------------------------------------------------------------


def test_contiguous_allocation_first_fit():
    a = PortAllocator(16, "contiguous")
    assert a.allocate("a", 4) == (0, 1, 2, 3)
    assert a.allocate("b", 4) == (4, 5, 6, 7)
    a.release("a")
    # first fit re-uses the freed leading run
    assert a.allocate("c", 3) == (0, 1, 2)
    assert a.utilization() == 7 / 16


def test_contiguous_fragmentation_rejects_where_fragmented_admits():
    """The classic external-fragmentation scenario: enough total free
    ports, no contiguous run — the policy split quantifies exactly this."""
    for policy, expect_grant in (("contiguous", False), ("fragmented", True)):
        a = PortAllocator(12, policy)
        assert a.allocate("a", 4) is not None
        assert a.allocate("b", 4) is not None
        assert a.allocate("c", 4) is not None
        a.release("a")
        a.release("c")                 # free = [0..3] + [8..11], split
        grant = a.allocate("d", 6)
        assert (grant is not None) == expect_grant, policy
        if expect_grant:
            assert grant == (0, 1, 2, 3, 8, 9)
        else:
            assert a.n_failed_allocs == 1


def test_fragmentation_metric():
    a = PortAllocator(12, "contiguous")
    assert a.fragmentation() == 0.0            # one free run
    a.allocate("a", 4)
    a.allocate("b", 4)
    a.allocate("c", 4)
    assert a.fragmentation() == 0.0            # full: defined as 0
    a.release("b")                             # one run again
    assert a.fragmentation() == 0.0
    a.release("a")                             # runs of 8... wait: [0..7]
    assert a.fragmentation() == 0.0            # coalesced [0..7]
    a.allocate("d", 2)                         # [2..7] free + nothing else
    a.release("c")                             # [2..7]+[8..11] coalesce
    assert a.fragmentation() == 0.0
    b = PortAllocator(12, "contiguous")
    b.allocate("x", 4)
    b.allocate("y", 4)
    b.allocate("z", 4)
    b.release("x")
    b.release("z")                             # free runs of 4 and 4
    assert b.fragmentation() == pytest.approx(0.5)
    assert b.free_runs() == [(0, 4), (8, 4)]


def test_allocator_double_grant_rejected():
    a = PortAllocator(8)
    a.allocate("a", 2)
    with pytest.raises(AssertionError):
        a.allocate("a", 2)


# ---------------------------------------------------------------------------
# the isolation invariant (acceptance criterion)
# ---------------------------------------------------------------------------


def test_register_rejects_port_overlap():
    orch = RailOrchestrator(0, CrossbarOCS(n_ports=32))
    orch.register_job(build_placement(SMALL, "a"), TopoId.uniform(2, 1))
    clash = build_placement(SMALL, "b")        # identity ports again
    with pytest.raises(AssertionError):
        orch.register_job(clash, TopoId.uniform(2, 1))


def test_apply_rejects_foreign_ports():
    """A job whose placement names ports it does not own is stopped at
    dispatch, before any OCS programming."""
    orch = RailOrchestrator(0, CrossbarOCS(n_ports=32))
    pl_a = build_placement(SMALL, "a")
    orch.register_job(pl_a, TopoId.uniform(2, 1))
    # adversarial: swap job b's state to point at a's ports post-register
    ports_b = tuple(range(8, 16))
    pl_b = build_placement(SMALL, "b", ports=ports_b)
    orch.register_job(pl_b, TopoId.uniform(2, 1))
    orch.jobs["b"].placement = pl_a            # b now claims a's ports
    for w in range(2):
        from repro.core.topo import build_submapping
        orch.jobs["b"].submaps[w] = build_submapping(pl_a,
                                                     TopoId.uniform(2, 1), w)
    with pytest.raises(AssertionError):
        orch.apply("b", TopoId((0, 0)))
    with pytest.raises(AssertionError):
        orch.apply_giant_ring("b")


def _shared_two_planes(ocs_fail_b=None):
    """Two jobs on one shared rail, planes driven by hand."""
    rail = RailOrchestrator(0, CrossbarOCS(n_ports=32,
                                         reconfig_latency=0.01))
    plane_a = ControlPlane(SMALL, mode=PROVISIONING, job_id="a",
                           collapse=True, orchestrators=[rail],
                           ports=tuple(range(8)))
    plane_b = ControlPlane(SMALL, mode=PROVISIONING, job_id="b",
                           collapse=True, orchestrators=[rail],
                           ports=tuple(range(8, 16)), ocs_fail=ocs_fail_b)
    return rail, plane_a, plane_b


def test_isolation_under_mid_barrier_fault_demotion():
    """Job b suffers a persistent OCS failure mid-barrier and demotes to
    its giant ring; job a's circuits on the SAME switch are untouched,
    and b's ring stays strictly inside b's grant."""
    wl = build(SMALL, "a100")
    rail, plane_a, plane_b = _shared_two_planes(ocs_fail_b=lambda at: True)
    for p in (plane_a, plane_b):
        p.profile(wl.ops)
        p.start_iteration()
    ports_a = set(range(8))
    ports_b = set(range(8, 16))
    t = 0.0
    for op in wl.ops:
        if op.scale != "scale_out":
            continue
        t += 1.0
        a_before = {p: rail.ocs.connected(p) for p in ports_a}
        plane_b.pre_comm_all(op, now=t)
        plane_b.post_comm_all(op, now=t)
        a_after = {p: rail.ocs.connected(p) for p in ports_a}
        assert a_before == a_after       # b NEVER programs a's ports
        plane_a.pre_comm_all(op, now=t)
        plane_a.post_comm_all(op, now=t)
    assert plane_b.fallback_giant_ring
    assert not plane_a.fallback_giant_ring
    # b's fallback ring is a cycle over exactly b's ports
    b_circuits = {p: d for p, d in rail.ocs.circuits.items()
                  if p in ports_b}
    assert set(b_circuits) == ports_b
    assert all(d in ports_b for d in b_circuits.values())
    # per-job telemetry never mixes tenants
    tel_a = plane_a.telemetry()
    tel_b = plane_b.telemetry()
    assert not tel_a["failure_log"] and tel_b["failure_log"]
    assert tel_a["n_ports_programmed"] + tel_b["n_ports_programmed"] == \
        rail.ocs.n_ports_programmed


def test_cluster_run_with_faulted_tenant_keeps_neighbours_healthy():
    """End to end through ClusterSim: one tenant demotes to the giant
    ring, the others finish with clean telemetry and normal overhead."""
    specs = [ClusterJobSpec(f"job{i}", SMALL, arrival=0.5 * i)
             for i in range(3)]
    res = simulate_cluster(specs, ClusterParams(n_ports=32,
                                                ocs_latency=0.01),
                           ocs_fail_by_job={"job1": lambda at: True})
    by_name = {r.spec.name: r for r in res.jobs}
    assert all(r.status == "done" for r in res.jobs)
    assert by_name["job1"].result.telemetry["fallback_giant_ring"]
    for name in ("job0", "job2"):
        assert not by_name[name].result.telemetry["fallback_giant_ring"]
        assert not by_name[name].result.telemetry["failure_log"]


# ---------------------------------------------------------------------------
# single-job bit-exactness (acceptance criterion)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["opus", "opus_prov"])
def test_single_job_cluster_is_bit_exact_with_single_job_engine(mode):
    """A cluster holding exactly one job IS the single-job engine: same
    floats, same telemetry — the cluster is a strict generalization."""
    job = JobConfig(model=CFG, tp=4, fsdp=8, pp=2, global_batch=64,
                    seq_len=8192)
    wl = build(job, "h200")
    single = simulate(wl, SimParams(mode=mode, ocs_latency=0.01))
    res = simulate_cluster(
        [ClusterJobSpec("job0", job, arrival=0.0, mode=mode)],
        ClusterParams(n_ports=16, ocs_latency=0.01, gpu="h200"))
    rec = res.jobs[0]
    assert rec.result.step_time == single.step_time          # bit-exact
    assert rec.result.n_reconfigs == single.n_reconfigs
    assert rec.result.exposed_reconfig == single.exposed_reconfig
    assert rec.result.exposed_control == single.exposed_control
    assert rec.result.telemetry == single.telemetry          # whole dict
    assert rec.queueing_delay == 0.0


def test_event_engine_generator_equals_run():
    """Draining events() by hand is the live walk's run() (a never-firing
    injector): the resumable form does not perturb the arithmetic."""
    wl = build(SMALL, "a100")
    p = SimParams(mode="opus_prov", ocs_latency=0.01)
    a = EventEngine(wl, p, ocs_fail=lambda attempt: False).run()
    eng = EventEngine(wl, p)
    clocks = list(eng.events())
    assert eng.result.step_time == a.step_time
    assert eng.result.telemetry == a.telemetry
    assert clocks == sorted(clocks)            # the clock never rewinds
    assert eng.t == clocks[-1]


# ---------------------------------------------------------------------------
# admission control / queueing
# ---------------------------------------------------------------------------


def test_fifo_queueing_delay_measured():
    """Two tenants, port space for one: the second waits for the first
    departure, and the measured queueing delay says exactly that."""
    specs = [ClusterJobSpec("a", SMALL, arrival=0.0),
             ClusterJobSpec("b", SMALL, arrival=0.1)]
    res = simulate_cluster(specs, ClusterParams(n_ports=8,
                                                ocs_latency=0.01))
    a, b = res.jobs
    assert a.status == b.status == "done"
    assert a.queueing_delay == 0.0
    assert a.finished > 0.1                    # b arrives while a runs
    assert b.admitted == a.finished            # admitted at the departure
    assert b.queueing_delay == pytest.approx(a.finished - 0.1)
    assert b.queueing_delay > 0
    assert res.allocator.n_failed_allocs >= 1
    s = res.summary()
    assert s["max_queueing_delay"] == b.queueing_delay
    assert s["peak_utilization"] == 1.0


def test_unsupported_mode_rejected_at_spec():
    """A cluster tenant must drive the real control plane on a circuit
    switch: native (packet fabric) and non-modes fail loudly.  oneshot
    IS accepted since DESIGN.md §10 — circuits patched once at
    admission, STATIC shims, zero reconfigurations contributed."""
    for mode in ("native", "analytic"):
        with pytest.raises(AssertionError):
            ClusterJobSpec("x", SMALL, mode=mode)
    assert ClusterJobSpec("x", SMALL, mode="oneshot").mode == "oneshot"


def test_infeasible_job_rejected_not_queued():
    specs = [ClusterJobSpec("big", SMALL, arrival=0.0)]
    res = simulate_cluster(specs, ClusterParams(n_ports=4))   # 8 ranks
    assert res.jobs[0].status == "rejected"
    assert res.jobs[0].result is None
    assert res.summary()["n_rejected"] == 1


def test_fifo_never_reorders_arrivals():
    """A later small job never jumps an earlier queued big one (strict
    FIFO head-of-line, documented behaviour)."""
    big = SMALL                                   # 8 ranks
    tiny = JobConfig(model=CFG.replace(n_layers=4), tp=2, fsdp=2, pp=2,
                     global_batch=16, seq_len=2048)   # 4 ranks
    specs = [ClusterJobSpec("first", big, arrival=0.0),
             ClusterJobSpec("queued_big", big, arrival=1.0),
             ClusterJobSpec("late_tiny", tiny, arrival=2.0)]
    res = simulate_cluster(specs, ClusterParams(n_ports=12))
    by = {r.spec.name: r for r in res.jobs}
    # 4 free ports while "first" runs would fit late_tiny, but FIFO holds
    assert by["late_tiny"].admitted >= by["queued_big"].admitted


# ---------------------------------------------------------------------------
# determinism (the perf gate exact-matches cluster counters)
# ---------------------------------------------------------------------------


def test_exp_trace_is_deterministic_and_exponential_ish():
    t1 = exp_trace(50, 2.0, seed=7)
    t2 = exp_trace(50, 2.0, seed=7)
    assert t1 == t2
    assert t1 == sorted(t1) and t1[0] > 0.0
    mean_gap = t1[-1] / 50
    assert 0.5 < mean_gap < 8.0                # loose sanity, not stats
    assert exp_trace(50, 2.0, seed=8) != t1


def test_cluster_is_deterministic_end_to_end():
    def once():
        specs = catalog_jobs(4, 8, mean_gap=1.0)
        return simulate_cluster(specs, ClusterParams(
            n_ports=24, ocs_latency=0.01)).summary()
    s1, s2 = once(), once()
    assert s1 == s2


# ---------------------------------------------------------------------------
# acceptance scale point: >=4 jobs, >=1024 GPUs, <10 s, real plane
# ---------------------------------------------------------------------------


def test_cluster_acceptance_scale_point():
    t0 = time.perf_counter()
    specs = catalog_jobs(4, 64, mean_gap=2.0)
    res = simulate_cluster(specs, ClusterParams(n_ports=288,
                                                ocs_latency=0.01))
    wall = time.perf_counter() - t0
    s = res.summary()
    assert s["n_jobs"] >= 4 and s["n_done"] == s["n_jobs"]
    assert s["total_gpus"] >= 1024
    assert wall < 10.0
    for rec in res.jobs:
        # every tenant ran the real collapsed plane with replay
        calls = rec.result.telemetry["calls"]
        assert calls["collapsed"] == 1
        assert calls["replayed_iterations"] >= 1
        m = rec.result.telemetry["measured"]
        assert m["n_barriers"] > 0


def test_cluster_benchmark_record_shape():
    """The --cluster sweep emits the record check_perf gates on."""
    from benchmarks.run import CLUSTER_SWEEP
    n_jobs, ranks, n_ports, policy = CLUSTER_SWEEP[0]
    assert n_jobs >= 4
    specs = catalog_jobs(n_jobs, ranks, mean_gap=2.0)
    res = simulate_cluster(specs, ClusterParams(n_ports=n_ports,
                                                policy=policy,
                                                ocs_latency=0.01))
    s = res.summary()
    assert s["total_gpus"] >= 1024
    assert isinstance(s["rails"]["n_queued_programs"], int)
    assert not math.isnan(s["mean_overhead_vs_native"])


def test_workload_kind_is_a_spec_field():
    """A cluster mix can include serving tenants (DESIGN.md §11): the
    workload kind rides on ClusterJobSpec without changing any default —
    train specs behave exactly as before."""
    assert ClusterJobSpec("t", SMALL).workload == "train"
    serve_job = JobConfig(model=CFG.replace(n_layers=4), tp=2, fsdp=4,
                          pp=1, global_batch=32, seq_len=2048)
    specs = [
        ClusterJobSpec("train0", SMALL, arrival=0.0),
        ClusterJobSpec("pre0", serve_job, arrival=0.5,
                       workload="serve_prefill"),
        ClusterJobSpec("dec0", serve_job, arrival=1.0,
                       workload="serve_decode", batch_slots=8),
    ]
    res = simulate_cluster(specs, ClusterParams(n_ports=24,
                                                ocs_latency=0.005))
    by = {r.spec.name: r for r in res.jobs}
    assert all(r.status == "done" for r in res.jobs)
    # serving tenants are single-phase: zero steady-state reconfigs on
    # the SHARED rails, while the training tenant reconfigures as usual
    assert by["pre0"].result.n_reconfigs == 0
    assert by["dec0"].result.n_reconfigs == 0
    assert by["train0"].result.n_reconfigs > 0
    assert by["dec0"].result.step_time < by["pre0"].result.step_time
    # a serving tenant never carries pipeline stages
    with pytest.raises(AssertionError, match="TP x FSDP"):
        ClusterJobSpec("bad", SMALL, workload="serve_decode")
    # catalog generalization: serving catalogs collapse pp into fsdp
    sspecs = catalog_jobs(3, 16, workload="serve_decode")
    assert all(sp.workload == "serve_decode" and sp.job.pp == 1
               for sp in sspecs)
    assert all(sp.n_ranks == 16 for sp in sspecs)
