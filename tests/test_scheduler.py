"""Scheduler-granularity axis (DESIGN.md §13).

Four layers of coverage:

* bit-identity — ``scheduler="phase_boundary"`` passed explicitly must
  reproduce the default path exactly (step time AND every integer
  counter) across the paper configs x the backend axis, and the event
  engine, its live walk and ``event_full`` agree under both schedulers;
  the default scheduler is the committed-baseline contract the perf
  gate enforces.
* per-collective decomposition — round counts, variants, byte
  conservation and compute placement of the rewritten op stream.
* the fabric still rules — radix holes on an OCS array and mid-round
  fault demotion apply to per-collective rounds unchanged.
* canonicalization — the ``repro.core.fabricspec`` and
  ``orchestrator.OCSDriver`` aliases resolve to the blessed surface
  and warn.
"""
import pytest

from repro.configs.base import get_config
from repro.core.fabric import CrossbarOCS, CrossSubSwitchError, FabricSpec
from repro.core.phases import CommOp, JobConfig
from repro.core.scheduler import (PerCollectiveScheduler,
                                  PhaseBoundaryScheduler, get_scheduler)
from repro.sim.opus_sim import EventEngine, SimParams, simulate
from repro.sim.workload import build

# the paper's dense Configs 1-2 plus two EP-heavy MoE shapes — the
# configs the scheduler axis was built for
PAPER_JOBS = {
    "config1": ("llama3_8b", dict(tp=4, fsdp=2, pp=2, global_batch=16,
                                  seq_len=8192)),
    "config2": ("llama3_8b", dict(tp=4, fsdp=8, pp=2, global_batch=64,
                                  seq_len=8192)),
    "deepseek_moe": ("deepseek_moe_16b",
                     dict(tp=2, fsdp=2, ep=4, pp=1, global_batch=32,
                          seq_len=4096)),
    "granite_moe": ("granite_moe_1b_a400m",
                    dict(tp=2, fsdp=2, ep=4, pp=1, global_batch=16,
                         seq_len=4096)),
}

# one cell per switch technology (DESIGN.md §10), in its natural mode
BACKEND_CELLS = (
    ("native", None, None),            # packet
    ("oneshot", None, None),           # patch panel
    ("opus_prov", "crossbar_ocs", None),
    ("opus_prov", "ocs_array", 64),
)


@pytest.fixture(scope="module")
def workloads():
    return {key: build(JobConfig(model=get_config(name), **shape), "h200")
            for key, (name, shape) in PAPER_JOBS.items()}


@pytest.fixture(scope="module")
def moe_wl(workloads):
    return workloads["deepseek_moe"]


def _assert_identical(a, b):
    """Bit-identical results: the floats exactly equal, every counter
    matching — the same contract check_perf holds baselines to."""
    assert a.step_time == b.step_time
    assert a.n_reconfigs == b.n_reconfigs
    assert a.n_topo_writes == b.n_topo_writes
    assert a.exposed_reconfig == b.exposed_reconfig
    assert a.exposed_control == b.exposed_control
    if a.telemetry is None or b.telemetry is None:
        assert a.telemetry == b.telemetry
        return
    assert a.telemetry["measured"] == b.telemetry["measured"]
    assert (a.telemetry["fallback_giant_ring"]
            == b.telemetry["fallback_giant_ring"])


# ---------------------------------------------------------------------------
# bit-identity of the default scheduler
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("jobkey", sorted(PAPER_JOBS))
@pytest.mark.parametrize("mode,backend,radix",
                         BACKEND_CELLS,
                         ids=[c[1] or c[0] for c in BACKEND_CELLS])
def test_explicit_phase_boundary_is_bit_identical(workloads, jobkey, mode,
                                                  backend, radix):
    wl = workloads[jobkey]
    kw = {} if backend is None else {"backend": backend, "radix": radix}
    base = simulate(wl, SimParams(mode=mode, ocs_latency=0.01, **kw))
    expl = simulate(wl, SimParams(mode=mode, ocs_latency=0.01,
                                  scheduler="phase_boundary", **kw))
    _assert_identical(base, expl)


@pytest.mark.parametrize("scheduler", ["phase_boundary", "per_collective"])
def test_three_way_engine_parity(moe_wl, scheduler):
    """event / the live walk (a never-firing injector) / event_full
    agree bit-for-bit under BOTH schedulers — the rewritten op stream is
    just a stream to them."""
    p = SimParams(mode="opus_prov", ocs_latency=0.01, scheduler=scheduler)
    ref = simulate(moe_wl, p, engine="event")
    _assert_identical(ref, EventEngine(
        moe_wl, p, ocs_fail=lambda attempt: False).run())
    _assert_identical(ref, simulate(moe_wl, p, engine="event_full"))


def test_analytic_engine_matches_event_on_default_path(workloads):
    wl = workloads["config1"]
    p = SimParams(mode="opus", ocs_latency=0.05)
    ev = simulate(wl, p, engine="event")
    an = simulate(wl, p, engine="analytic")
    assert an.step_time == pytest.approx(ev.step_time, rel=1e-9)
    assert an.n_reconfigs == ev.n_reconfigs


# ---------------------------------------------------------------------------
# per-collective round decomposition
# ---------------------------------------------------------------------------

MOE_JOB = JobConfig(model=get_config("deepseek_moe_16b"), tp=2, fsdp=4,
                    ep=8, pp=1, global_batch=64, seq_len=2048)
MB = float(1 << 20)


def _op(kind, nbytes, dim="ep", scale="scale_out", compute=1.5):
    return CommOp(uid=0, dim=dim, kind=kind, way=-1, microbatch=0,
                  bytes_per_gpu=nbytes, scale=scale,
                  compute_before=compute)


def test_a2a_becomes_shift_rounds():
    """k-1 shift rounds, variants 1..k-1, direct bytes split evenly,
    compute carried by the first round only."""
    sched = PerCollectiveScheduler()
    rounds = sched.schedule([_op("all_to_all", 56 * MB)], MOE_JOB,
                            circuit=True)
    k = MOE_JOB.ep
    assert len(rounds) == k - 1
    assert [r.variant for r in rounds] == list(range(1, k))
    assert sum(r.bytes_per_gpu for r in rounds) == pytest.approx(56 * MB)
    assert rounds[0].compute_before == 1.5
    assert all(r.compute_before == 0.0 for r in rounds[1:])
    assert [r.uid for r in rounds] == list(range(k - 1))


def test_ag_ring_rounds_keep_variant_zero():
    """Ring rounds never leave the phase's shift-1 ring: granularity
    changes, the wiring does not."""
    sched = PerCollectiveScheduler()
    rounds = sched.schedule([_op("all_gather", 8 * MB, dim="fsdp")],
                            MOE_JOB, circuit=True)
    k = MOE_JOB.fsdp
    assert len(rounds) == k - 1
    assert all(r.variant == 0 for r in rounds)
    assert sum(r.bytes_per_gpu for r in rounds) == pytest.approx(8 * MB)


def test_halving_rounds_xor_ladder():
    """halving mode: AG walks d = 1, 2, 4 (recursive doubling), RS the
    reverse, each round an XOR matching carrying d/(k-1) of the bytes."""
    sched = PerCollectiveScheduler(collective_rounds="halving")
    ag = sched.schedule([_op("all_gather", 7 * MB, dim="ep")], MOE_JOB,
                        circuit=True)
    rs = sched.schedule([_op("reduce_scatter", 7 * MB, dim="ep")],
                        MOE_JOB, circuit=True)
    assert [r.variant for r in ag] == [-1, -2, -4]
    assert [r.variant for r in rs] == [-4, -2, -1]
    for rounds in (ag, rs):
        # byte ladder: round at distance d carries d/(k-1) of the total
        for r in rounds:
            assert r.bytes_per_gpu == pytest.approx(abs(r.variant) * MB)
        assert sum(r.bytes_per_gpu for r in rounds) == pytest.approx(7 * MB)
        assert rounds[0].compute_before == 1.5


def test_halving_falls_back_to_ring_off_power_of_two():
    job = JobConfig(model=get_config("llama3_8b"), tp=4, fsdp=6, pp=1,
                    global_batch=24, seq_len=2048)
    sched = PerCollectiveScheduler(collective_rounds="halving")
    rounds = sched.schedule([_op("all_gather", 6 * MB, dim="fsdp")], job,
                            circuit=True)
    assert len(rounds) == job.fsdp - 1          # ring fallback
    assert all(r.variant == 0 for r in rounds)


def test_all_reduce_composes_rs_then_ag():
    sched = PerCollectiveScheduler()
    rounds = sched.schedule([_op("all_reduce", 14 * MB, dim="fsdp")],
                            MOE_JOB, circuit=True)
    k = MOE_JOB.fsdp
    assert len(rounds) == 2 * (k - 1)
    kinds = [r.kind for r in rounds]
    assert kinds == ["reduce_scatter"] * (k - 1) + ["all_gather"] * (k - 1)
    assert sum(r.bytes_per_gpu for r in rounds) == pytest.approx(14 * MB)


def test_small_collectives_pass_through_undecomposed():
    """Below min_bytes nothing decomposes — but an all-to-all left on
    the phase ring still pays the k-hop forwarding tax (it executes
    there, whoever scheduled it)."""
    sched = PerCollectiveScheduler()
    ar = sched.schedule([_op("all_reduce", 64e3, dim="fsdp")], MOE_JOB,
                        circuit=True)
    assert len(ar) == 1 and ar[0].bytes_per_gpu == 64e3
    a2a = sched.schedule([_op("all_to_all", 64e3, dim="ep")], MOE_JOB,
                         circuit=True)
    assert len(a2a) == 1
    assert a2a[0].bytes_per_gpu == 64e3 * MOE_JOB.ep


def test_scale_up_and_send_recv_untouched():
    sched = PerCollectiveScheduler()
    ops = [_op("all_gather", 50 * MB, dim="tp", scale="scale_up"),
           _op("send_recv", 50 * MB, dim="pp")]
    out = sched.schedule(ops, MOE_JOB, circuit=True)
    assert [(o.kind, o.bytes_per_gpu) for o in out] == \
        [(o.kind, o.bytes_per_gpu) for o in ops]
    assert [o.uid for o in out] == [0, 1]       # renumbered dense


def test_phase_boundary_taxes_a2a_on_circuits_only():
    sched = PhaseBoundaryScheduler()
    ops = [_op("all_to_all", 8 * MB, dim="ep")]
    packet = sched.schedule(ops, MOE_JOB, circuit=False)
    assert packet[0].bytes_per_gpu == 8 * MB
    circuit = sched.schedule(ops, MOE_JOB, circuit=True)
    assert circuit[0].bytes_per_gpu == 8 * MB * MOE_JOB.ep


def test_scheduler_registry():
    assert get_scheduler("phase_boundary").name == "phase_boundary"
    assert get_scheduler("per_collective").name == "per_collective"
    with pytest.raises(ValueError, match="unknown scheduler"):
        get_scheduler("per_packet")


def test_per_collective_rejected_on_static_fabrics():
    with pytest.raises(ValueError, match="per_collective"):
        SimParams(mode="native", scheduler="per_collective").fabric_spec()
    with pytest.raises(ValueError, match="per_collective"):
        SimParams(mode="oneshot", scheduler="per_collective").fabric_spec()


# ---------------------------------------------------------------------------
# the fabric still rules the rounds
# ---------------------------------------------------------------------------


def test_per_collective_counts_more_reconfigs_on_moe(moe_wl):
    """The whole point of the axis: per-collective buys direct routing
    with extra reconfigurations — the counters must show both."""
    pb = simulate(moe_wl, SimParams(mode="opus_prov", ocs_latency=0.001,
                                    scheduler="phase_boundary"))
    pc = simulate(moe_wl, SimParams(mode="opus_prov", ocs_latency=0.001,
                                    scheduler="per_collective"))
    assert pc.n_reconfigs > pb.n_reconfigs
    assert pc.n_topo_writes > pb.n_topo_writes


def test_per_collective_a2a_respects_sub_switch_radix(moe_wl):
    """Shift-variant rounds are wired inside the job's sub-switch: a
    radix that holds the job runs identically to the crossbar, one that
    cannot hold it is a hard CrossSubSwitchError, not silent spanning."""
    xbar = simulate(moe_wl, SimParams(mode="opus_prov", ocs_latency=0.01,
                                      scheduler="per_collective"))
    arr = simulate(moe_wl, SimParams(mode="opus_prov", ocs_latency=0.01,
                                     backend="ocs_array", radix=16,
                                     scheduler="per_collective"))
    _assert_identical(xbar, arr)
    with pytest.raises(CrossSubSwitchError):
        simulate(moe_wl, SimParams(mode="opus_prov", ocs_latency=0.01,
                                   backend="ocs_array", radix=4,
                                   scheduler="per_collective"))


def test_fault_demotes_job_mid_round(moe_wl):
    """A persistent OCS failure during per-collective rounds triggers
    the §4.2 giant-ring fallback exactly as it does for phase wiring."""
    p = SimParams(mode="opus_prov", ocs_latency=0.01,
                  scheduler="per_collective")
    ok = simulate(moe_wl, p)
    bad = simulate(moe_wl, p, ocs_fail=lambda attempt: True)
    assert ok.telemetry["fallback_giant_ring"] is False
    assert bad.telemetry["fallback_giant_ring"] is True
    # demoted: the rails stop reprogramming entirely (the fault may even
    # come out ahead of paying hundreds of per-round reconfigs — the
    # giant ring trades reconfig cost for bandwidth dilation)
    assert ok.n_reconfigs > 0
    assert bad.n_reconfigs == 0
    assert bad.exposed_reconfig == 0.0
    # ...but the dilation is real: slower than a healthy fabric whose
    # reconfigurations cost nothing
    free = simulate(moe_wl, SimParams(mode="opus_prov", ocs_latency=0.0,
                                      scheduler="per_collective"))
    assert bad.step_time > free.step_time


def test_crossover_economics():
    """The headline trade on a genuinely EP-heavy shape: per-collective
    wins when rounds are cheap, and the win shrinks as the per-round
    reconfiguration cost grows."""
    job = JobConfig(model=get_config("granite_moe_1b_a400m"), tp=2,
                    fsdp=4, ep=8, pp=1, global_batch=128, seq_len=8192)
    wl = build(job, "h200")

    def step(sched, lat):
        return simulate(wl, SimParams(mode="opus_prov", ocs_latency=lat,
                                      scheduler=sched)).step_time

    assert step("per_collective", 0.001) < step("phase_boundary", 0.001)
    assert step("per_collective", 0.01) > step("per_collective", 0.001)
    win_fast = step("phase_boundary", 0.001) - step("per_collective", 0.001)
    win_slow = step("phase_boundary", 0.01) - step("per_collective", 0.01)
    assert win_slow < win_fast


# ---------------------------------------------------------------------------
# canonicalized fabric surface: the aliases warn and resolve
# ---------------------------------------------------------------------------


def test_fabricspec_module_is_deprecated_alias():
    import repro.core.fabricspec as legacy
    with pytest.warns(DeprecationWarning, match="repro.core.fabric"):
        spec_cls = legacy.FabricSpec
    assert spec_cls is FabricSpec
    with pytest.warns(DeprecationWarning):
        err_cls = legacy.CrossSubSwitchError
    assert err_cls is CrossSubSwitchError


def test_ocsdriver_is_deprecated_alias_of_crossbar():
    from repro.core import orchestrator
    with pytest.warns(DeprecationWarning, match="CrossbarOCS"):
        drv = orchestrator.OCSDriver
    assert drv is CrossbarOCS
