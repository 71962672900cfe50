"""The event engine's fast-forward (DESIGN.md §12): bit-exact parity of
``engine="event"``, the live walk and ``engine="event_full"`` over the
paper configs, fast-forward integer exactness beyond the captured steady
iteration, runtime-sized tenants, and the shared-rail cluster numbers
equal to an all-live cluster's.

The live walk is the same engine on a plane with a fault injector that
never fires: a faultable plane never fast-forwards."""
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.phases import (JobConfig, build_phase_table,
                               iteration_schedule, phase_index_of)
from repro.sim.cluster import ClusterParams, catalog_jobs, simulate_cluster
from repro.sim.opus_sim import EventEngine, SimParams, simulate
from repro.sim.workload import build

LLAMA = get_config("llama3_8b")
# the four paper configs the parity contract covers: dense pp=2, wide
# fsdp, deep pp=4 with MoE EP phases, and a CP mesh
PAPER_CONFIGS = (
    JobConfig(model=LLAMA, tp=4, fsdp=2, pp=2, global_batch=16,
              seq_len=8192),
    JobConfig(model=LLAMA, tp=4, fsdp=8, pp=2, global_batch=64,
              seq_len=8192),
    JobConfig(model=get_config("deepseek_v3_16b"), tp=4, fsdp=1, pp=4,
              global_batch=8, seq_len=2048),
    JobConfig(model=LLAMA, tp=2, fsdp=4, pp=2, cp=2, global_batch=32,
              seq_len=8192),
)
MODES = ("native", "oneshot", "opus", "opus_prov")


def _tel_no_calls(tel):
    """Telemetry minus the per-engine call-shape stats (the collapsed
    and uncollapsed planes legitimately differ in n_classes/n_plane_
    calls; everything else must match exactly)."""
    return {k: v for k, v in tel.items() if k != "calls"}


def _params(mode):
    return SimParams(mode=mode, ocs_latency=0.01)


def _never(attempt):
    """A fault injector that never fires: the plane stays healthy, but
    the engine walks every iteration live."""
    return False


def _live(wl, params, **kw):
    return EventEngine(wl, params, ocs_fail=_never, **kw)


@pytest.mark.parametrize("job", PAPER_CONFIGS,
                         ids=[f"cfg{i}" for i in range(len(PAPER_CONFIGS))])
@pytest.mark.parametrize("mode", MODES)
def test_three_way_parity(job, mode):
    """engine="event", the live walk, and "event_full" agree
    bit-exactly: step time, every counter, every measured delta, the
    whole timeline."""
    wl = build(job, "h200")
    vec = simulate(wl, _params(mode))
    live = _live(wl, _params(mode)).run()
    full = simulate(wl, _params(mode), engine="event_full")
    for other in (live, full):
        assert vec.step_time == other.step_time
        assert vec.n_reconfigs == other.n_reconfigs
        assert vec.n_topo_writes == other.n_topo_writes
        assert vec.exposed_reconfig == other.exposed_reconfig
        assert vec.exposed_control == other.exposed_control
        assert vec.timeline == other.timeline
        assert _tel_no_calls(vec.telemetry) == _tel_no_calls(
            other.telemetry)


@pytest.mark.parametrize("mode", ("opus", "opus_prov"))
def test_parity_under_persistent_fault_demotion(mode):
    """A persistently failing OCS demotes the job to the §4.2 giant-ring
    fallback; the engine must take the demotion live (never fast-forward
    a faulted plane) and stay bit-exact with the uncollapsed plane."""
    job = PAPER_CONFIGS[1]
    wl = build(job, "h200")
    vec, full = [simulate(wl, _params(mode), engine=eng,
                          ocs_fail=lambda attempt: True)
                 for eng in ("event", "event_full")]
    assert vec.telemetry["fallback_giant_ring"]
    assert vec.step_time == full.step_time
    assert vec.timeline == full.timeline
    assert _tel_no_calls(vec.telemetry) == _tel_no_calls(full.telemetry)
    # demoted planes never capture a replay schedule to fast-forward
    engine = EventEngine(wl, _params(mode),
                         ocs_fail=lambda attempt: True, iterations=6)
    engine.run()
    assert engine.fastforwarded_iterations == 0


@pytest.mark.parametrize("mode", ("opus", "opus_prov", "oneshot"))
def test_fastforward_integer_exactness(mode):
    """Beyond the captured steady iteration the engine jumps k
    iterations in one array op: every integer counter must land EXACTLY
    where the live walk lands, and the clock within float accumulation
    noise."""
    job = PAPER_CONFIGS[0]
    wl = build(job, "h200")
    iters = 9
    vec = EventEngine(wl, _params(mode), iterations=iters)
    vec.run()
    live = _live(wl, _params(mode), iterations=iters)
    live.run()
    assert vec.fastforwarded_iterations > 0
    assert live.fastforwarded_iterations == 0
    v_tel, l_tel = vec.result.telemetry, live.result.telemetry
    for key, lv in _tel_no_calls(l_tel).items():
        vv = v_tel[key]
        if isinstance(lv, dict):
            assert {k: x for k, x in vv.items()
                    if isinstance(x, int)} \
                == {k: x for k, x in lv.items() if isinstance(x, int)}, key
        elif isinstance(lv, int) and not isinstance(lv, bool):
            assert vv == lv, key
    assert v_tel["measured"] == l_tel["measured"]
    # the jumped clock is t += k * step_time where the live walk
    # re-accumulates per op: equal to float-accumulation noise, not ulp
    assert vec.result.step_time == pytest.approx(live.result.step_time,
                                                 rel=1e-9)
    assert vec.t == pytest.approx(live.t, rel=1e-9)


def test_fastforward_and_live_iterations_partition():
    job = PAPER_CONFIGS[0]
    wl = build(job, "h200")
    engine = EventEngine(wl, _params("opus_prov"), iterations=12)
    engine.run()
    # the warmup and the captured first replayed iteration run live;
    # every steady iteration after that fast-forwards
    assert engine.fastforwarded_iterations == 12 - 2


def test_min_runtime_fastforwards_to_target():
    job = PAPER_CONFIGS[0]
    wl = build(job, "h200")
    engine = EventEngine(wl, _params("opus_prov"),
                         min_runtime_s=3600.0, start=5.0)
    engine.run()
    step = engine.result.step_time
    assert engine.t >= 5.0 + 3600.0
    # departs at the FIRST iteration boundary past the target
    assert engine.t - step < 5.0 + 3600.0
    assert engine.fastforwarded_iterations > 100


def test_cluster_numbers_are_engine_invariant():
    """The shared-rail cluster point produces the same summary (every
    counter exact, every float identical) whether tenants may fast-
    forward or every tenant walks live (a never-firing injector each)."""
    specs = catalog_jobs(4, 16, mean_gap=0.5)
    params = ClusterParams(n_ports=64, policy="contiguous",
                           ocs_latency=0.01)
    vec = simulate_cluster(specs, params).summary()
    live = simulate_cluster(specs, params, ocs_fail_by_job={
        s.name: _never for s in specs}).summary()
    assert vec == live


def test_cluster_runtime_tenants_depart_at_runtime():
    week = 7 * 86400.0
    specs = catalog_jobs(3, 16, mean_gap=10.0, runtime_s=week)
    res = simulate_cluster(specs, ClusterParams(n_ports=64,
                                                ocs_latency=0.01))
    s = res.summary()
    assert s["n_done"] == 3
    for rec in res.jobs:
        held = rec.finished - rec.admitted
        assert held >= week
        # at most one extra steady iteration past the target
        assert held < week + 2 * rec.result.step_time


def test_phase_index_of_is_int64_vector():
    job = PAPER_CONFIGS[0]
    ops = iteration_schedule(job)
    table = build_phase_table(ops)
    idx = phase_index_of(ops, table)
    assert isinstance(idx, np.ndarray)
    assert idx.dtype == np.int64
    assert len(idx) == len(ops)
    # every scale-out op maps into the table, in non-decreasing order
    mapped = idx[idx >= 0]
    assert np.all(np.diff(mapped) >= 0)
    assert mapped.max() == len(table) - 1
    # non-comm ops (mgmt / scale-up) carry the -1 sentinel
    for op, pi in zip(ops, idx.tolist()):
        assert (pi >= 0) == (op.scale == "scale_out")


def test_workload_tables_shared_by_config_identity():
    """build() is lru-cached on (job, gpu) and the phase tables cache on
    the instance: every tenant of a shared config reuses ONE table."""
    job = PAPER_CONFIGS[0]
    a = build(job, "h200")
    b = build(JobConfig(model=LLAMA, tp=4, fsdp=2, pp=2, global_batch=16,
                        seq_len=8192), "h200")
    assert a is b
    assert a.phase_info() is b.phase_info()
    assert a.shim_table() is b.shim_table()
    assert a.phase_info()[0] == build_phase_table(a.ops)


def test_min_runtime_rejects_zero_length_iterations():
    job = PAPER_CONFIGS[0]
    wl = build(job, "h200")
    empty = wl.__class__(job=wl.job, gpu=wl.gpu, ops=[],
                         t_fwd_layer=0.0, t_bwd_layer=0.0)
    engine = EventEngine(empty, _params("opus_prov"),
                         min_runtime_s=10.0)
    with pytest.raises(ValueError):
        engine.run()


def test_vector_engine_reports_event_engine_name():
    wl = build(PAPER_CONFIGS[0], "h200")
    r = simulate(wl, _params("opus_prov"))
    assert r.engine == "event"
    rf = simulate(wl, _params("opus_prov"), engine="event_full")
    assert rf.engine == "event_full"


def test_simulate_default_engine_is_vectorized():
    """The default engine path goes through EventEngine (with zero
    fast-forward at the committed 2-iteration shape, hence bit-exact
    BENCH records)."""
    wl = build(PAPER_CONFIGS[0], "h200")
    engine = EventEngine(wl, _params("opus_prov"))
    engine.run()
    assert engine.fastforwarded_iterations == 0
    assert engine.result.step_time == simulate(
        wl, _params("opus_prov")).step_time
