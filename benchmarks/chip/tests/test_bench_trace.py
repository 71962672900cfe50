"""The trace reduction: interval arithmetic by hand, and the whole
reduction on a small trace recorded on a TPU v5e (``record_trace.py``):
the three Pallas kernels, each in a host span, with host pauses between."""
import re

import pytest
from bench_smoke import DATA
from chipbench import spec as sp
from chipbench import trace as tr

TRACE = DATA / "small_trace.xplane.pb"


def test_union_clip_subtract():
    u = tr.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [(0, 3), (5, 9)]
    assert tr.length(u) == 7
    assert tr.clip(u, (2, 6)) == [(2, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [
        (0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 3), (5, 9)], [(2, 6)]) == [(0, 2), (6, 9)]
    assert tr.subtract([(0, 3)], []) == [(0, 3)]


def _synthetic():
    ops = [tr.Op(0, 40, "fusion.1", "%fusion.1 = f32[8] fusion()"),
           tr.Op(30, 60, "collective-permute-done.2",
                 "%collective-permute-done.2 = f32[8] collective-permute()"),
           tr.Op(70, 80, "closed_call.3", "%closed_call.3 = f32[8] "
                 'custom-call(), custom_call_target="tpu_custom_call"')]
    host = [(0, 100, tr.WINDOW_SPAN), (60, 70, "bench.dispatch"),
            (80, 100, "bench.readback")]
    return tr.Trace((0.0, 100.0), {"/device:TPU:0": ops}, host)


def test_reduction_by_hand():
    t = _synthetic()
    assert tr.busy_seconds(t) == {"/device:TPU:0": 70e-9}
    assert tr.pallas_kernel(t, r"= f32\[8\]") == {"/device:TPU:0": (1, 10e-9)}
    assert tr.pallas_kernel(t, r"= bf16") == {"/device:TPU:0": (0, 0.0)}
    # 30..60 is collective; 30..40 is covered by the fusion
    assert tr.exposed_collective_seconds(t) == {"/device:TPU:0": 20e-9}
    assert tr.idle_gaps(t) == [["bench.readback", 20e-9],
                               ["bench.dispatch", 10e-9]]
    assert tr.top_ops(t)[0] == ["fusion", 40e-9]
    assert ["pallas:closed_call", 10e-9] in tr.top_ops(t)


@pytest.fixture(scope="module")
def chip_trace():
    return tr.load(str(TRACE))


def test_chip_trace_busy_within_window(chip_trace):
    assert chip_trace.devices and chip_trace.window_s > 0
    for busy in tr.busy_seconds(chip_trace).values():
        assert 0 < busy < chip_trace.window_s


# flash attention: one bf16 result from exactly q, K and V in bf16
FLASH = (r"= bf16\[[\d,]+\]\{[^}]*\} custom-call\("
         + r"bf16\[[\d,]+\]\{[^}]*\} %[\w.-]+, " * 2
         + r"bf16\[[\d,]+\]\{[^}]*\} %[\w.-]+\)")
SIGNATURES = {"bench.ssd": lambda: sp.metric_reader("ssd_roofline").SIGNATURE,
              "bench.decode": lambda: sp.metric_reader(
                  "decode_attention_roofline").SIGNATURE,
              "bench.flash": lambda: FLASH}


@pytest.mark.parametrize("span", sorted(SIGNATURES))
def test_chip_trace_finds_each_kernel_once(chip_trace, span):
    """Each kernel's shape signature picks out its one call and no other
    Pallas call."""
    (n, seconds), = tr.pallas_kernel(chip_trace,
                                     SIGNATURES[span]()).values()
    assert n == 1 and 0 < seconds < chip_trace.window_s


def test_chip_trace_device_ops_follow_their_host_spans(chip_trace):
    """After aligning the clocks no device op starts before the host span
    that launched it, and each kernel runs inside its own span, to the
    0.2 ms by which the two clocks drift apart over the trace."""
    dev, = chip_trace.devices.values()
    spans = {n: (s, e) for s, e, n in chip_trace.host}
    assert min(o.start for o in dev) >= spans["bench.flash"][0]
    for name, sig in SIGNATURES.items():
        op, = [o for o in dev if tr.PALLAS in o.text
               and re.search(sig(), o.text)]
        assert spans[name][0] - 2e5 <= op.start <= spans[name][1]


def test_chip_trace_names_idle_gaps_by_host_span(chip_trace):
    gaps = tr.idle_gaps(chip_trace)
    assert gaps and all(s > 0 for _, s in gaps)
    assert "bench.pause" in {name for name, _ in gaps}
    ops = tr.top_ops(chip_trace)
    assert ops and all(s > 0 for _, s in ops)
    assert tr.exposed_collective_seconds(chip_trace) == {
        d: 0.0 for d in chip_trace.devices}
