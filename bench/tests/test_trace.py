"""The reduction from a profiler trace to numbers: on a small trace
recorded on a v5e (a flash-attention gradient and a matmul, each under a
``bench.step`` span inside ``bench.window``), and on hand-made events."""
from pathlib import Path

import pytest

from bench.harness import trace as t

DATA = Path(__file__).parent / "data" / "probe.xplane.pb"


@pytest.fixture(scope="module")
def probe():
    return t.load(str(DATA))


def test_recorded_trace_planes_and_window(probe):
    assert list(probe.device_ops) == [0]
    assert len(probe.device_ops[0]) == 11
    lo, hi = probe.window
    assert hi - lo == 12_763_108
    assert [n for n, _, _ in probe.host_spans].count("bench.step") == 2


def test_device_clock_is_moved_onto_the_host_clock(probe):
    # the first module ran at 44,767,350 ns on the device's clock, and the
    # host launched it at 46,022,965 ns: the largest such lead
    assert probe.clock_shift_ns == 1_255_615
    first = min(s for _, s, _ in probe.device_ops[0])
    assert first == 44_767_357 + 1_255_615
    for _, s, e in probe.device_ops[0]:
        assert probe.window[0] <= s < e <= probe.window[1]


def test_busy_kernel_and_op_names(probe):
    # eleven operations, none overlapping: busy is their summed duration
    total = sum(e - s for _, s, e in probe.device_ops[0]) * 1e-9
    assert probe.busy_s() == pytest.approx(total)
    # the four flash-attention custom calls: forward, delta, dK/dV, dQ
    assert probe.kernel_s(("flash_attention",)) == pytest.approx(
        (3820 + 497 + 3052 + 2232) * 1e-9)
    assert probe.kinds["jvp_jit_flash_attention__.1"] == "custom-call"
    assert probe.kinds["convolution_tanh_fusion"] == "fusion"
    top = t.top_ops(probe, 3)
    assert top[0] == ["jvp_jit_flash_attention__.1 (custom-call)",
                      pytest.approx(3820e-9)]
    idle = dict(t.idle_by_span(probe))
    assert sum(idle.values()) == pytest.approx(probe.window_s
                                               - probe.busy_s())
    assert set(idle) == {"bench.window", "bench.step"}


def test_split_hlo():
    assert t.split_hlo("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)") == \
        ("fusion.3", "fusion")
    assert t.split_hlo('%k.1 = (f32[2]{0}, f32[2]{0}) custom-call(f32[2] '
                       '%a), custom_call_target="tpu_custom_call"') == \
        ("k.1", "custom-call")


def test_clock_shift_needs_one_launch_per_module():
    assert t.clock_shift([10.0, 50.0], [15.0, 52.0]) == 5.0
    assert t.clock_shift([10.0, 50.0], [15.0]) == 0.0
    assert t.clock_shift([10.0], [5.0]) == 0.0


def _trace(ops, spans):
    tr = t.Trace(device_ops={0: ops}, host_spans=spans)
    tr.kinds = {n: "custom-call" for n, _, _ in ops}
    return tr


def test_union_gaps_and_idle_attribution_by_hand():
    ops = [("a", 10, 20), ("b", 15, 30), ("c", 50, 60), ("d", 95, 120)]
    spans = [(t.WINDOW_SPAN, 0, 100), ("bench.pump", 0, 40),
             ("bench.admit", 5, 35), ("bench.generator_idle", 40, 100)]
    tr = _trace(ops, spans)
    # busy in [0, 100]: [10, 30] + [50, 60] + [95, 100]
    assert t.union_ns(tr.ops_in_window()) == 35
    assert tr.busy_s() == pytest.approx(35e-9)
    assert t.gaps(tr.ops_in_window(), 0, 100) == [(0, 10), (30, 50),
                                                  (60, 95)]
    idle = dict(t.idle_by_span(tr))
    # [0,5] pump, [5,10] admit, [30,35] admit, [35,40] pump,
    # [40,50] + [60,95] generator_idle
    assert idle == {"bench.pump": pytest.approx(10e-9),
                    "bench.admit": pytest.approx(10e-9),
                    "bench.generator_idle": pytest.approx(45e-9)}
    assert tr.kernel_s(("a", "d")) == pytest.approx((10 + 5) * 1e-9)


def test_top_ops_leave_out_loops_that_enclose_their_bodies():
    tr = _trace([("while.1", 0, 100), ("fusion.2", 10, 40),
                 ("k.3", 50, 90)], [(t.WINDOW_SPAN, 0, 100)])
    tr.kinds = {"while.1": "while", "fusion.2": "fusion",
                "k.3": "custom-call"}
    assert t.top_ops(tr) == [["k.3 (custom-call)", pytest.approx(40e-9)],
                             ["fusion.2 (fusion)", pytest.approx(30e-9)]]
    assert tr.busy_s() == pytest.approx(100e-9)
