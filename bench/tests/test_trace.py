"""The trace reduction is exact on small traces with known intervals, and on
a trace recorded on a v5e chip (``data/v5e_probe.xplane.pb``: three steps of
two jitted programs, one of them a 50-iteration scan)."""
from pathlib import Path

import pytest

from harness import trace


def make(device_ops, host):
    devices = [{"ops": ops} for ops in device_ops]
    return trace.from_events(devices, host + [(trace.WINDOW_SPAN, 0.0, 10.0)])


def test_busy_union_idle_and_top_ops():
    ops = [("m/fusion.1", 1.0, 3.0), ("m/fusion.2", 2.0, 4.0),   # overlap: union 1..4
           ("m/dot.3", 6.0, 7.5), ("m/fusion.1", 9.5, 11.0)]     # clipped at the window
    t = make([ops], [("bench.step", 0.5, 5.0), ("bench.feed_wait", 4.0, 6.0)])
    assert t.window_s == 10.0
    assert t.busy[0] == [(1.0, 4.0), (6.0, 7.5), (9.5, 10.0)]
    assert t.busy_s == pytest.approx(5.0)
    # fusion.1 at 1..3 has another op starting inside it: not innermost, not ranked
    assert t.top_ops(2) == [["m/fusion.2", pytest.approx(2.0)], ["m/dot.3", pytest.approx(1.5)]]
    # gaps: 0-1 (step 0.5), 4-6 (feed 2.0 vs step 1.0), 7.5-9.5 (nothing), longest first
    assert t.idle_gaps() == [["bench.feed_wait", pytest.approx(2.0)], ["idle", pytest.approx(2.0)],
                             ["bench.step", pytest.approx(1.0)]]
    # a span over less than half of a gap does not name it
    t = make([ops], [("bench.request", 3.5, 4.5), ("bench.request", 7.0, 8.0)])
    assert t.idle_gaps() == [["idle", pytest.approx(2.0)], ["idle", pytest.approx(2.0)],
                             ["idle", pytest.approx(1.0)]]


def test_busy_within_intervals_and_devices():
    d0 = [("a", 0.0, 2.0), ("b", 5.0, 6.0)]
    d1 = [("a", 1.0, 3.0)]
    t = make([d0, d1], [("bench.request", 1.0, 5.5), ("bench.request", 5.2, 8.0)])
    assert t.busy_s == pytest.approx((3.0 + 2.0) / 2)
    inflight = t.host_spans["bench.request"]
    # in flight 1.0..8.0: device 0 busy 1..2 and 5..6 (2 s), device 1 busy 1..3 (2 s)
    assert t.busy_within(inflight) == pytest.approx(2.0)
    assert t.ops == {"a": pytest.approx(2.0), "b": pytest.approx(0.5)}


def test_busy_is_program_runs_and_top_ops_are_innermost():
    dev = {"modules": [("jit_step", 1.0, 5.0), ("jit_step", 6.0, 9.0)],
           "ops": [("jit_step/while.1", 1.0, 4.0), ("jit_step/dot.2", 1.5, 2.0),
                   ("jit_step/fusion.3", 2.5, 3.5), ("jit_step/fusion.3", 6.0, 7.0)]}
    t = trace.from_events([dev], [(trace.WINDOW_SPAN, 0.0, 10.0)])
    assert t.busy_s == pytest.approx(7.0)
    assert t.top_ops() == [["jit_step/fusion.3", pytest.approx(2.0)],
                           ["jit_step/dot.2", pytest.approx(0.5)]]


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace.from_events([{"ops": [("a", 0, 1)]}], [("bench.step", 0, 1)])


def test_recorded_v5e_trace():
    t = trace.load(Path(__file__).parent / "data")
    ns = 1e-9
    assert t.window == pytest.approx((44922749 * ns, 51165479 * ns))
    # program runs inside the window, the first clipped at its start:
    # (45242384 - 44922749) + 15240 + 619142 + 14991 + 619146 ns
    assert t.busy_s == pytest.approx(1588154 * ns)
    assert len(t.host_spans["bench.step"]) == 3
    top = t.top_ops(1)[0]
    assert top[0] == "jit_g/convolution_tanh_fusion.2" and top[1] > 0.9 * t.busy_s
