"""Each per-layer metric's reader is exact on synthetic records."""
import json
from types import SimpleNamespace

import pytest

from harness import trace
from harness.spec import BENCH, load_module

PEAK = {"bf16_flops": 200e12}


def reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py").read


def rec(tr=None, **counters):
    return SimpleNamespace(trace=tr, counters=counters, peak=PEAK)


def test_every_listed_metric_has_a_reader():
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        assert callable(reader(m["name"]))


def test_device_idle_share_serve():
    t = trace.from_events([{"ops": [("x", 1.0, 2.0), ("x", 6.0, 7.0)]}],
                          [(trace.WINDOW_SPAN, 0.0, 10.0), ("bench.request", 0.5, 2.5),
                           ("bench.request", 2.0, 3.0), ("bench.request", 6.0, 8.0)])
    # in flight 0.5..3.0 and 6.0..8.0 = 4.5 s, busy inside 2.0 s
    assert reader("device_idle_share.serve")(rec(t)) == pytest.approx(100 * 2.5 / 4.5)
    empty = trace.from_events([{"ops": [("x", 1.0, 2.0)]}], [(trace.WINDOW_SPAN, 0.0, 10.0)])
    assert reader("device_idle_share.serve")(rec(empty)) is None


def test_serve_mfu_and_queue_wait():
    t = trace.from_events([{"ops": [("x", 0.0, 2.0)]}], [(trace.WINDOW_SPAN, 0.0, 4.0)])
    r = rec(t, serve_required_flops=1e14, queue_wait_count=400, queue_wait_sum_s=0.2)
    assert reader("serve_mfu")(r) == pytest.approx(25.0)
    assert reader("flight_queue_wait_ms")(r) == pytest.approx(0.5)
    assert reader("serve_mfu")(rec(t)) is None
    assert reader("flight_queue_wait_ms")(rec(queue_wait_count=0)) is None
