"""The stall readings printed beside every serve run are exact on made-up
request times, and the heartbeat sees a thread that holds the interpreter."""
import time

import pytest

from harness.common import Heartbeat
from harness.spec import BENCH, load_module

SERVE = load_module(BENCH / "drivers" / "serve_score.py")


def test_silences_are_stretches_with_a_request_out_and_no_reply():
    sent = [0.0, 0.1, 2.0, 5.0]
    done = [0.2, 1.5, 2.1, 5.05]
    # out 0.0..1.5: replies at 0.2 and 1.5, so silent 0.2..1.5; 2.0..2.1 and 5.0..5.05 are short
    got = SERVE.silences(sent, done, [0, 1, 2, 3])
    assert [(round(s, 6), round(d, 6)) for s, d in got] == [(0.2, 1.3)]
    assert SERVE.silences(sent, done, [0, 2, 3]) == []


def test_heartbeat_sees_the_interpreter_held():
    beat = Heartbeat(over=0.1)
    time.sleep(0.05)
    t = time.perf_counter()
    sum(range(40_000_000))  # one C call: holds the interpreter lock throughout
    held = time.perf_counter() - t
    late = beat.stop()
    assert held > 0.15
    assert late and 0.1 < max(d for _, d in late) <= held + 0.05
