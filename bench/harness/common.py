"""What every driver shares: set-up clock, compile events, statistics, output."""
from __future__ import annotations

import json
import math
import sys
import threading
import time
from contextlib import contextmanager

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class SetupClock:
    """Wall time of each named set-up phase, from the process's start."""

    def __init__(self, t_start: float):
        self.t_start = t_start
        self.parts: dict[str, float] = {}

    @contextmanager
    def phase(self, name: str):
        t = time.perf_counter()
        try:
            yield
        finally:
            self.parts[name] = self.parts.get(name, 0.0) + time.perf_counter() - t

    def total(self) -> float:
        return time.perf_counter() - self.t_start


class CompileEvents:
    """Counts and sums JAX's tracing, lowering, compile and cache events."""

    KINDS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
        "/jax/core/compile/backend_compile_duration": "compile",
        "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read",
    }

    def __init__(self):
        import jax

        self.count = {k: 0 for k in self.KINDS.values()}
        self.seconds = {k: 0.0 for k in self.KINDS.values()}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        kind = self.KINDS.get(event)
        if kind is not None:
            self.count[kind] += 1
            self.seconds[kind] += duration

    def snapshot(self) -> dict:
        return dict(self.count)

    def since(self, snap: dict) -> dict:
        return {k: self.count[k] - snap.get(k, 0) for k in self.count}


def quantile(values, q: float) -> float:
    """Nearest-rank quantile; a missing value (None) counts as infinitely late."""
    xs = sorted(math.inf if v is None else v for v in values)
    if not xs:
        return math.nan
    return xs[min(len(xs) - 1, max(0, math.ceil(q * len(xs)) - 1))]


def device_info(chips: int) -> tuple[dict, list]:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}, devs[:chips]


def memory_peak(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def report(checks: dict, result: dict) -> None:
    """Print each compared number beside its limit as the last lines on
    stderr, then the result line, with ``checks`` as its last key."""
    for name, c in checks.items():
        log(f"[check] {name} {c['value']!r} limit {c['limit']!r}")
    result = {**result, "checks": checks}
    print(json.dumps(result, default=_num), flush=True)


def _num(x):
    if isinstance(x, (np.floating, np.integer)):
        return x.item()
    raise TypeError(type(x))


class Heartbeat:
    """A thread that sleeps ``tick`` seconds at a time and keeps each wake-up
    more than ``over`` seconds late: stretches in which none of this
    process's threads could run it (the interpreter lock held, or the
    process not scheduled)."""

    def __init__(self, over: float = 0.1, tick: float = 0.01):
        self.over, self.tick = over, tick
        self.late: list[tuple[float, float]] = []  # (perf_counter before the sleep, seconds late)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.is_set():
            t = time.perf_counter()
            time.sleep(self.tick)
            late = time.perf_counter() - t - self.tick
            if late > self.over:
                self.late.append((t, late))

    def stop(self) -> list[tuple[float, float]]:
        self._stop.set()
        self._thread.join()
        return self.late

