"""Reduces a profiler trace to busy time, idle gaps and top device operations.

The device is busy while one of its programs runs: the union of the events
of each device plane's ``XLA Modules`` line (one per program execution).  The
``XLA Ops`` line, whose events nest (a loop's op spans its body's ops), gives
the top operations by the time of its innermost events; on a long trace the
profiler drops some of those, so they rank operations and do not measure
busy time.  The benchmark's own host spans are the ``bench.*`` events
(written with ``jax.profiler.TraceAnnotation``) on the same clock; the traced
window is the ``bench.trace_window`` span.
"""
from __future__ import annotations

from bisect import bisect_right
from dataclasses import dataclass, field
from pathlib import Path

WINDOW_SPAN = "bench.trace_window"


def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a, b) -> list[tuple[float, float]]:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


@dataclass
class Trace:
    """Times in seconds on the trace's clock."""
    window: tuple[float, float]
    busy: list                         # per device: merged busy intervals in the window
    ops: dict = field(default_factory=dict)         # op name -> seconds in the window
    host_spans: dict = field(default_factory=dict)  # span name -> [(start, end)]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        return sum(length(b) for b in self.busy) / max(len(self.busy), 1)

    def busy_within(self, intervals) -> float:
        """Busy seconds inside the union of ``intervals``, averaged over devices."""
        u = merge(clip(intervals, *self.window))
        return sum(length(intersect(b, u)) for b in self.busy) / max(len(self.busy), 1)

    def top_ops(self, n: int = 10) -> list:
        return [[k, v] for k, v in sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list:
        """The longest idle gaps on the first device, each named by the host
        span that overlaps it most, where that covers at least half of it
        (``idle`` where none does: a request whose reply leaves just after
        the device finished does not make the pause before the next one a
        stall)."""
        busy = self.busy[0] if self.busy else []
        lo, hi = self.window
        gaps, t = [], lo
        for s, e in busy + [(hi, hi)]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        gaps.sort(key=lambda g: g[0] - g[1])
        spans = {k: merge(v) for k, v in self.host_spans.items() if k != WINDOW_SPAN}
        out = []
        for s, e in gaps[:n]:
            best, name = 0.5 * (e - s), "idle"
            for span, ivs in spans.items():
                ov = length(intersect(ivs, [(s, e)]))
                if ov >= best:
                    best, name = ov, span
            out.append([name, e - s])
        return out


def leaves(ops) -> list:
    """The innermost of properly nested (name, start, end) events."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for o, nxt in zip(ops, ops[1:] + [None]) if nxt is None or nxt[1] >= o[2]]


def from_events(devices: list, host_events: list) -> Trace:
    """``devices``: per device {"modules": [...], "ops": [...]}, each event
    (name, start_s, end_s); ``host_events``: (name, start_s, end_s) of the
    benchmark's spans."""
    spans: dict = {}
    for name, s, e in host_events:
        spans.setdefault(name, []).append((s, e))
    if WINDOW_SPAN not in spans:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    lo, hi = spans[WINDOW_SPAN][0]
    busy, ops = [], {}
    for dev in devices:
        runs = dev.get("modules") or dev.get("ops", [])
        busy.append(merge(clip([(s, e) for _, s, e in runs], lo, hi)))
        for name, s, e in leaves(dev.get("ops", [])):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                ops[name] = ops.get(name, 0.0) + d / len(devices)
    return Trace(window=(lo, hi), busy=busy, ops=ops, host_spans=spans)


def start(trace_dir: Path) -> None:
    """Start the profiler with Python's own function tracing off: the host
    spans the reduction needs are the benchmark's ``TraceAnnotation``s."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def _events(line) -> list:
    return [(ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
            for ev in line.events]


def _device(plane) -> dict:
    """A device plane's program runs and operations; an operation is named
    ``<program>/<op>`` after the run that contains it, its HLO text cut to
    the op's own name."""
    lines = {line.name: line for line in plane.lines}
    mods = sorted((s, e, n.split("(")[0]) for n, s, e in
                  (_events(lines["XLA Modules"]) if "XLA Modules" in lines else []))
    starts = [m[0] for m in mods]
    ops = []
    for name, s, e in _events(lines["XLA Ops"]) if "XLA Ops" in lines else []:
        i = bisect_right(starts, s) - 1
        mod = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
        ops.append((f"{mod}/{name.split(' = ')[0].lstrip('%')}", s, e))
    return {"modules": [(n, s, e) for s, e, n in mods], "ops": ops}


def load(trace_dir: Path) -> Trace:
    """Read the ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            devices.append(_device(plane))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append((ev.name, ev.start_ns * 1e-9,
                                     (ev.start_ns + ev.duration_ns) * 1e-9))
    return from_events(devices, host)
