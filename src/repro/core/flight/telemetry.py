"""Telemetry plane: distributed tracing + latency histograms, exported as Arrow.

The paper's headline claim — >80% of data-access time lost to ser/de,
recovered by Flight — is an *attribution* claim, and attribution needs
per-stage accounting: where did one DoGet spend its time (accept queue,
worker handoff, encode, sendmsg), and which hop of a client → head → shard
fan-out was the slow one?  This module supplies the three primitives and the
export path; the wiring lives in middleware.py / server.py / eventloop.py /
cluster.py.

**Distributed tracing.**  A ``TraceContext`` (trace id, span id, parent span)
rides ``CallOptions.headers`` (client → server) and endpoint
``app_metadata["trace"]`` (planner → scheduler → shard), so one trace
stitches every hop of a distributed read, a 2PC commit, or a chained
exchange pipeline.  Tracing is **sampled by the caller** by default:
servers only record spans for requests that arrive carrying trace headers —
untraced traffic pays one dict lookup per RPC and nothing else.  An operator
can switch a server to **server-rooted** sampling at run time
(``server-trace`` with ``{"sample": "all"}``): every RPC then opens a root
span of its own.  Each recorded ``Span`` carries per-stage timings
(queue-wait, handler, encode, flush, backpressure stalls) filled in by the
server and event loop via the thread-local ``add_stage`` hook, and its start
on two clocks: ``start_s`` (wall, for stitching hosts) and ``mono_s``
(``time.perf_counter``, for placing child intervals and lining spans up
with other monotonic timelines of the same machine).

**Latency histograms.**  ``LogHistogram`` is a fixed-size log2-bucket
histogram (one integer increment per observation, no allocation, no lock —
the count bumps are GIL-atomic and deliberately approximate, like the event
loop's diagnostics counters).  Bucket ``i`` holds observations whose
microsecond value has bit-length ``i``, i.e. upper bound ``2**i µs`` — 40
buckets span sub-µs to ~9 minutes.  Percentiles are read as the upper bound
of the bucket where the cumulative count crosses the rank: an upper-bound
estimate with ≤2x resolution error, which is what p99 dashboards need.

**Arrow-native export.**  ``spans_to_batch`` / ``metrics_to_batch`` render
snapshots as ``RecordBatch``es; the ``server-trace`` / ``server-metrics``
actions (``telemetry_action``) return them as one-batch Arrow IPC streams in
the action body, and the cluster head's ``cluster-trace`` /
``cluster-metrics`` scrape fans out to every shard and merges one
epoch-stamped cluster-wide batch.  The telemetry plane's wire format *is*
the data plane's wire format.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field

from ..recordbatch import RecordBatch
from ..ipc import read_stream_with_schema, write_stream
from .errors import FlightInvalidArgument

# Trace headers (CallOptions.headers / endpoint app_metadata["trace"] keys).
HDR_TRACE = "x-trace-id"
HDR_SPAN = "x-span-id"
HDR_PARENT = "x-parent-span"

# bounded per-server span buffer (drop-oldest): a scoring request leaves 7
# spans, so this holds ~2300 requests — a 45-s window at 13 req/s 3x over
MAX_SPANS = 16384
SAMPLING = ("caller", "all")
MAX_BUCKETS = 40      # log2 µs buckets: 2**39 µs ≈ 9.1 min ceiling


def _new_id() -> str:
    return os.urandom(8).hex()


# --------------------------------------------------------------------------
# trace context + spans
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class TraceContext:
    """One hop's identity within a trace: who am I, who called me."""

    trace_id: str
    span_id: str
    parent_id: str | None = None

    @classmethod
    def new(cls) -> "TraceContext":
        return cls(trace_id=_new_id(), span_id=_new_id())

    def child(self) -> "TraceContext":
        return TraceContext(self.trace_id, _new_id(), self.span_id)

    def to_headers(self) -> dict:
        h = {HDR_TRACE: self.trace_id, HDR_SPAN: self.span_id}
        if self.parent_id:
            h[HDR_PARENT] = self.parent_id
        return h

    @classmethod
    def from_headers(cls, headers: dict | None) -> "TraceContext | None":
        if not headers:
            return None
        tid = headers.get(HDR_TRACE)
        sid = headers.get(HDR_SPAN)
        if not tid or not sid:
            return None
        return cls(tid, sid, headers.get(HDR_PARENT) or None)


@dataclass
class Span:
    """One timed operation within a trace, with per-stage breakdown."""

    trace_id: str
    span_id: str
    parent_id: str | None
    name: str
    service: str = "?"
    shard: int = -1
    start_s: float = 0.0      # wall clock (time.time), comparable across hosts
    duration_s: float = 0.0
    status: str = "ok"        # "ok" or the FlightError wire code
    stages: dict = field(default_factory=dict)  # stage name -> seconds
    mono_s: float = 0.0       # start on time.perf_counter, this host's monotonic clock

    def context(self) -> TraceContext:
        return TraceContext(self.trace_id, self.span_id, self.parent_id)


class SpanRecorder:
    """Bounded, thread-safe span sink (drop-oldest ring).

    ``recorded`` counts every span ever recorded, ``dropped`` those pushed
    out of a full ring before any snapshot drained them."""

    def __init__(self, maxlen: int = MAX_SPANS):
        self._lock = threading.Lock()
        self._spans: deque[Span] = deque(maxlen=maxlen)
        self.recorded = 0
        self.dropped = 0

    def record(self, span: Span) -> None:
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
            self._spans.append(span)
            self.recorded += 1

    def snapshot(self, clear: bool = False) -> list[Span]:
        with self._lock:
            out = list(self._spans)
            if clear:
                self._spans.clear()
            return out

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)


# --------------------------------------------------------------------------
# log2 histograms
# --------------------------------------------------------------------------


class LogHistogram:
    """Fixed log2-bucket histogram: one int increment per observe, no lock.

    ``scale`` maps observed values to the bucketed integer domain —
    ``1e6`` (default) buckets seconds by microsecond bit-length; ``1``
    buckets raw counts (queue depths).  Bucket ``i``'s upper bound is
    ``2**i / scale``."""

    __slots__ = ("counts", "count", "total", "scale")

    def __init__(self, scale: float = 1e6):
        self.counts = [0] * MAX_BUCKETS
        self.count = 0
        self.total = 0.0
        self.scale = scale

    def observe(self, value: float) -> None:
        # GIL-atomic-ish bumps, same contract as the event loop's
        # "approximate: bumped without dedicated locks" diagnostics
        idx = int(value * self.scale).bit_length()
        if idx >= MAX_BUCKETS:
            idx = MAX_BUCKETS - 1
        self.counts[idx] += 1
        self.count += 1
        self.total += value

    def bucket_upper(self, idx: int) -> float:
        return (1 << idx) / self.scale

    def percentile(self, q: float) -> float:
        """Upper-bound estimate of the q-quantile (0 < q <= 1)."""
        n = self.count
        if n == 0:
            return 0.0
        rank = q * n
        cum = 0
        for i, c in enumerate(self.counts):
            cum += c
            if cum >= rank:
                return self.bucket_upper(i)
        return self.bucket_upper(MAX_BUCKETS - 1)

    def merge(self, other: "LogHistogram") -> None:
        for i, c in enumerate(other.counts):
            self.counts[i] += c
        self.count += other.count
        self.total += other.total

    def snapshot(self) -> dict:
        return {
            "count": self.count,
            "sum": round(self.total, 6),
            "p50": self.percentile(0.50),
            "p95": self.percentile(0.95),
            "p99": self.percentile(0.99),
            "buckets": {i: c for i, c in enumerate(self.counts) if c},
        }


# --------------------------------------------------------------------------
# thread-local active span (the stage-timing hook)
# --------------------------------------------------------------------------

_tls = threading.local()


def current_span() -> Span | None:
    return getattr(_tls, "span", None)


def current_context() -> TraceContext | None:
    span = getattr(_tls, "span", None)
    return span.context() if span is not None else None


def propagation_headers() -> dict | None:
    """Headers a downstream hop should carry to parent under the active
    span; ``None`` when no trace is active (the common case)."""
    span = getattr(_tls, "span", None)
    if span is None:
        return None
    return {HDR_TRACE: span.trace_id, HDR_SPAN: span.span_id}


def add_stage(name: str, seconds: float) -> None:
    """Attribute ``seconds`` to a named stage of the active span.

    No-op (one thread-local read) when the request is untraced, so hot
    paths may call it unconditionally."""
    span = getattr(_tls, "span", None)
    if span is not None:
        span.stages[name] = span.stages.get(name, 0.0) + seconds


def _push_span(span: Span) -> Span | None:
    prev = getattr(_tls, "span", None)
    _tls.span = span
    return prev


def _pop_span(prev: Span | None) -> None:
    _tls.span = prev


# --------------------------------------------------------------------------
# per-server telemetry bundle
# --------------------------------------------------------------------------


class ServerTelemetry:
    """What one server owns: mode, sampling, identity, and the span sink.

    ``mode`` gates cost: ``"off"`` (no histograms, no spans), ``"metrics"``
    (histograms only), ``"full"`` (histograms + sampled spans).  Under
    ``"full"``, ``sample`` picks which RPCs are traced: ``"caller"`` (the
    default) those that arrive with trace headers, ``"all"`` every RPC, the
    untraced ones under a root span of their own; ``set_sample`` switches it
    at run time."""

    def __init__(self, mode: str = "full", service: str = "?",
                 shard: int | None = None):
        if mode not in ("off", "metrics", "full"):
            raise ValueError(f"telemetry mode {mode!r} (off|metrics|full)")
        self.mode = mode
        self.sample = "caller"
        self.service = service
        self.shard = -1 if shard is None else shard
        self.spans = SpanRecorder()

    def set_sample(self, sample: str) -> None:
        if sample not in SAMPLING:
            raise ValueError(f"trace sampling {sample!r} (caller|all)")
        self.sample = sample

    @property
    def metrics_enabled(self) -> bool:
        return self.mode != "off"

    @property
    def trace_enabled(self) -> bool:
        return self.mode == "full"

    def _new_span(self, name: str, trace_id: str, parent_id: str | None,
                  mono_s: float | None = None) -> Span:
        return Span(
            trace_id=trace_id, span_id=_new_id(), parent_id=parent_id,
            name=name, service=self.service, shard=self.shard,
            start_s=time.time(),
            mono_s=time.perf_counter() if mono_s is None else mono_s)

    def begin_span(self, name: str, parent: TraceContext | None,
                   mono_s: float | None = None) -> tuple[Span, Span | None]:
        """Open a server span as a child of the caller's context (a root
        span of a fresh trace when ``parent`` is None) and make it the
        thread's active span; returns ``(span, previous)`` for the matching
        ``end_span``.  ``mono_s``: its start, when it began before this
        call (default: now)."""
        if parent is None:
            span = self._new_span(name, _new_id(), None, mono_s)
        else:
            span = self._new_span(name, parent.trace_id, parent.span_id, mono_s)
        return span, _push_span(span)

    def end_span(self, span: Span, prev: Span | None, duration_s: float,
                 error: Exception | None = None) -> None:
        _pop_span(prev)
        self._finish(span, duration_s, error)
        span.stages.setdefault("handler", duration_s)
        self.spans.record(span)

    def _finish(self, span: Span, duration_s: float,
                error: Exception | None) -> None:
        span.duration_s = duration_s
        if error is not None:
            span.status = getattr(error, "code", None) or type(error).__name__

    def interval(self, name: str) -> Span | None:
        """Open a child of the thread's active span without making it the
        active span, for an interval that ends in another frame than it
        began (``close`` records it); ``None`` when untraced."""
        parent = getattr(_tls, "span", None)
        if parent is None or not self.trace_enabled:
            return None
        return self._new_span(name, parent.trace_id, parent.span_id)

    def close(self, span: Span, error: Exception | None = None) -> None:
        """Record an ``interval`` span, ending now."""
        self._finish(span, time.perf_counter() - span.mono_s, error)
        self.spans.record(span)

    def span(self, name: str, parent: TraceContext | None = None):
        """Record an explicit sub-span (e.g. a 2PC sub-txn run in-proc,
        bypassing middleware).  Parent defaults to the thread's active
        span; with no parent and no active trace this is a no-op that
        allocates nothing."""
        if parent is None and (getattr(_tls, "span", None) is None
                               or not self.trace_enabled):
            return _NO_SPAN
        return self._span(name, parent)

    @contextmanager
    def _span(self, name: str, parent: TraceContext | None):
        if not self.trace_enabled:
            yield None
            return
        span, prev = self.begin_span(name, parent or current_context())
        try:
            yield span
        except Exception as e:
            self.end_span(span, prev, time.perf_counter() - span.mono_s, e)
            raise
        else:
            self.end_span(span, prev, time.perf_counter() - span.mono_s)


_NO_SPAN = nullcontext()  # stateless: one instance serves every untraced span()


class Tracer:
    """Client-side trace root: opens the span every server hop stitches to.

    >>> tracer = Tracer(service="client")
    >>> with tracer.trace("read") as ctx:
    ...     opts = CallOptions(headers=ctx.to_headers())   # doctest: +SKIP
    """

    def __init__(self, service: str = "client"):
        self.service = service
        self.spans = SpanRecorder()

    @contextmanager
    def trace(self, name: str):
        ctx = TraceContext.new()
        span = Span(trace_id=ctx.trace_id, span_id=ctx.span_id,
                    parent_id=None, name=name, service=self.service,
                    start_s=time.time(), mono_s=time.perf_counter())
        prev = _push_span(span)
        try:
            yield ctx
        except Exception as e:
            span.status = getattr(e, "code", None) or type(e).__name__
            raise
        finally:
            span.duration_s = time.perf_counter() - span.mono_s
            _pop_span(prev)
            self.spans.record(span)


# --------------------------------------------------------------------------
# Arrow export
# --------------------------------------------------------------------------


def spans_to_batch(spans: list[Span]) -> RecordBatch:
    """Render spans as one RecordBatch (variable stages ride as JSON)."""
    return RecordBatch.from_pydict({
        "trace_id": [s.trace_id for s in spans],
        "span_id": [s.span_id for s in spans],
        "parent_id": [s.parent_id or "" for s in spans],
        "name": [s.name for s in spans],
        "service": [s.service for s in spans],
        "shard": [int(s.shard) for s in spans],
        "start_s": [float(s.start_s) for s in spans],
        "duration_s": [float(s.duration_s) for s in spans],
        "status": [s.status for s in spans],
        "stages": [json.dumps({k: round(v, 9) for k, v in s.stages.items()})
                   for s in spans],
        "mono_s": [float(s.mono_s) for s in spans],
    } if spans else _EMPTY_SPANS)


_EMPTY_SPANS = {
    "trace_id": [], "span_id": [], "parent_id": [], "name": [],
    "service": [], "shard": [], "start_s": [], "duration_s": [],
    "status": [], "stages": [], "mono_s": [],
}


def batch_to_spans(batch: RecordBatch) -> list[dict]:
    """Decode a span batch into row dicts (stages JSON rehydrated)."""
    cols = batch.to_pydict()
    rows = []
    for i in range(batch.num_rows):
        row = {k: v[i] for k, v in cols.items()}
        row["stages"] = json.loads(row.get("stages") or "{}")
        rows.append(row)
    return rows


def metrics_rows(scope: str, hists: dict) -> list[dict]:
    """Flatten ``{name: LogHistogram | snapshot-dict}`` into export rows."""
    rows = []
    for name, h in sorted(hists.items()):
        snap = h.snapshot() if isinstance(h, LogHistogram) else h
        rows.append({
            "scope": scope, "name": name,
            "count": int(snap.get("count", 0)),
            "sum_s": float(snap.get("sum", 0.0)),
            "p50_s": float(snap.get("p50", 0.0)),
            "p95_s": float(snap.get("p95", 0.0)),
            "p99_s": float(snap.get("p99", 0.0)),
            "buckets": json.dumps(snap.get("buckets", {})),
        })
    return rows


def metrics_to_batch(rows: list[dict], shard: int = -1,
                     epoch: int = -1) -> RecordBatch:
    return RecordBatch.from_pydict({
        "scope": [r["scope"] for r in rows],
        "name": [r["name"] for r in rows],
        "count": [int(r["count"]) for r in rows],
        "sum_s": [float(r["sum_s"]) for r in rows],
        "p50_s": [float(r["p50_s"]) for r in rows],
        "p95_s": [float(r["p95_s"]) for r in rows],
        "p99_s": [float(r["p99_s"]) for r in rows],
        "buckets": [r["buckets"] for r in rows],
        "shard": [int(r.get("shard", shard)) for r in rows],
        "epoch": [int(r.get("epoch", epoch)) for r in rows],
    } if rows else {k: [] for k in (
        "scope", "name", "count", "sum_s", "p50_s", "p95_s", "p99_s",
        "buckets", "shard", "epoch")})


def batch_to_rows(batch: RecordBatch) -> list[dict]:
    cols = batch.to_pydict()
    return [{k: v[i] for k, v in cols.items()} for i in range(batch.num_rows)]


def encode_telemetry_batch(batch: RecordBatch) -> bytes:
    """One-batch Arrow IPC stream — the ``server-trace``/``server-metrics``
    action body format (decode with ``decode_telemetry_batch``)."""
    return write_stream([batch], schema=batch.schema)


def decode_telemetry_batch(body: bytes) -> RecordBatch:
    schema, batches = read_stream_with_schema(bytes(body))
    if not batches:
        return RecordBatch.from_pydict({f.name: [] for f in schema.fields}, schema)
    return batches[0]


def merge_telemetry_batches(batches: list[tuple[int, RecordBatch]],
                            epoch: int) -> RecordBatch:
    """Head-side scrape merge: concatenate per-shard batches into one
    cluster-wide batch, stamping ``shard`` and ``epoch`` per row."""
    merged: dict[str, list] = {}
    template: RecordBatch | None = None
    for shard, b in batches:
        if template is None:
            template = b
            merged = {k: [] for k in b.to_pydict()}
        cols = b.to_pydict()
        n = b.num_rows
        for k in merged:
            vals = cols.get(k, [None] * n)
            if k == "shard":
                vals = [shard if v in (None, -1) else v for v in vals]
            elif k == "epoch":
                vals = [epoch] * n
            merged[k].extend(vals)
    if template is None:
        return metrics_to_batch([])
    return RecordBatch.from_pydict(merged)


# --------------------------------------------------------------------------
# the server-trace / server-metrics actions (shared by server + cluster head)
# --------------------------------------------------------------------------


def server_metrics_rows(server) -> list[dict]:
    """Every histogram scope one server exposes, flattened to export rows."""
    rows: list[dict] = []
    metrics = getattr(server, "metrics", None)
    if metrics is not None:
        rows += metrics_rows("verb", getattr(metrics, "latency", {}))
        rows += metrics_rows(
            "exchange",
            {k: v["hist"] for k, v in getattr(metrics, "exchanges", {}).items()
             if isinstance(v, dict) and isinstance(v.get("hist"), LogHistogram)})
        for verb, codes in getattr(metrics, "error_codes", {}).items():
            for code, n in sorted(codes.items()):
                rows.append({
                    "scope": "errors", "name": f"{verb}:{code}", "count": n,
                    "sum_s": 0.0, "p50_s": 0.0, "p95_s": 0.0, "p99_s": 0.0,
                    "buckets": "{}"})
    listener = getattr(server, "_listener", None)
    if listener is not None:
        rows += metrics_rows("io", getattr(listener, "histograms", lambda: {})())
    # monotone serve counters (no histogram): scrape deltas give rates;
    # a server adds its own through ``serve_counters()``
    counters = {"rows_served": int(getattr(server, "rows_served", 0))}
    counters.update(getattr(server, "serve_counters", dict)())
    for name, n in counters.items():
        rows.append({
            "scope": "serve", "name": name, "count": int(n),
            "sum_s": 0.0, "p50_s": 0.0, "p95_s": 0.0, "p99_s": 0.0,
            "buckets": "{}"})
    tel = getattr(server, "telemetry", None)
    shard = tel.shard if tel is not None else -1
    for r in rows:
        r.setdefault("shard", shard)
    return rows


def telemetry_action(server, action) -> "list | None":
    """Serve ``server-trace`` / ``server-metrics`` for one server; returns
    ``None`` for any other action type (caller falls through).

    ``server-trace`` takes an optional JSON body: ``"sample"`` (``"caller"``
    or ``"all"``) switches the server's sampling before the snapshot,
    ``"clear"`` drains the span ring after it.  Its reply is the span batch,
    then a JSON result ``{"sample", "recorded", "dropped", "returned"}``:
    the ring's totals since the server started (a window's drops are the
    difference of two scrapes)."""
    from .protocol import ActionResult  # lazy: protocol imports stay light

    if action.type == "server-metrics":
        batch = metrics_to_batch(server_metrics_rows(server))
        return [ActionResult(encode_telemetry_batch(batch))]
    if action.type == "server-trace":
        opts = json.loads(action.body) if action.body else {}
        tel = getattr(server, "telemetry", None)
        if tel is None:
            return [ActionResult(encode_telemetry_batch(spans_to_batch([])))]
        if "sample" in opts:
            try:
                tel.set_sample(opts["sample"])
            except ValueError as e:
                raise FlightInvalidArgument(str(e)) from None
        spans = tel.spans.snapshot(clear=bool(opts.get("clear")))
        ring = {"sample": tel.sample, "recorded": tel.spans.recorded,
                "dropped": tel.spans.dropped, "returned": len(spans)}
        return [ActionResult(encode_telemetry_batch(spans_to_batch(spans))),
                ActionResult(json.dumps(ring).encode())]
    return None
