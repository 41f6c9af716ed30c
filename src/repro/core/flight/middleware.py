"""Server middleware: a composable interception chain around verb dispatch.

Arrow Flight lets servers install middleware that observes/steers every RPC
(auth, tracing, metrics) without touching handlers; this is our equivalent.
``FlightServerBase`` runs each incoming RPC through a ``MiddlewareStack``:

* ``on_call(ctx)`` runs front-to-back *before* the verb handler; raising a
  ``FlightError`` short-circuits the call (later middleware and the handler
  never run) and the typed error goes back over the wire.
* ``on_complete(ctx, error)`` runs back-to-front *after* the handler (or the
  short-circuit) for every middleware whose ``on_call`` was invoked —
  ``error`` is ``None`` on success.

``CallContext.state`` is a per-call scratch dict middleware can use to pass
data between its two hooks (e.g. a start timestamp) or to later middleware.

The hard-coded ``_check_auth`` of earlier revisions is now just
``AuthTokenMiddleware`` installed by the server when ``auth_token`` is set.
"""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Callable

from .errors import FlightError, FlightUnauthenticated
from .telemetry import LogHistogram, ServerTelemetry, TraceContext


def _exchange_service_label(request: dict) -> str:
    """Which exchange service a DoExchange request names (metrics key).

    Best-effort: a label must never fail the call, so malformed descriptors
    degrade to ``"?"`` (the serve path rejects them with a typed error)."""
    d = request.get("descriptor") or {}
    path = d.get("path")
    if path:
        return "path:" + "/".join(path)
    raw = d.get("command")
    if raw:
        from .protocol import ExchangeCommand, parse_command  # lazy: keeps import light

        try:
            cmd = parse_command(raw.encode("latin1") if isinstance(raw, str) else raw)
        except Exception:
            return "?"
        return cmd.service if isinstance(cmd, ExchangeCommand) else type(cmd).__name__
    return "?"


@dataclass
class CallContext:
    """What middleware sees about one RPC."""

    method: str                      # verb name: "DoGet", "DoPut", ...
    headers: dict = field(default_factory=dict)   # token + CallOptions headers
    request: dict = field(default_factory=dict)   # raw control-frame payload
    state: dict = field(default_factory=dict)     # per-call middleware scratch


class ServerMiddleware:
    """Override one or both hooks; the defaults are no-ops."""

    def on_call(self, ctx: CallContext) -> None:  # raise FlightError to reject
        pass

    def on_complete(self, ctx: CallContext, error: Exception | None) -> None:
        pass


class MiddlewareStack:
    def __init__(self, items: list[ServerMiddleware] | None = None):
        self.items: list[ServerMiddleware] = list(items or [])

    @contextmanager
    def wrap(self, ctx: CallContext):
        """Run the chain around one dispatched verb (see module docstring)."""
        started: list[ServerMiddleware] = []
        error: Exception | None = None
        try:
            for m in self.items:
                started.append(m)
                m.on_call(ctx)
            yield
        except Exception as e:
            error = e
            raise
        finally:
            for m in reversed(started):
                try:
                    m.on_complete(ctx, error)
                except Exception:
                    pass  # completion hooks never mask the real outcome


# --------------------------------------------------------------------------
# stock middleware
# --------------------------------------------------------------------------


class AuthTokenMiddleware(ServerMiddleware):
    """Shared-token auth — the typed replacement for ``_check_auth``."""

    def __init__(self, token: str):
        self.token = token

    def on_call(self, ctx: CallContext) -> None:
        if ctx.headers.get("token") != self.token:
            raise FlightUnauthenticated(
                "bad or missing token", detail={"method": ctx.method}
            )


class MetricsMiddleware(ServerMiddleware):
    """Per-verb call/error/latency accounting (surfaced by ``server-stats``).

    Latency is a ``LogHistogram`` per verb (and per exchange service), so
    ``server-metrics`` exports p50/p95/p99 instead of one scalar sum; the
    legacy ``seconds`` sums stay for back-compat.  Errors count per verb
    *and* per ``FlightError`` wire code (``error_codes``) — a dashboard can
    tell ``not_found`` noise from an ``unavailable`` incident.

    When constructed with a ``ServerTelemetry`` in ``"full"`` mode this is
    also the server-side tracer: a request arriving with trace headers gets
    a child ``Span`` opened in ``on_call`` (installed as the thread-local
    active span so handlers can ``add_stage`` and open child spans) and
    recorded in ``on_complete`` with queue-wait and handler stage timings;
    under sampling ``"all"`` every other request gets a root span.
    Untraced requests pay one header lookup.  Everything here is
    non-blocking and allocation-light on purpose: this middleware lives in the
    ``MiddlewareStack`` module, so it must keep the event loop's inline
    fast-path certificate valid (see ``FlightServerBase._rpc_inline_ok``).

    Locked where it matters: each TCP connection runs on its own handler
    thread, so concurrent RPCs hit the dict read-modify-writes
    simultaneously; histogram bumps are deliberately lock-free."""

    def __init__(self, telemetry: ServerTelemetry | None = None):
        self.telemetry = telemetry
        self.calls: dict[str, int] = {}
        self.errors: dict[str, int] = {}
        self.error_codes: dict[str, dict[str, int]] = {}
        self.seconds: dict[str, float] = {}
        self.latency: dict[str, LogHistogram] = {}  # per-verb log2 buckets
        self.actions: dict[str, int] = {}  # DoAction broken out by type
        # DoExchange broken out by service: call/error/latency per transform
        self.exchanges: dict[str, dict] = {}
        self._lock = threading.Lock()

    def _exchange_entry(self, label: str) -> dict:
        return self.exchanges.setdefault(
            label, {"calls": 0, "errors": 0, "seconds": 0.0,
                    "hist": LogHistogram()})

    def on_call(self, ctx: CallContext) -> None:
        ctx.state["metrics_t0"] = time.perf_counter()
        with self._lock:
            self.calls[ctx.method] = self.calls.get(ctx.method, 0) + 1
            if ctx.method == "DoAction":
                kind = (ctx.request.get("action") or {}).get("type", "?")
                self.actions[kind] = self.actions.get(kind, 0) + 1
            elif ctx.method == "DoExchange":
                label = _exchange_service_label(ctx.request)
                ctx.state["metrics_exchange"] = label
                self._exchange_entry(label)["calls"] += 1
        tel = self.telemetry
        if tel is not None and tel.trace_enabled:
            parent = TraceContext.from_headers(ctx.headers)
            # caller-sampled: only traced requests pay; sampling "all" gives
            # an untraced request a root span of its own
            if parent is not None or tel.sample == "all":
                name = ctx.method
                if name == "DoAction":
                    name = f"DoAction:{(ctx.request.get('action') or {}).get('type', '?')}"
                elif name == "DoExchange":
                    name = f"DoExchange:{ctx.state.get('metrics_exchange', '?')}"
                span, prev = tel.begin_span(name, parent, mono_s=ctx.state["metrics_t0"])
                qw = ctx.state.get("queue_wait_s")
                if qw is not None:
                    span.stages["queue"] = qw
                ctx.state["telemetry_span"] = (span, prev)

    def on_complete(self, ctx: CallContext, error: Exception | None) -> None:
        dt = time.perf_counter() - ctx.state.get("metrics_t0", time.perf_counter())
        tel = self.telemetry
        if tel is None or tel.metrics_enabled:
            hist = self.latency.get(ctx.method)
            if hist is None:  # racy setdefault is fine: worst case one resets
                hist = self.latency[ctx.method] = LogHistogram()
            hist.observe(dt)
        with self._lock:
            self.seconds[ctx.method] = self.seconds.get(ctx.method, 0.0) + dt
            if error is not None:
                self.errors[ctx.method] = self.errors.get(ctx.method, 0) + 1
                code = getattr(error, "code", None) or type(error).__name__
                by_code = self.error_codes.setdefault(ctx.method, {})
                by_code[code] = by_code.get(code, 0) + 1
            label = ctx.state.get("metrics_exchange")
            if label is not None:
                e = self._exchange_entry(label)
                e["seconds"] += dt
                if error is not None:
                    e["errors"] += 1
        if label is not None and (tel is None or tel.metrics_enabled):
            self.exchanges[label]["hist"].observe(dt)
        traced = ctx.state.pop("telemetry_span", None)
        if traced is not None:
            span, prev = traced
            # handler time excludes the pre-dispatch queue wait, which
            # happened before this span's clock started
            span.stages.setdefault("handler", dt)
            tel.end_span(span, prev, dt, error)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "calls": dict(self.calls),
                "errors": dict(self.errors),
                "error_codes": {k: dict(v) for k, v in self.error_codes.items()},
                "seconds": {k: round(v, 6) for k, v in self.seconds.items()},
                "latency": {k: h.snapshot() for k, h in self.latency.items()},
                "actions": dict(self.actions),
                "exchanges": {
                    k: {**{kk: vv for kk, vv in v.items() if kk != "hist"},
                        "seconds": round(v["seconds"], 6),
                        "latency": v["hist"].snapshot()}
                    for k, v in self.exchanges.items()
                },
            }


class LoggingMiddleware(ServerMiddleware):
    """Calls ``log(line)`` per completed RPC; defaults to collecting lines."""

    def __init__(self, log: Callable[[str], None] | None = None):
        self.lines: list[str] = []
        self._log = log if log is not None else self.lines.append

    def on_complete(self, ctx: CallContext, error: Exception | None) -> None:
        status = "ok" if error is None else f"error:{getattr(error, 'code', 'exception')}"
        self._log(f"{ctx.method} {status}")
