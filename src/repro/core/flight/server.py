"""Flight server: RPC dispatch + an in-memory store implementation.

``FlightServerBase`` defines the six verbs (GetFlightInfo, ListFlights,
DoGet, DoPut, DoAction, DoExchange) against abstract handlers; it can be
used in-process (zero-copy object handoff) or served over TCP via
``serve_tcp``.  TCP serving runs on the event-loop core by default
(``io_mode="eventloop"``: one selector dispatch thread + a small worker
pool, eventloop.py — server threads stay O(worker pool), not O(clients));
``io_mode="threads"`` keeps the historical thread-per-connection listener
one release for bisection.  Both modes speak the identical framed wire
format and run the identical ``_dispatch_rpc``.

Every RPC is dispatched through a **middleware stack** (see middleware.py):
auth is just ``AuthTokenMiddleware`` (installed automatically when
``auth_token`` is set), a ``MetricsMiddleware`` counts per-verb calls/errors
/latency (surfaced via ``server-stats``), and servers can prepend their own
interceptors.  Failures raise the typed ``FlightError`` hierarchy
(errors.py) and round-trip to clients as structured control frames.

``InMemoryFlightServer`` is the paper's "simple data producer with an
InMemoryStore" (§4.2.2) — datasets are lists of RecordBatches keyed by
descriptor path.  Tickets carry typed ``Command``s (protocol.py):

* ``RangeReadCommand`` — idempotent (dataset, start, stop) range reads, so
  any batch range can be re-fetched (hedged reads / resume);
* ``QueryCommand`` — executed **natively** via ``query.engine.execute``
  (predicate/projection/limit pushdown), no ``do_get_impl`` monkeypatching.
  Pass-through queries (no predicate, full projection, no limit) serve from
  the encode-once cache like plain range reads; filtered queries encode
  per-request and never poison the cache.

Data-plane fast paths (the wire-speed work):

* **encode-once cache** — datasets are pre-encoded to ``EncodedMessage``s on
  first DoGet and every later DoGet serves from the cache (zero
  ``encode_batch`` calls — asserted via the ``server-stats`` counters).  The
  cache is invalidated on DoPut / ``add_dataset`` / ``drop``, and bypassed
  whenever ``do_get_impl`` is overridden (paced shards, test monkeypatches)
  so behavior-modifying subclasses keep their semantics.
* **frame coalescing** — DoGet streams go out via
  ``FrameConnection.send_data_many`` (many frames per ``sendmsg``) unless
  disabled; ``CallOptions.coalesce`` overrides per call.
* ``wire_codec`` selects the IPC metadata codec (binary default; json kept
  for comparison benchmarks); ``CallOptions.wire_codec`` overrides per call
  (bypassing the cache, which holds server-codec messages).
* **DoPut dedup guard** — recently committed put payloads are content-hashed
  per dataset; an identical re-append within the window (a retried parallel
  put after partial failure) is dropped instead of duplicating rows.

Transactional staged DoPut (the two-phase cluster write protocol):

* a DoPut whose descriptor carries ``StagedPutCommand(dataset, txn_id,
  "stage")`` lands in a **staging store** keyed by txn id — invisible to
  every DoGet/query until committed, and never touching the encode-once
  cache (invalidation happens on *commit*, not stage);
* ``txn-prepare`` / ``txn-commit`` / ``txn-abort`` DoActions drive the
  commit round (commit flips all of a txn's staged batches into the visible
  dataset under one lock acquisition — a concurrent reader sees none or all
  of them; abort discards them).  Commit and abort are idempotent within a
  recent-transactions window, so a retried coordinator round is safe;
* a TTL **GC reaper** (daemon thread, started when the first stage arrives)
  discards stages whose writer went away — an orphaned txn is never
  readable and stops holding memory after ``stage_ttl`` seconds;
* ``server-stats`` surfaces ``staged_bytes`` / ``staged_txns`` /
  ``txn_commits`` / ``txn_aborts`` / ``txn_gc_reaped``.

Streaming DoExchange (the microservice plane — exchange.py / services.py):

* descriptors carrying an ``ExchangeCommand`` route the bidirectional
  stream through the server's ``ExchangeServiceRegistry`` (``services``
  attr; stock echo/filter/project/repartition plus registered callables);
  path descriptors keep the legacy per-batch ``do_exchange_impl`` hook;
* the serve loop (``_run_exchange``) is pipelined: output frames buffer
  and **flush when a read would block** (coalesced sendmsg bursts without
  starving a lockstep peer), and consumption acks ride the output
  direction so the client's bounded in-flight window provides
  backpressure — see docs/wire-format.md ("DoExchange framing");
* mid-stream failures are sent as typed error control frames the client
  rehydrates, then the connection is torn down (frames may be in flight
  in both directions — an exchange error never bleeds into a later RPC).
"""
from __future__ import annotations

import hashlib
import json
import threading
import time
from collections import OrderedDict
from collections.abc import Mapping
from dataclasses import dataclass, field, replace
from itertools import chain
from typing import Iterable, Iterator

from ..ipc import (
    CODEC_BINARY,
    CODEC_JSON,
    DEFAULT_CODEC,
    EncodedMessage,
    decode_message,
    encode_batch,
    encode_eos,
    encode_schema,
)
from ..recordbatch import RecordBatch
from ..schema import Schema
from .errors import (
    FlightError,
    FlightInvalidArgument,
    FlightNotFound,
    FlightUnauthenticated,
)
from .middleware import (
    AuthTokenMiddleware,
    CallContext,
    MetricsMiddleware,
    MiddlewareStack,
    ServerMiddleware,
)
from .protocol import (
    Action,
    ActionResult,
    ExchangeCommand,
    FlightDescriptor,
    FlightEndpoint,
    FlightInfo,
    Location,
    QueryCommand,
    RangeReadCommand,
    StagedPutCommand,
    Ticket,
    parse_command,
)
from .eventloop import EventLoopListener
from .exchange import DEFAULT_WINDOW, ack_interval
from .services import ExchangeService, ExchangeServiceRegistry, drive_exchange
from .storage import StorageProvider, make_provider
from .telemetry import (
    ServerTelemetry,
    add_stage,
    current_span,
    propagation_headers,
    telemetry_action,
)
from .transport import (
    COALESCE_BYTES,
    KIND_CTRL,
    KIND_DATA,
    FrameConnection,
    SocketListener,
)

_PUT_DEDUP_WINDOW = 32   # recent content hashes remembered per dataset
_TXN_FINISH_WINDOW = 64  # recent committed/aborted txn ids (idempotency)

_UNSET = object()  # legacy-kwarg sentinel: distinguishes "not passed" from a value


@dataclass(frozen=True)
class ServerConfig:
    """One bundle for ``InMemoryFlightServer``'s construction knobs.

    Replaces the sprawling per-kwarg signature: build a config once and hand
    it to many servers (cluster shards, benchmark sweeps).  The legacy
    keyword arguments are still accepted for one release and route through
    this dataclass — an explicitly passed kwarg overrides the same field of
    a ``config`` also given.

    ``storage`` selects the dataset backend (storage.py): ``None``/
    ``"memory"``, ``"disk:<root>"``, ``"remote:<uri>"``, or a ready
    ``StorageProvider`` instance.

    ``io_mode`` selects the TCP serving core: ``"eventloop"`` (default —
    one selector dispatch thread + a small worker pool, eventloop.py) or
    ``"threads"`` (the historical thread-per-connection ``SocketListener``,
    retained one release for bisection).  ``io_workers`` sizes the event
    loop's worker pool (0 = auto: half the cores, floor 2, cap 8).
    """

    auth_token: str | None = None
    wire_codec: str = DEFAULT_CODEC
    coalesce: bool = True
    cache_encoded: bool = True
    batches_per_endpoint: int = 0
    endpoints_per_query: int = 4
    dedup_puts: bool = True
    stage_ttl: float = 60.0
    storage: "str | StorageProvider | None" = None
    io_mode: str = "eventloop"
    io_workers: int = 0
    # telemetry plane (telemetry.py): "off" | "metrics" (histograms only) |
    # "full" (histograms + caller-sampled distributed tracing, the default —
    # untraced traffic pays one header lookup per RPC)
    telemetry: str = "full"


class _ProviderMapping(Mapping):
    """Read-only dict-shaped view over a provider (``_store``/``_schemas``
    back-compat: external code historically peeked at those dicts)."""

    def __init__(self, provider: StorageProvider, getter):
        self._provider = provider
        self._getter = getter

    def __getitem__(self, name):
        if not self._provider.exists(name):
            raise KeyError(name)
        return self._getter(name)

    def __contains__(self, name):
        return self._provider.exists(name)

    def __iter__(self):
        return iter(self._provider.list())

    def __len__(self):
        return len(self._provider.list())


def parse_txn_body(raw: bytes) -> dict:
    """Decode a txn action body: ``StagedPutCommand`` bytes or a JSON dict.

    Returns ``{"txn_id", "dataset", ...}`` — JSON bodies may carry extra
    coordinator fields (e.g. ``expect_shards``)."""
    if not raw:
        raise FlightInvalidArgument("empty transaction body")
    if raw[0] == 0xC2:
        cmd = parse_command(raw)
        if not isinstance(cmd, StagedPutCommand):
            raise FlightInvalidArgument(
                f"txn action body must be a StagedPutCommand, got {type(cmd).__name__}")
        return {"txn_id": cmd.txn_id, "dataset": cmd.dataset}
    try:
        o = json.loads(raw.decode())
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        raise FlightInvalidArgument(f"unparseable txn body: {e}") from e
    if not isinstance(o, dict) or "txn_id" not in o:
        raise FlightInvalidArgument("txn body JSON must name a txn_id")
    return o


@dataclass
class _StagedTxn:
    """Bookkeeping for one staged-but-invisible transaction.

    The payload itself lives in the storage provider (durably, for the disk
    backend); the server only tracks counters, the in-txn dedup digests,
    and the TTL/prepared state that drive the 2PC protocol."""

    dataset: str
    schema: Schema
    batches: int = 0
    rows: int = 0
    nbytes: int = 0
    digests: set = field(default_factory=set)  # in-txn stream dedup (retries)
    expires_at: float = 0.0
    prepared: bool = False


class _LegacyExchangeService(ExchangeService):
    """Adapter: path exchange descriptors run ``do_exchange_impl`` per batch.

    The output schema is whatever the handler returns, so it cannot be
    declared up front — ``out_schema`` returns ``None`` and the serve loop
    defers the schema frame to the first output batch."""

    def __init__(self, server: "FlightServerBase", descriptor: FlightDescriptor):
        self._server = server
        self._descriptor = descriptor
        self.name = descriptor.key

    def out_schema(self, in_schema, params):
        return None  # deferred: sent with the first output batch

    def transform(self, in_schema, batches, params):
        for b in batches:
            yield self._server.do_exchange_impl(self._descriptor, in_schema, b)


class FlightServerBase:
    """Override the ``*_impl`` handlers to build a service."""

    def __init__(
        self,
        location_name: str = "local",
        auth_token: str | None = None,
        *,
        wire_codec: str = DEFAULT_CODEC,
        coalesce: bool = True,
        io_mode: str = "eventloop",
        io_workers: int = 0,
        telemetry: str = "full",
        middleware: Iterable[ServerMiddleware] | None = None,
        services: ExchangeServiceRegistry | None = None,
    ):
        self.location_name = location_name
        self.auth_token = auth_token
        self.wire_codec = wire_codec
        self.coalesce = coalesce
        self.io_mode = io_mode
        self.io_workers = io_workers
        self.encode_calls = 0  # encode_batch invocations on the DoGet path
        self.rows_served = 0  # rows shipped by DoGet (cached + uncached paths)
        # named streaming-exchange transforms (services.py); a shared
        # registry object makes one `register` visible on many servers
        self.services = services if services is not None else ExchangeServiceRegistry()
        self._listener: SocketListener | EventLoopListener | None = None
        self.telemetry = ServerTelemetry(telemetry, service=location_name)
        stack: list[ServerMiddleware] = list(middleware or [])
        if auth_token is not None and not any(
            isinstance(m, AuthTokenMiddleware) for m in stack
        ):
            stack.insert(0, AuthTokenMiddleware(auth_token))
        # first: counts rejected calls too; also the server-side tracer
        self.metrics = MetricsMiddleware(telemetry=self.telemetry)
        self.middleware = MiddlewareStack([self.metrics, *stack])

    # -- handlers to override ------------------------------------------- #
    def list_flights_impl(self) -> list[FlightInfo]:
        raise NotImplementedError

    def get_flight_info_impl(self, descriptor: FlightDescriptor) -> FlightInfo:
        raise NotImplementedError

    def do_get_impl(self, ticket: Ticket) -> tuple[Schema, Iterator[RecordBatch]]:
        raise NotImplementedError

    def do_get_encoded(
        self, ticket: Ticket
    ) -> tuple[EncodedMessage, list[EncodedMessage]] | None:
        """Optional fast path: pre-encoded ``(schema msg, batch msgs)``.

        Return ``None`` (the default) to serve through ``do_get_impl`` +
        per-request encoding."""
        return None

    def do_put_impl(
        self, descriptor: FlightDescriptor, schema: Schema, batches: Iterator[RecordBatch]
    ) -> dict:
        raise NotImplementedError

    def do_action_impl(self, action: Action) -> list[ActionResult]:
        raise NotImplementedError

    def do_exchange_impl(
        self, descriptor: FlightDescriptor, schema: Schema, batch: RecordBatch
    ) -> RecordBatch:
        """Per-batch handler for *path* exchange descriptors (the original
        scoring-microservice hook).  Command descriptors carrying an
        ``ExchangeCommand`` route through ``self.services`` instead — see
        ``resolve_exchange``."""
        raise NotImplementedError

    # -- locations -------------------------------------------------------- #
    def locations(self) -> tuple[Location, ...]:
        locs: list[Location] = [Location.inproc(self.location_name)]
        if self._listener is not None:
            locs.append(Location.for_tcp(self._listener.host, self._listener.port))
        return tuple(locs)

    # -- TCP serving ------------------------------------------------------ #
    def serve_tcp(self, host: str = "127.0.0.1", port: int = 0) -> "FlightServerBase":
        if self.io_mode == "eventloop":
            self._listener = EventLoopListener(
                self._dispatch_rpc, host, port,
                workers=self.io_workers or None,
                inline_ok=self._rpc_inline_ok,
                telemetry=self.telemetry.metrics_enabled).start()
        elif self.io_mode == "threads":
            self._listener = SocketListener(self._handle_connection, host, port).start()
        else:
            raise FlightInvalidArgument(
                f"unknown io_mode {self.io_mode!r} (eventloop|threads)",
                detail={"io_mode": self.io_mode})
        return self

    @property
    def port(self) -> int:
        assert self._listener is not None, "serve_tcp() first"
        return self._listener.port

    def shutdown(self) -> None:
        if self._listener is not None:
            self._listener.stop()
            self._listener = None

    def _rpc_inline_ok(self, req: dict) -> bool:
        """Certify a request for loop-thread dispatch (eventloop.py).

        Inline RPCs run on the event loop's one dispatch thread, so the
        contract is strict: never read another frame, never block, cheap.
        The base server can only vouch for ``Handshake``; subclasses widen
        this where they can *prove* the fast path (see
        ``InMemoryFlightServer``).  User middleware voids the certificate —
        its hooks run inside the dispatch and may block."""
        if any(type(m).__module__ != MiddlewareStack.__module__
               for m in self.middleware.items):
            return False
        return req.get("method") == "Handshake"

    # -- dispatch ---------------------------------------------------------- #
    def _check_auth(self, req: dict) -> None:
        """Deprecated — auth now runs as ``AuthTokenMiddleware``."""
        if self.auth_token is not None and req.get("token") != self.auth_token:
            raise FlightUnauthenticated("bad or missing token")

    def _call_context(self, method: str, req: dict) -> CallContext:
        opts = req.get("options") or {}
        headers = {"token": req.get("token")}
        headers.update(opts.get("headers") or {})
        return CallContext(method=method, headers=headers, request=req)

    def _handle_connection(self, conn: FrameConnection) -> None:
        """One connection = a sequence of RPCs (like an HTTP/2 channel).

        The blocking serve loop of the thread-per-connection listener; the
        event-loop listener instead calls ``_dispatch_rpc`` per opening
        frame from its worker pool.  Both run the same dispatch."""
        while True:
            try:
                kind, req, _ = conn.recv_frame()
            except (ConnectionError, OSError):
                return
            self._dispatch_rpc(conn, kind, req)

    def _dispatch_rpc(self, conn: FrameConnection, kind: int, req: dict) -> None:
        """Serve one RPC whose opening frame has already been read.

        Raises ``FlightError`` for protocol violations that must kill the
        connection (non-control opening frame); RPC-level failures are
        reported to the peer as typed error frames and the channel stays
        usable."""
        if kind != KIND_CTRL:
            raise FlightError("expected control frame opening an RPC")
        method = req.get("method")
        opts = req.get("options") or {}
        ctx = self._call_context(method or "?", req)
        # event-loop channels stamp how long the opening frame sat parsed in
        # the inbox before a worker picked it up (0 inline: it never
        # queued); traced spans surface it as the "queue" stage.  Threaded
        # connections have no inbox, and no stage
        queue_wait = getattr(conn, "last_queue_wait_s", None)
        if queue_wait is not None:
            ctx.state["queue_wait_s"] = queue_wait
        try:
            # unary verbs buffer their reply and send it *after* the
            # middleware chain unwinds: once the client holds the answer,
            # every on_complete hook (metrics, logging) has already fired
            reply: dict | None = None
            with self.middleware.wrap(ctx):
                if method == "GetFlightInfo":
                    info = self.get_flight_info_impl(
                        FlightDescriptor.from_json(req["descriptor"]))
                    reply = {"info": info.to_json()}
                elif method == "ListFlights":
                    infos = self.list_flights_impl()
                    reply = {"infos": [i.to_json() for i in infos]}
                elif method == "DoAction":
                    results = self.do_action_impl(Action.from_json(req["action"]))
                    reply = {"results": [r.to_json() for r in results]}
                elif method == "DoGet":
                    self._serve_do_get(conn, Ticket.from_json(req["ticket"]), opts)
                elif method == "DoPut":
                    self._serve_do_put(conn, FlightDescriptor.from_json(req["descriptor"]))
                elif method == "DoExchange":
                    self._serve_do_exchange(
                        conn, FlightDescriptor.from_json(req["descriptor"]), opts)
                elif method == "Handshake":
                    reply = {"ok": True}
                else:
                    raise FlightInvalidArgument(f"unknown method {method!r}")
            if reply is not None:
                conn.send_ctrl(reply)
        except FlightError as e:
            conn.send_ctrl(e.to_wire())

    def _send_stream(
        self, conn: FrameConnection, msgs: Iterable[EncodedMessage], coalesce: bool | None = None
    ) -> None:
        if self.coalesce if coalesce is None else coalesce:
            conn.send_data_many(msgs)
        else:
            for m in msgs:
                conn.send_data(m)

    def _serve_do_get(self, conn: FrameConnection, ticket: Ticket, opts: dict | None = None) -> None:
        opts = opts or {}
        codec = opts.get("wire_codec") or self.wire_codec
        if codec not in (CODEC_BINARY, CODEC_JSON):
            # reject before the ok frame: an unknown codec must be a typed
            # refusal, not a ValueError killing the handler mid-stream
            raise FlightInvalidArgument(f"unknown wire codec {codec!r}",
                                        detail={"wire_codec": codec})
        coalesce = opts.get("coalesce")
        # stage timing is sampled: only a traced request (active span set by
        # MetricsMiddleware) pays the perf_counter pairs on this hot path
        traced = current_span() is not None
        pre = self.do_get_encoded(ticket) if codec == self.wire_codec else None
        if pre is not None:  # encode-once cache: no per-request encoding
            schema_msg, batch_msgs = pre
            conn.send_ctrl({"ok": True})
            t0 = time.perf_counter() if traced else 0.0
            self._send_stream(
                conn, chain((schema_msg,), batch_msgs, (encode_eos(codec),)), coalesce
            )
            if traced:
                add_stage("flush", time.perf_counter() - t0)
            return
        schema, batches = self.do_get_impl(ticket)
        conn.send_ctrl({"ok": True})

        def frames() -> Iterator[EncodedMessage]:
            yield encode_schema(schema)
            for b in batches:
                self.encode_calls += 1
                self.rows_served += b.num_rows
                if traced:
                    te = time.perf_counter()
                    msg = encode_batch(b, codec)
                    add_stage("encode", time.perf_counter() - te)
                    yield msg
                else:
                    yield encode_batch(b, codec)
            yield encode_eos(codec)

        t0 = time.perf_counter() if traced else 0.0
        self._send_stream(conn, frames(), coalesce)
        if traced:
            # the walltime of the send loop minus encode = queueing/sendmsg
            add_stage("flush", max(time.perf_counter() - t0
                                   - (current_span().stages.get("encode", 0.0)), 0.0))

    def _recv_stream(self, conn: FrameConnection) -> tuple[Schema, Iterator[RecordBatch]]:
        kind, meta, body = conn.recv_frame()
        if kind != KIND_DATA:
            raise FlightError("expected schema message")
        msg = decode_message(meta, body)
        if msg.kind != "schema":
            raise FlightError(f"expected schema, got {msg.kind}")
        schema = msg.schema

        def gen() -> Iterator[RecordBatch]:
            while True:
                k, m, b = conn.recv_frame()
                if k != KIND_DATA:
                    raise FlightError("expected data frame in stream")
                dm = decode_message(m, b)
                if dm.kind == "eos":
                    return
                yield dm.batch(schema)

        return schema, gen()

    def _serve_do_put(self, conn: FrameConnection, descriptor: FlightDescriptor) -> None:
        conn.send_ctrl({"ok": True})
        schema, batches = self._recv_stream(conn)
        stats = self.do_put_impl(descriptor, schema, batches)
        conn.send_ctrl({"ok": True, "stats": stats})

    # -- streaming DoExchange (the microservice plane; see exchange.py) ---- #
    def resolve_exchange(self, descriptor: FlightDescriptor) -> tuple[ExchangeService, dict]:
        """Which transform serves this exchange descriptor.

        ``ExchangeCommand`` descriptors route through the ``services``
        registry (unknown names are a typed ``FlightNotFound`` refused
        before the stream opens); path descriptors keep the legacy
        per-batch ``do_exchange_impl`` semantics via an adapter."""
        if descriptor.command is not None:
            cmd = descriptor.parsed_command()
            if isinstance(cmd, ExchangeCommand):
                return self.services.get(cmd.service), cmd.params
            raise FlightInvalidArgument(
                f"DoExchange takes an ExchangeCommand or path descriptor, "
                f"not {type(cmd).__name__}")
        return _LegacyExchangeService(self, descriptor), {}

    def _serve_do_exchange(self, conn: FrameConnection, descriptor: FlightDescriptor,
                           opts: dict | None = None) -> None:
        opts = opts or {}
        codec = opts.get("wire_codec") or self.wire_codec
        if codec not in (CODEC_BINARY, CODEC_JSON):
            raise FlightInvalidArgument(f"unknown wire codec {codec!r}",
                                        detail={"wire_codec": codec})
        coalesce = self.coalesce if opts.get("coalesce") is None else opts["coalesce"]
        window = max(1, int(opts.get("read_window") or DEFAULT_WINDOW))
        # service resolution and param validation failures (unknown name,
        # malformed command, malformed params) refuse *before* the ok frame:
        # the client has not started streaming and the channel stays clean.
        # Schema-dependent validation (project's unknown-column check) needs
        # the input schema and surfaces as a typed mid-stream error instead
        service, params = self.resolve_exchange(descriptor)
        service.check_params(params)
        conn.send_ctrl({"ok": True})
        try:
            self._run_exchange(conn, service, params, codec, coalesce, window)
        except (ConnectionError, OSError):
            raise  # peer died: nothing to report, nobody to report it to
        except Exception as e:
            # mid-stream failure: input frames may still be in flight, so
            # the channel cannot be reused — send the typed error as a
            # control frame (the client rehydrates it mid-read) and tear
            # the connection down.  Non-Flight exceptions (a service
            # callable bug) surface as the base typed error, matching the
            # inproc path, instead of killing the handler thread raw
            err = e if isinstance(e, FlightError) else FlightError(f"exchange failed: {e}")
            try:
                conn.send_ctrl(err.to_wire())
            except (ConnectionError, OSError):
                pass
            conn.close()
            raise ConnectionError(f"exchange aborted: {err}") from e

    def _run_exchange(self, conn: FrameConnection, service: ExchangeService,
                      params: dict, codec: str, coalesce: bool, window: int) -> None:
        """The pipelined exchange loop, single-threaded by design.

        The serve thread alternates between pulling input frames (as the
        service consumes them) and emitting output frames; pipelining comes
        from *buffering with flush-before-block*: encoded output frames
        accumulate while more input is already waiting (one coalesced
        ``sendmsg`` per ~budget), and flush the moment a read would block —
        so a lockstep (window=1) peer always sees its response before the
        server waits for its next batch, while a windowed peer gets
        syscall-amortized bursts.  Backpressure is the client-side window:
        the server acks batches as the service consumes them (``{"ack": n}``
        control frames riding the output direction), and the client writer
        blocks once ``window`` batches are unacked — so at most ``window``
        batches are ever queued in the socket, and a serial server never
        needs its own input queue.

        A traced call leaves two child spans of its RPC span: ``flight.read``
        from here until its first input batch is decoded (or its input
        ends), and ``flight.reply`` from its first output batch (or, with
        none, its end of stream) to the final ``ok``.  In a streaming call
        the later batches' work runs inside ``flight.reply``.  A call that
        fails records its RPC span's error and neither interval."""
        tel = self.telemetry
        read = tel.interval("flight.read")  # None when untraced
        traced = read is not None
        reply = None

        def end_read() -> None:
            nonlocal read
            if read is not None:
                tel.close(read)
                read = None

        def begin_reply() -> None:
            nonlocal reply
            if traced and reply is None:
                reply = tel.interval("flight.reply")

        kind, meta, body = conn.recv_frame()
        if kind != KIND_DATA:
            raise FlightInvalidArgument("exchange: expected a schema data frame first")
        msg = decode_message(meta, body)
        if msg.kind != "schema":
            raise FlightInvalidArgument(
                f"exchange: expected schema first, got {msg.kind!r}")
        in_schema = msg.schema
        state = {"in": 0, "acked": 0, "rows_in": 0, "out": 0, "rows_out": 0}
        every = ack_interval(window)
        pending: list[EncodedMessage] = []
        pending_bytes = 0

        def flush() -> None:
            nonlocal pending, pending_bytes
            if not pending:
                return
            if coalesce and len(pending) > 1:
                conn.send_data_many(pending)
            else:
                for f in pending:
                    conn.send_data(f)
            pending = []
            pending_bytes = 0

        def emit(frame: EncodedMessage) -> None:
            nonlocal pending_bytes
            pending.append(frame)
            pending_bytes += frame.nbytes()
            if not coalesce or pending_bytes >= COALESCE_BYTES:
                flush()

        def emit_batch(ob: RecordBatch) -> None:
            begin_reply()
            emit(encode_batch(ob, codec))

        def inputs() -> Iterator[RecordBatch]:
            while True:
                if not conn.receive_ready():
                    flush()  # about to block on the peer: let it see progress
                k, m, b = conn.recv_frame()
                if k != KIND_DATA:
                    raise FlightInvalidArgument(
                        "exchange: unexpected control frame in the input stream")
                dm = decode_message(m, b)
                if dm.kind == "eos":
                    if state["acked"] != state["in"]:  # final ack frees the writer
                        conn.send_ctrl({"ack": state["in"]})
                        state["acked"] = state["in"]
                    end_read()
                    return
                if dm.kind == "schema":
                    raise FlightInvalidArgument("exchange: duplicate schema mid-stream")
                state["in"] += 1
                state["rows_in"] += dm.batch_meta.rows
                if state["in"] - state["acked"] >= every:
                    conn.send_ctrl({"ack": state["in"]})
                    state["acked"] = state["in"]
                batch = dm.batch(in_schema)
                end_read()
                yield batch

        # `declare` sends directly: it only ever runs with nothing pending
        # (up front, or immediately before the first output batch), so the
        # schema frame is never held back by the coalescing buffer
        drive_exchange(
            service, in_schema, params, inputs(),
            declare=lambda s: conn.send_data(encode_schema(s)),
            emit=emit_batch,
            state=state,
        )
        begin_reply()
        emit(encode_eos(codec))
        flush()
        conn.send_ctrl({"ok": True, "stats": {
            "service": service.name,
            "batches_in": state["in"], "rows_in": state["rows_in"],
            "batches_out": state["out"], "rows_out": state["rows_out"],
        }})
        if reply is not None:
            tel.close(reply)


def _query_out_schema(plan, schema: Schema) -> Schema:
    """Schema a QueryCommand's DoGet stream carries.

    Aggregating plans stream per-group *state* batches (the partial half of
    the operator split), so the planned FlightInfo schema is the state
    schema — which also makes empty shards merge cleanly (the scheduler
    materializes an empty state batch from it).  Plain plans stream rows in
    the projected schema.  ``group_by`` without aggregations is refused:
    the plane has no distinct-rows operator."""
    from ...query.engine import partial_schema  # lazy: engine imports this layer

    if plan.group_by and not plan.aggregations:
        raise FlightInvalidArgument(
            "QueryPlan.group_by requires at least one aggregation")
    if plan.aggregations:
        return partial_schema(plan, schema)
    return schema.select(plan.projection) if plan.projection else schema


def _content_digest(schema: Schema, batches: list[RecordBatch]) -> str:
    """Stable content hash of a put payload (dedup key for retried puts).

    Hashes the IPC frame *views* (metadata + zero-copy buffer slices) rather
    than materializing each message, so the cost is one pass over the bytes
    with no per-batch body copy."""
    h = hashlib.blake2b(digest_size=16)
    h.update(json.dumps(schema.to_json(), sort_keys=True).encode())
    for b in batches:
        for part in encode_batch(b).frame_parts():
            h.update(part)
    return h.hexdigest()


class InMemoryFlightServer(FlightServerBase):
    """Dataset store: descriptor path[0] -> list[RecordBatch].

    The store itself lives behind a pluggable ``StorageProvider``
    (storage.py) — memory (default, the historical behavior), ``disk:<root>``
    (Arrow-IPC spill files, mmap-backed re-serve, durable staging +
    restart recovery), or ``remote:<uri>`` (forward to another Flight
    endpoint).  The serving layer — verbs, encode-once cache, the 2PC
    staging protocol — is identical across backends."""

    def __init__(
        self,
        location_name: str = "local",
        auth_token=_UNSET,
        batches_per_endpoint=_UNSET,
        shard_id: int | None = None,
        *,
        config: ServerConfig | None = None,
        wire_codec=_UNSET,
        coalesce=_UNSET,
        cache_encoded=_UNSET,
        endpoints_per_query=_UNSET,
        dedup_puts=_UNSET,
        stage_ttl=_UNSET,
        storage=_UNSET,
        io_mode=_UNSET,
        io_workers=_UNSET,
        telemetry=_UNSET,
        middleware: Iterable[ServerMiddleware] | None = None,
        services: ExchangeServiceRegistry | None = None,
    ):
        # legacy kwargs (accepted for one release) route through ServerConfig;
        # an explicitly passed kwarg wins over the same field of `config`
        cfg = config if config is not None else ServerConfig()
        overrides = {
            k: v for k, v in {
                "auth_token": auth_token,
                "batches_per_endpoint": batches_per_endpoint,
                "wire_codec": wire_codec,
                "coalesce": coalesce,
                "cache_encoded": cache_encoded,
                "endpoints_per_query": endpoints_per_query,
                "dedup_puts": dedup_puts,
                "stage_ttl": stage_ttl,
                "storage": storage,
                "io_mode": io_mode,
                "io_workers": io_workers,
                "telemetry": telemetry,
            }.items() if v is not _UNSET
        }
        if overrides:
            cfg = replace(cfg, **overrides)
        self.config = cfg
        super().__init__(location_name, cfg.auth_token, wire_codec=cfg.wire_codec,
                         coalesce=cfg.coalesce, io_mode=cfg.io_mode,
                         io_workers=cfg.io_workers, telemetry=cfg.telemetry,
                         middleware=middleware, services=services)
        self._provider = make_provider(cfg.storage)
        self._lock = threading.Lock()
        self.batches_per_endpoint = cfg.batches_per_endpoint  # 0 = single endpoint
        self.shard_id = shard_id  # set by cluster.py: stamped into tickets
        if shard_id is not None:
            self.telemetry.shard = shard_id  # spans carry shard identity
        self.endpoints_per_query = cfg.endpoints_per_query  # GetFlightInfo(QueryCommand) fan-out
        # encode-once cache: dataset -> (schema msg, per-batch msgs), built on
        # first DoGet, invalidated whenever the dataset changes
        self.cache_encoded = cfg.cache_encoded
        self._encoded: dict[
            str, tuple[EncodedMessage, tuple[EncodedMessage, ...], tuple[int, ...]]
        ] = {}
        self._versions: dict[str, int] = {}  # bumped on every dataset mutation
        self.cache_hits = 0
        self.cache_misses = 0
        # query pushdown counters (per-shard evidence that filtering ran here)
        self.queries_executed = 0
        self.query_rows_in = 0
        self.query_rows_out = 0
        self.partial_aggs_executed = 0  # DoGet served per-group state, not rows
        self.joins_executed = 0         # local-join actions run on this shard
        # DoPut dedup guard: dataset -> recent payload content hashes
        self.dedup_puts = cfg.dedup_puts
        self._recent_puts: dict[str, OrderedDict[str, dict]] = {}
        self.put_dedup_hits = 0
        # transactional staged puts: txn_id -> staged payload, plus a window
        # of finished txns so duplicate commit/abort rounds are idempotent
        self.stage_ttl = cfg.stage_ttl
        self._staged: dict[str, _StagedTxn] = {}
        self._finished_txns: OrderedDict[str, tuple[str, dict]] = {}
        self._reaper: threading.Thread | None = None
        self._reaper_stop = threading.Event()
        self.txn_commits = 0
        self.txn_aborts = 0
        self.txn_gc_reaped = 0
        # restart recovery: a durable provider hands back the stages a
        # previous process left behind — prepared ones stay GC-exempt and
        # commit/abort from the coordinator finishes the interrupted 2PC
        for txn_id, e in self._provider.staged_txns().items():
            self._staged[txn_id] = _StagedTxn(
                e.dataset, e.schema, e.batches, e.rows, e.nbytes,
                expires_at=time.monotonic() + self.stage_ttl,
                prepared=e.prepared)
        if self._staged:
            with self._lock:
                self._ensure_reaper()

    @property
    def storage(self) -> StorageProvider:
        return self._provider

    # back-compat read views: external code (and a long tail of tests)
    # historically peeked at the server's store/schema dicts
    @property
    def _store(self) -> Mapping:
        return _ProviderMapping(self._provider, self._provider.read_batches)

    @property
    def _schemas(self) -> Mapping:
        return _ProviderMapping(self._provider, self._provider.schema)

    # -- direct (in-proc) API ------------------------------------------- #
    def add_dataset(
        self, name: str, batches: list[RecordBatch], schema: Schema | None = None
    ) -> None:
        """``schema`` allows registering an empty shard of a known dataset."""
        if schema is None:
            schema = batches[0].schema
        with self._lock:
            self._provider.replace(name, schema, list(batches))
            self._encoded.pop(name, None)
            self._recent_puts.pop(name, None)
            self._versions[name] = self._versions.get(name, 0) + 1

    def dataset(self, name: str) -> list[RecordBatch]:
        return self._provider.read_batches(name)

    # -- handlers ---------------------------------------------------------- #
    def _info_for(self, name: str) -> FlightInfo:
        info = self._provider.info(name)
        n = info["batches"]
        per = self.batches_per_endpoint or n or 1
        extra = {} if self.shard_id is None else {"shard": self.shard_id}
        # a traced planning call stamps its span into the endpoints, so the
        # scheduler's later DoGets stitch to this GetFlightInfo's trace
        md = dict(extra)
        trace = propagation_headers()
        if trace is not None:
            md["trace"] = trace
        endpoints = [
            FlightEndpoint(
                Ticket.for_range(name, i, min(i + per, n), **extra),
                self.locations(),
                app_metadata=md or None,
            )
            for i in range(0, max(n, 1), per)
        ]
        return FlightInfo(
            self._provider.schema(name),
            FlightDescriptor.for_path(name),
            endpoints,
            total_records=info["rows"],
            total_bytes=info["bytes"],
        )

    def _plan_query_info(self, cmd: QueryCommand, descriptor: FlightDescriptor) -> FlightInfo:
        """Plan ``GetFlightInfo(QueryCommand)``: per-range query endpoints.

        The command's own ``[start, stop)`` scope (if any) bounds the planned
        ranges, so a ranged query descriptor only ever touches its slice."""
        plan = cmd.plan
        with self._lock:
            if not self._provider.exists(plan.dataset):
                raise FlightNotFound(f"no such dataset: {plan.dataset}",
                                     detail={"dataset": plan.dataset})
            n = self._provider.info(plan.dataset)["batches"]
            schema = self._provider.schema(plan.dataset)
        out_schema = _query_out_schema(plan, schema)
        lo = min(max(cmd.start, 0), n)
        hi = n if cmd.stop < 0 else min(cmd.stop, n)
        span = max(hi - lo, 0)
        per = max(1, -(-span // self.endpoints_per_query))
        extra = {} if self.shard_id is None else {"shard": self.shard_id}
        trace = propagation_headers()
        if trace is not None:
            extra = {**extra, "trace": trace}
        endpoints = [
            FlightEndpoint(
                Ticket.for_command(
                    QueryCommand(cmd.plan_bytes, i, min(i + per, hi), self.shard_id)),
                self.locations(),
                app_metadata=extra or None,
            )
            for i in range(lo, max(hi, lo + 1), per)
        ]
        return FlightInfo(out_schema, descriptor, endpoints,
                          total_records=-1, total_bytes=-1)

    def list_flights_impl(self) -> list[FlightInfo]:
        with self._lock:
            return [self._info_for(name) for name in self._provider.list()]

    def get_flight_info_impl(self, descriptor: FlightDescriptor) -> FlightInfo:
        if descriptor.path is None:
            cmd = descriptor.parsed_command()
            if isinstance(cmd, QueryCommand):
                return self._plan_query_info(cmd, descriptor)
            raise FlightInvalidArgument(
                f"in-memory store plans path or query descriptors, not "
                f"{type(cmd).__name__}")
        name = descriptor.path[0]
        with self._lock:
            if not self._provider.exists(name):
                raise FlightNotFound(f"no such flight: {name}", detail={"dataset": name})
            return self._info_for(name)

    def _execute_query(self, cmd: QueryCommand) -> tuple[Schema, Iterator[RecordBatch]]:
        """Native QueryCommand execution: filter/project where the data lives.

        A plan carrying aggregations runs the *partial* half of the operator
        split instead: the stream is one per-group state batch (per-group
        sums/counts/extrema — see ``query.engine.partial_schema``), not rows.
        The caller (cluster head or client) merges state batches from every
        shard with ``merge_partials`` — only group-sized state crosses the
        wire, never the surviving rows."""
        from ...query.engine import execute, partial_aggregate

        plan = cmd.plan
        with self._lock:
            if not self._provider.exists(plan.dataset):
                raise FlightNotFound(f"no such dataset: {plan.dataset}",
                                     detail={"dataset": plan.dataset})
            stop = cmd.stop if cmd.stop >= 0 else None
            batches = self._provider.read_batches(plan.dataset, cmd.start, stop)
            schema = self._provider.schema(plan.dataset)
        out_schema = _query_out_schema(plan, schema)
        if plan.aggregations:
            state = partial_aggregate(plan, batches, schema)
            with self._lock:
                self.queries_executed += 1
                self.partial_aggs_executed += 1
                self.query_rows_in += sum(b.num_rows for b in batches)
                self.query_rows_out += state.num_rows
            return out_schema, iter([state])
        results = list(execute(plan, batches))
        with self._lock:
            self.queries_executed += 1
            self.query_rows_in += sum(b.num_rows for b in batches)
            self.query_rows_out += sum(b.num_rows for b in results)
        return out_schema, iter(results)

    def do_get_impl(self, ticket: Ticket) -> tuple[Schema, Iterator[RecordBatch]]:
        cmd = ticket.command()
        if isinstance(cmd, QueryCommand):
            return self._execute_query(cmd)
        if isinstance(cmd, (StagedPutCommand, ExchangeCommand)):
            raise FlightInvalidArgument(
                f"{type(cmd).__name__} tickets are not redeemable via DoGet")
        name = cmd.dataset
        with self._lock:
            if not self._provider.exists(name):
                raise FlightNotFound(f"no such flight: {name}", detail={"dataset": name})
            stop = cmd.stop if cmd.stop >= 0 else None
            batches = self._provider.read_batches(name, cmd.start, stop)
            schema = self._provider.schema(name)
        return schema, iter(batches)

    def do_get_encoded(
        self, ticket: Ticket
    ) -> tuple[EncodedMessage, list[EncodedMessage]] | None:
        # A subclass or monkeypatch that changes do_get_impl (pacing, fault
        # injection) must keep serving through it.
        if (
            not self.cache_encoded
            or type(self).do_get_impl is not InMemoryFlightServer.do_get_impl
            or "do_get_impl" in self.__dict__
        ):
            return None
        cmd = ticket.command()
        if isinstance(cmd, QueryCommand):
            # pass-through queries (no predicate, full projection, no limit)
            # are range reads in disguise: serve them from the cache.  Real
            # pushdown queries return per-request results and must never
            # enter (or poison) the cache.
            plan = cmd.plan
            with self._lock:
                schema = (self._provider.schema(plan.dataset)
                          if self._provider.exists(plan.dataset) else None)
            if schema is None or not plan.is_passthrough(schema.names):
                return None
            name, start, stop = plan.dataset, cmd.start, cmd.stop
        elif isinstance(cmd, RangeReadCommand):
            name, start, stop = cmd.dataset, cmd.start, cmd.stop
        else:
            return None
        stop_ix = stop if stop >= 0 else None
        with self._lock:
            if not self._provider.exists(name):
                raise FlightNotFound(f"no such flight: {name}", detail={"dataset": name})
            entry = self._encoded.get(name)
            if entry is not None:
                self.cache_hits += 1
                self.rows_served += sum(entry[2][start:stop_ix])
                return entry[0], list(entry[1][start:stop_ix])
            self.cache_misses += 1
            batches = self._provider.read_batches(name)
            schema = self._provider.schema(name)
            version = self._versions.get(name, 0)
        # encode outside the lock: a multi-GB first build must not stall
        # every other RPC on this server.  For the disk provider the batches
        # are mmap-backed views, so this pass is the only value-data read.
        schema_msg = encode_schema(schema)
        msgs = []
        for b in batches:
            self.encode_calls += 1
            msgs.append(encode_batch(b, self.wire_codec))
        entry = (schema_msg, tuple(msgs), tuple(b.num_rows for b in batches))
        with self._lock:
            # cache only if the dataset didn't change while we encoded; the
            # stale-but-consistent snapshot still serves this request
            if self._versions.get(name, 0) == version and self._provider.exists(name):
                self._encoded[name] = entry
            self.rows_served += sum(entry[2][start:stop_ix])
        return entry[0], list(entry[1][start:stop_ix])

    def _rpc_inline_ok(self, req: dict) -> bool:
        """Widen the base certificate: a cache-warm ``DoGet`` is pure
        memoryview queueing (no encode, no user code, no blocking), so the
        event loop may serve it on the dispatch thread.  A cold cache, an
        overridden ``do_get_impl``, a real pushdown query, or a foreign
        codec all fall back to the worker pool — first request per dataset
        warms the cache through a worker, the rest inline."""
        if req.get("method") == "DoGet":
            if any(type(m).__module__ != MiddlewareStack.__module__
                   for m in self.middleware.items):
                return False
            opts = req.get("options") or {}
            if (opts.get("wire_codec") or self.wire_codec) != self.wire_codec:
                return False
            if (
                not self.cache_encoded
                or type(self).do_get_impl is not InMemoryFlightServer.do_get_impl
                or "do_get_impl" in self.__dict__
            ):
                return False
            try:
                cmd = Ticket.from_json(req["ticket"]).command()
            except Exception:
                return False
            if isinstance(cmd, RangeReadCommand):
                name = cmd.dataset
            elif isinstance(cmd, QueryCommand):
                name = cmd.plan.dataset
                with self._lock:
                    schema = (self._provider.schema(name)
                              if self._provider.exists(name) else None)
                if schema is None or not cmd.plan.is_passthrough(schema.names):
                    return False
            else:
                return False
            with self._lock:
                return name in self._encoded
        return super()._rpc_inline_ok(req)

    # -- transactional staged puts -------------------------------------- #
    def _ensure_reaper(self) -> None:
        """Start the GC reaper lazily (under ``self._lock``); it exits when
        the staging store drains and restarts on the next stage."""
        if self._reaper is not None and self._reaper.is_alive():
            return
        self._reaper_stop = threading.Event()
        self._reaper = threading.Thread(
            target=self._reap_loop, daemon=True,
            name=f"stage-gc-{self.location_name}")
        self._reaper.start()

    def _reap_loop(self) -> None:
        interval = min(max(self.stage_ttl / 4.0, 0.02), 30.0)
        stop = self._reaper_stop
        while not stop.wait(interval):
            self._gc_staged()
            with self._lock:
                if not self._staged:  # idle: exit; _ensure_reaper restarts us
                    self._reaper = None
                    return

    def _gc_staged(self) -> None:
        """Discard expired stages — an orphaned writer's payload is never
        readable, and stops holding memory after ``stage_ttl`` seconds.

        *Prepared* stages are exempt: after a yes vote the txn's fate
        belongs to the coordinator, and reaping it here could land between
        a sibling shard's commit and ours — a half-visible txn.  The cost
        is the classic 2PC in-doubt window: a coordinator that dies after
        prepare leaves the stage pinned until an explicit txn-abort."""
        now = time.monotonic()
        with self._lock:
            expired = [t for t, s in self._staged.items()
                       if s.expires_at <= now and not s.prepared]
            for txn_id in expired:
                self._staged.pop(txn_id)
                self._provider.discard_stage(txn_id)
                self._finish_txn(txn_id, "expired", {})
                self.txn_gc_reaped += 1

    def _finish_txn(self, txn_id: str, outcome: str, stats: dict) -> None:
        """Record a txn's fate (idempotency window). Caller holds the lock."""
        self._finished_txns[txn_id] = (outcome, stats)
        while len(self._finished_txns) > _TXN_FINISH_WINDOW:
            self._finished_txns.popitem(last=False)

    def _stage_put(self, cmd: StagedPutCommand, schema: Schema,
                   received: list[RecordBatch]) -> dict:
        """The stage leg: payload lands keyed by txn id, invisible to reads.

        Stages never touch ``_store`` or the encode-once cache — cache
        invalidation happens on commit, when the data becomes visible.
        Re-staged streams (scheduler put retries) are deduplicated by
        content hash *within the txn*, so a retry cannot double rows.
        Like the plain-put guard this is gated on ``dedup_puts`` and shares
        its trade-off: byte-identical parallel streams in one txn are
        indistinguishable from retries and collapse to one — stage distinct
        payloads, or construct the server with ``dedup_puts=False`` (which
        also makes stage-leg retries unsafe, exactly as for plain puts)."""
        digest = _content_digest(schema, received) if self.dedup_puts else None
        nbytes = sum(b.nbytes() for b in received)
        rows = sum(b.num_rows for b in received)
        with self._lock:
            outcome = self._finished_txns.get(cmd.txn_id)
            if outcome is not None:
                raise FlightInvalidArgument(
                    f"txn {cmd.txn_id!r} already {outcome[0]}: cannot stage",
                    detail={"txn_id": cmd.txn_id, "outcome": outcome[0]})
            txn = self._staged.get(cmd.txn_id)
            if txn is None:
                txn = self._staged[cmd.txn_id] = _StagedTxn(cmd.dataset, schema)
                self._ensure_reaper()
            elif txn.dataset != cmd.dataset:
                raise FlightInvalidArgument(
                    f"txn {cmd.txn_id!r} is bound to dataset {txn.dataset!r}",
                    detail={"txn_id": cmd.txn_id, "dataset": txn.dataset})
            elif txn.schema != schema:
                raise FlightInvalidArgument(
                    f"schema mismatch on staged stream of txn {cmd.txn_id!r}")
            txn.expires_at = time.monotonic() + self.stage_ttl
            if digest is not None:
                if digest in txn.digests:  # retried stage stream: idempotent
                    self.put_dedup_hits += 1
                    return {"staged": True, "txn_id": cmd.txn_id, "deduped": True,
                            "batches": len(received), "rows": rows,
                            "bytes": nbytes}
                txn.digests.add(digest)
            # payload lands in the provider (durably, for the disk backend)
            self._provider.stage(cmd.txn_id, cmd.dataset, schema, received)
            txn.batches += len(received)
            txn.rows += rows
            txn.nbytes += nbytes
        return {"staged": True, "txn_id": cmd.txn_id, "batches": len(received),
                "rows": rows, "bytes": nbytes}

    def _txn_prepare(self, o: dict) -> dict:
        """Phase-1 vote: is this txn's stage present and healthy here?

        Never raises for an unknown txn — the coordinator uses ``staged``
        to tell participants from bystanders.  Preparing refreshes the TTL
        so GC cannot race the commit that immediately follows."""
        self._gc_staged()
        txn_id = o["txn_id"]
        with self._lock:
            outcome = self._finished_txns.get(txn_id)
            if outcome is not None and outcome[0] == "committed":
                return {"txn_id": txn_id, "staged": True, "committed": True,
                        **outcome[1]}
            if outcome is not None and outcome[0] == "expired":
                # the stage was here but the reaper ate it: the coordinator
                # must abort the whole txn, not commit the surviving shards
                return {"txn_id": txn_id, "staged": False, "expired": True}
            txn = self._staged.get(txn_id)
            if txn is None or outcome is not None:
                return {"txn_id": txn_id, "staged": False}
            txn.prepared = True
            txn.expires_at = time.monotonic() + self.stage_ttl
            # durable backends persist the yes vote: a prepared stage must
            # survive a restart and stay GC-exempt in the next process too
            self._provider.mark_prepared(txn_id)
            return {"txn_id": txn_id, "staged": True,
                    "batches": txn.batches,
                    "rows": txn.rows,
                    "bytes": txn.nbytes}

    def _txn_commit(self, o: dict) -> dict:
        """Flip a txn's staged batches into the visible dataset atomically.

        The flip happens under one ``self._lock`` acquisition — the same
        lock every DoGet/query snapshot takes — so a concurrent reader sees
        either none or all of the txn's batches, never a torn prefix."""
        self._gc_staged()
        txn_id = o["txn_id"]
        with self._lock:
            outcome = self._finished_txns.get(txn_id)
            if outcome is not None:
                if outcome[0] == "committed":  # duplicate commit: idempotent
                    return {**outcome[1], "committed": True, "duplicate": True}
                if outcome[0] == "aborted":
                    raise FlightInvalidArgument(
                        f"txn {txn_id!r} was aborted: cannot commit",
                        detail={"txn_id": txn_id, "outcome": outcome[0]})
            txn = self._staged.pop(txn_id, None)
            if txn is None:
                raise FlightNotFound(
                    f"no staged txn {txn_id!r} (never staged, or GC'd after "
                    f"{self.stage_ttl}s)", detail={"txn_id": txn_id})
            name = txn.dataset
            # the provider makes the staged payload part of the dataset —
            # on disk, an atomic rename of the staged part files
            self._provider.commit_stage(txn_id)
            self._encoded.pop(name, None)  # visibility flip invalidates cache
            self._versions[name] = self._versions.get(name, 0) + 1
            stats = {
                "txn_id": txn_id,
                "dataset": name,
                "batches": txn.batches,
                "rows": txn.rows,
                "bytes": txn.nbytes,
            }
            self._finish_txn(txn_id, "committed", stats)
            self.txn_commits += 1
        return {**stats, "committed": True}

    def _txn_abort(self, o: dict) -> dict:
        """Discard a txn's staged batches.  Unknown/expired txns are a
        no-op (idempotent — the coordinator aborts broadly on failure);
        aborting a *committed* txn is a protocol error and surfaces."""
        self._gc_staged()
        txn_id = o["txn_id"]
        with self._lock:
            outcome = self._finished_txns.get(txn_id)
            if outcome is not None:
                if outcome[0] == "committed":
                    raise FlightInvalidArgument(
                        f"txn {txn_id!r} already committed: cannot abort",
                        detail={"txn_id": txn_id})
                if outcome[0] == "aborted":  # duplicate abort: idempotent
                    return {"txn_id": txn_id, "aborted": True, "duplicate": True}
                return {"txn_id": txn_id, "aborted": False, "expired": True}
            txn = self._staged.pop(txn_id, None)
            if txn is None:
                return {"txn_id": txn_id, "aborted": False}
            self._provider.discard_stage(txn_id)
            self._finish_txn(txn_id, "aborted", {"dataset": txn.dataset})
            self.txn_aborts += 1
        return {"txn_id": txn_id, "aborted": True}

    def do_put_impl(self, descriptor, schema, batches) -> dict:
        if descriptor.path is None and descriptor.command is not None:
            cmd = descriptor.parsed_command()
            if isinstance(cmd, StagedPutCommand):
                if cmd.phase != "stage":
                    raise FlightInvalidArgument(
                        f"DoPut takes the stage leg only; {cmd.phase!r} rides "
                        f"the txn-{cmd.phase} action",
                        detail={"phase": cmd.phase})
                return self._stage_put(cmd, schema, list(batches))
        name = descriptor.path[0] if descriptor.path else descriptor.key
        received = list(batches)
        digest = _content_digest(schema, received) if self.dedup_puts else None
        with self._lock:
            if digest is not None:
                recent = self._recent_puts.setdefault(name, OrderedDict())
                if digest in recent:
                    # retried put of an already-committed payload: idempotent
                    self.put_dedup_hits += 1
                    return {**recent[digest], "deduped": True}
            self._provider.append(name, schema, received)
            self._encoded.pop(name, None)
            self._versions[name] = self._versions.get(name, 0) + 1
            stats = {
                "batches": len(received),
                "rows": sum(b.num_rows for b in received),
                "bytes": sum(b.nbytes() for b in received),
            }
            if digest is not None:
                recent[digest] = stats
                while len(recent) > _PUT_DEDUP_WINDOW:
                    recent.popitem(last=False)
        return stats

    def shutdown(self) -> None:
        self._reaper_stop.set()
        self._provider.close()
        super().shutdown()

    def do_action_impl(self, action: Action) -> list[ActionResult]:
        # telemetry export: spans / histogram snapshots as Arrow IPC bodies
        told = telemetry_action(self, action)
        if told is not None:
            return told
        if action.type == "txn-prepare":
            return [ActionResult(json.dumps(
                self._txn_prepare(parse_txn_body(action.body))).encode())]
        if action.type == "txn-commit":
            return [ActionResult(json.dumps(
                self._txn_commit(parse_txn_body(action.body))).encode())]
        if action.type == "txn-abort":
            return [ActionResult(json.dumps(
                self._txn_abort(parse_txn_body(action.body))).encode())]
        if action.type == "drop":
            name = action.body.decode()
            with self._lock:
                self._provider.drop(name)
                self._encoded.pop(name, None)
                self._recent_puts.pop(name, None)
                self._versions[name] = self._versions.get(name, 0) + 1
            return [ActionResult(b"dropped")]
        if action.type == "list-names":
            with self._lock:
                names = ",".join(self._provider.list())
            return [ActionResult(names.encode())]
        if action.type == "aggregate":
            # filtered aggregation where the data lives — only scalars (or,
            # for grouped plans, per-group result columns) cross the wire
            from ...query.engine import QueryPlan, aggregate  # lazy import cycle

            plan = QueryPlan.deserialize(action.body)
            with self._lock:
                if not self._provider.exists(plan.dataset):
                    raise FlightNotFound(f"no such dataset: {plan.dataset}",
                                         detail={"dataset": plan.dataset})
                batches = self._provider.read_batches(plan.dataset)
                schema = self._provider.schema(plan.dataset)
            res = aggregate(plan, batches, schema)
            if isinstance(res, RecordBatch):  # grouped → columnar JSON
                res = {"group_by": plan.group_by, "columns": res.to_pydict()}
            return [ActionResult(json.dumps(res).encode())]
        if action.type == "local-join":
            # inner equi-join of two datasets living on this server; the
            # result lands as a new local dataset (the per-shard leg of the
            # cluster's shuffled join — key-aligned inputs, local output)
            from ...query.engine import hash_join

            spec = json.loads(action.body.decode())
            on = spec["on"] if isinstance(spec["on"], list) else [spec["on"]]
            with self._lock:
                for name in (spec["left"], spec["right"]):
                    if not self._provider.exists(name):
                        raise FlightNotFound(f"no such dataset: {name}",
                                             detail={"dataset": name})
                lb = self._provider.read_batches(spec["left"])
                rb = self._provider.read_batches(spec["right"])
                ls = self._provider.schema(spec["left"])
                rs = self._provider.schema(spec["right"])
            joined = hash_join(lb, rb, on, ls, rs)
            self.add_dataset(spec["into"], [joined], joined.schema)
            with self._lock:
                self.joins_executed += 1
            return [ActionResult(json.dumps(
                {"dataset": spec["into"], "rows": joined.num_rows}).encode())]
        if action.type == "health":
            return [ActionResult(b"ok")]
        if action.type == "heartbeat":
            # a liveness ping that also tells the caller who answered —
            # cluster probers feed this into their membership registry
            return [ActionResult(json.dumps(
                {"ok": True, "shard": self.shard_id}).encode())]
        if action.type == "server-stats":
            with self._lock:
                stats = {
                    "encode_calls": self.encode_calls,
                    "encode_cache_hits": self.cache_hits,
                    "encode_cache_misses": self.cache_misses,
                    "encode_cache_datasets": len(self._encoded),
                    "rows_served": self.rows_served,
                    "wire_codec": self.wire_codec,
                    "coalesce": self.coalesce,
                    "queries_executed": self.queries_executed,
                    "query_rows_in": self.query_rows_in,
                    "query_rows_out": self.query_rows_out,
                    "partial_aggs_executed": self.partial_aggs_executed,
                    "joins_executed": self.joins_executed,
                    "put_dedup_hits": self.put_dedup_hits,
                    "staged_txns": len(self._staged),
                    "staged_bytes": sum(t.nbytes for t in self._staged.values()),
                    "txn_commits": self.txn_commits,
                    "txn_aborts": self.txn_aborts,
                    "txn_gc_reaped": self.txn_gc_reaped,
                    "storage": self._provider.stats(),
                    "io": (self._listener.stats()
                           if self._listener is not None else None),
                    "verbs": self.metrics.snapshot(),
                }
            return [ActionResult(json.dumps(stats).encode())]
        if action.type == "stats":
            with self._lock:
                stats = {name: self._provider.info(name)
                         for name in self._provider.list()}
            return [ActionResult(json.dumps(stats).encode())]
        raise FlightError(f"unknown action {action.type!r}")

    def do_exchange_impl(self, descriptor, schema, batch) -> RecordBatch:
        return batch  # echo; scoring services override
