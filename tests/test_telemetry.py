"""Telemetry plane: tracing, histograms, Arrow export, cluster scrape.

The acceptance scenario lives in ``TestClusterTraceTCP``: one traced
replicated-cluster query must stitch client + head + shard spans under a
single trace id, each with non-zero stage timings.
"""
import json
import time

import pytest

from repro.core import RecordBatch
from repro.core.flight import (
    Action,
    FlightClient,
    FlightClusterClient,
    FlightClusterServer,
    FlightInvalidArgument,
    FlightNotFound,
    InMemoryFlightServer,
    LogHistogram,
    ServerConfig,
    Tracer,
    TraceContext,
    batch_to_rows,
    batch_to_spans,
    decode_telemetry_batch,
)
from repro.core.flight.protocol import FlightDescriptor, Ticket
from repro.core.flight.telemetry import (
    HDR_PARENT,
    HDR_SPAN,
    HDR_TRACE,
    MAX_BUCKETS,
    ServerTelemetry,
    Span,
    SpanRecorder,
    encode_telemetry_batch,
    merge_telemetry_batches,
    metrics_rows,
    metrics_to_batch,
    spans_to_batch,
)
from repro.query import QueryPlan, col


def seq_batches(n=6, rows=100):
    return [
        RecordBatch.from_pydict({
            "k": list(range(i * rows, (i + 1) * rows)),
            "v": [float(j) * 0.5 for j in range(i * rows, (i + 1) * rows)],
        })
        for i in range(n)
    ]


# --------------------------------------------------------------------------
# log2 histograms
# --------------------------------------------------------------------------


class TestLogHistogram:
    def test_bucketing_and_percentiles(self):
        h = LogHistogram()  # scale=1e6: seconds in by microsecond bit-length
        for _ in range(99):
            h.observe(100e-6)   # ~100 µs -> bucket 7 (upper 128 µs)
        h.observe(50e-3)        # one 50 ms outlier
        assert h.count == 100
        assert h.percentile(0.50) == pytest.approx(128e-6)
        assert h.percentile(0.99) == pytest.approx(128e-6)
        assert h.percentile(1.0) == pytest.approx(h.bucket_upper(16))
        snap = h.snapshot()
        assert snap["count"] == 100
        assert snap["sum"] == pytest.approx(99 * 100e-6 + 50e-3, rel=1e-3)
        assert sum(snap["buckets"].values()) == 100

    def test_overflow_clamps_to_last_bucket(self):
        h = LogHistogram()
        h.observe(1e7)  # ~116 days: beyond the 2**39 µs ceiling
        assert h.counts[MAX_BUCKETS - 1] == 1
        assert h.percentile(0.5) == h.bucket_upper(MAX_BUCKETS - 1)

    def test_count_scale_buckets_raw_values(self):
        h = LogHistogram(scale=1)  # queue depths: raw integer domain
        for d in (1, 2, 3, 900):
            h.observe(d)
        assert h.percentile(0.5) == 4.0   # depth 3 -> bucket 2, upper 4
        assert h.percentile(1.0) == 1024.0

    def test_merge_sums_counts(self):
        a, b = LogHistogram(), LogHistogram()
        a.observe(1e-3)
        b.observe(1e-3)
        b.observe(2.0)
        a.merge(b)
        assert a.count == 3
        assert a.total == pytest.approx(2e-3 + 2.0)

    def test_empty_percentile_is_zero(self):
        assert LogHistogram().percentile(0.99) == 0.0


# --------------------------------------------------------------------------
# trace context + spans
# --------------------------------------------------------------------------


class TestTraceContext:
    def test_header_round_trip(self):
        ctx = TraceContext.new().child()
        back = TraceContext.from_headers(ctx.to_headers())
        assert back == ctx
        assert back.parent_id is not None

    def test_child_links_parent(self):
        root = TraceContext.new()
        child = root.child()
        assert child.trace_id == root.trace_id
        assert child.parent_id == root.span_id
        assert child.span_id != root.span_id

    def test_absent_or_partial_headers_are_untraced(self):
        assert TraceContext.from_headers(None) is None
        assert TraceContext.from_headers({}) is None
        assert TraceContext.from_headers({HDR_TRACE: "t"}) is None
        assert TraceContext.from_headers({HDR_SPAN: "s"}) is None
        full = {HDR_TRACE: "t", HDR_SPAN: "s", HDR_PARENT: ""}
        assert TraceContext.from_headers(full) == TraceContext("t", "s", None)


class TestSpanExport:
    def test_span_batch_round_trip(self):
        spans = [
            Span("t1", "s1", None, "read", service="client",
                 duration_s=0.5, stages={"handler": 0.4}),
            Span("t1", "s2", "s1", "DoGet", service="srv", shard=2,
                 status="unavailable"),
        ]
        rows = batch_to_spans(decode_telemetry_batch(
            encode_telemetry_batch(spans_to_batch(spans))))
        assert [r["span_id"] for r in rows] == ["s1", "s2"]
        assert rows[0]["parent_id"] == ""
        assert rows[0]["stages"] == {"handler": 0.4}
        assert rows[1]["shard"] == 2
        assert rows[1]["status"] == "unavailable"

    def test_empty_span_batch_round_trip(self):
        batch = decode_telemetry_batch(
            encode_telemetry_batch(spans_to_batch([])))
        assert batch.num_rows == 0
        assert batch_to_spans(batch) == []

    def test_metrics_batch_round_trip(self):
        h = LogHistogram()
        h.observe(1e-3)
        rows = metrics_rows("verb", {"DoGet": h})
        batch = metrics_to_batch(rows, shard=3, epoch=7)
        back = batch_to_rows(batch)
        assert back[0]["scope"] == "verb"
        assert back[0]["name"] == "DoGet"
        assert back[0]["count"] == 1
        assert back[0]["shard"] == 3 and back[0]["epoch"] == 7
        assert json.loads(back[0]["buckets"])  # non-empty bucket map

    def test_merge_stamps_shard_and_epoch(self):
        h = LogHistogram()
        h.observe(1e-3)
        part = metrics_to_batch(metrics_rows("io", {"queue_wait": h}))
        merged = merge_telemetry_batches([(0, part), (1, part)], epoch=9)
        rows = batch_to_rows(merged)
        assert [r["shard"] for r in rows] == [0, 1]
        assert all(r["epoch"] == 9 for r in rows)


class TestServerTelemetry:
    def test_mode_validation(self):
        with pytest.raises(ValueError):
            ServerTelemetry("verbose")
        assert not ServerTelemetry("off").metrics_enabled
        assert ServerTelemetry("metrics").metrics_enabled
        assert not ServerTelemetry("metrics").trace_enabled
        assert ServerTelemetry("full").trace_enabled

    def test_explicit_span_requires_parent(self):
        tel = ServerTelemetry("full", service="s")
        with tel.span("orphan") as sp:   # no active trace: no-op
            assert sp is None
        assert len(tel.spans) == 0
        with tel.span("child", parent=TraceContext.new()) as sp:
            assert sp is not None
        assert len(tel.spans) == 1

    def test_span_error_status_is_wire_code(self):
        tel = ServerTelemetry("full", service="s")
        with pytest.raises(FlightNotFound):
            with tel.span("lookup", parent=TraceContext.new()):
                raise FlightNotFound("nope")
        [span] = tel.spans.snapshot()
        assert span.status == "not_found"


# --------------------------------------------------------------------------
# one server over TCP: middleware spans, histograms, error codes, export
# --------------------------------------------------------------------------


class TestServerTelemetryTCP:
    def _serve(self, telemetry="full"):
        srv = InMemoryFlightServer(config=ServerConfig(telemetry=telemetry))
        srv.add_dataset("t", seq_batches(2))
        srv.serve_tcp()
        return srv, FlightClient(f"tcp://127.0.0.1:{srv.port}")

    def test_traced_read_records_stitched_spans_with_stages(self):
        srv, c = self._serve()
        try:
            tracer = Tracer()
            with tracer.trace("read") as ctx:
                info = c.get_flight_info(FlightDescriptor.for_path("t"))
                rows = sum(b.num_rows
                           for ep in info.endpoints for b in c.do_get(ep.ticket))
            assert rows == 200
            [client_span] = tracer.spans.snapshot()
            assert client_span.trace_id == ctx.trace_id
            spans = srv.telemetry.spans.snapshot()
            assert {s.name for s in spans} >= {"GetFlightInfo", "DoGet"}
            for s in spans:
                assert s.trace_id == ctx.trace_id
                assert s.parent_id == ctx.span_id  # direct children of the root
                assert s.duration_s > 0
                assert s.stages.get("handler", 0) > 0
            doget = next(s for s in spans if s.name == "DoGet")
            assert doget.stages.get("flush", 0) > 0  # cache-warm send timed
        finally:
            srv.shutdown()

    def test_untraced_requests_record_no_spans(self):
        srv, c = self._serve()
        try:
            assert len(c.list_flights()) == 1
            assert len(srv.telemetry.spans) == 0
            assert srv.metrics.calls.get("ListFlights") == 1  # metrics still on
        finally:
            srv.shutdown()

    def test_telemetry_off_records_nothing(self):
        srv, c = self._serve(telemetry="off")
        try:
            tracer = Tracer()
            with tracer.trace("read"):
                assert len(c.list_flights()) == 1
            assert len(srv.telemetry.spans) == 0
            assert srv.metrics.latency == {}
        finally:
            srv.shutdown()

    def test_error_counters_break_out_by_flight_code(self):
        srv, c = self._serve()
        try:
            with pytest.raises(FlightNotFound):
                c.get_flight_info(FlightDescriptor.for_path("missing"))
            with pytest.raises(FlightNotFound):
                list(c.do_get(Ticket.for_range("missing", 0, 1)))
            snap = srv.metrics.snapshot()
            assert snap["error_codes"]["GetFlightInfo"] == {"not_found": 1}
            assert snap["error_codes"]["DoGet"] == {"not_found": 1}
            # and the Arrow export carries them as scope="errors" rows
            res = c.do_action(Action("server-metrics", b""))
            rows = batch_to_rows(decode_telemetry_batch(res[0].body))
            errs = {r["name"]: r["count"] for r in rows if r["scope"] == "errors"}
            assert errs["DoGet:not_found"] == 1
        finally:
            srv.shutdown()

    def test_latency_histograms_replace_scalar_sums(self):
        srv, c = self._serve()
        try:
            for _ in range(5):
                assert len(c.list_flights()) == 1
            snap = srv.metrics.snapshot()
            lat = snap["latency"]["ListFlights"]
            assert lat["count"] == 5
            assert lat["p99"] >= lat["p50"] > 0
            assert snap["seconds"]["ListFlights"] > 0  # legacy sum kept
        finally:
            srv.shutdown()

    def test_server_trace_action_exports_and_clears(self):
        srv, c = self._serve()
        try:
            tracer = Tracer()
            with tracer.trace("read"):
                assert len(c.list_flights()) == 1
            res = c.do_action(Action("server-trace", b'{"clear": true}'))
            rows = batch_to_spans(decode_telemetry_batch(res[0].body))
            assert [r["name"] for r in rows] == ["ListFlights"]
            assert len(srv.telemetry.spans) == 0  # clear=true drained it
        finally:
            srv.shutdown()

    def test_server_metrics_exports_io_histograms(self):
        srv, c = self._serve()
        try:
            assert len(c.list_flights()) == 1
            res = c.do_action(Action("server-metrics", b""))
            rows = batch_to_rows(decode_telemetry_batch(res[0].body))
            scopes = {r["scope"] for r in rows}
            assert "verb" in scopes and "io" in scopes
            names = {r["name"] for r in rows if r["scope"] == "io"}
            assert names >= {"queue_wait", "inline_rpc", "dispatch",
                             "worker_queue_depth", "backpressure_stall"}
        finally:
            srv.shutdown()


class TestEventLoopErrorRecords:
    def test_handler_crash_yields_structured_io_error(self):
        class Crashy(InMemoryFlightServer):
            def do_action_impl(self, action):
                if action.type == "boom":
                    raise RuntimeError("kaput")
                return super().do_action_impl(action)

        srv = Crashy()
        srv.serve_tcp()
        try:
            c = FlightClient(f"tcp://127.0.0.1:{srv.port}")
            tracer = Tracer()
            with tracer.trace("crash"), pytest.raises(Exception):
                c.do_action(Action("boom", b""))
            c2 = FlightClient(f"tcp://127.0.0.1:{srv.port}")
            stats = json.loads(
                c2.do_action(Action("server-stats", b""))[0].body)
            io = stats["io"]
            assert io["handler_errors"] == 1
            [rec] = io["recent_errors"]
            assert rec["verb"] == "DoAction"
            assert rec["fd"] > 0
            assert "RuntimeError" in rec["error"]
            assert rec["trace_id"]  # the traced request's id rode along
        finally:
            srv.shutdown()


# --------------------------------------------------------------------------
# cluster: end-to-end stitching + cluster-wide scrape (the acceptance test)
# --------------------------------------------------------------------------


class TestClusterTraceTCP:
    def test_replicated_query_stitches_client_head_shard_spans(self):
        """Acceptance: one traced replicated cluster query end-to-end over
        TCP yields >= 3 spans (client root, head planning, shard execution)
        under a single trace id, every server span with non-zero stages."""
        cl = FlightClusterServer(num_shards=3, replicas=2).serve_tcp()
        try:
            cl.add_dataset("d", seq_batches(6))
            cli = FlightClusterClient(f"tcp://127.0.0.1:{cl.port}")
            tracer = Tracer()
            plan = QueryPlan("d", predicate=col("k") >= 300)
            with tracer.trace("query") as ctx:
                t, _ = cli.query(plan)
            assert t.num_rows == 300
            res = cli.head.do_action(Action("cluster-trace", b""))
            spans = [s for s in batch_to_spans(decode_telemetry_batch(res[0].body))
                     if s["trace_id"] == ctx.trace_id]
            [client_span] = tracer.spans.snapshot()
            assert client_span.trace_id == ctx.trace_id
            head = [s for s in spans if s["name"] == "GetFlightInfo"]
            shard = [s for s in spans if s["name"] == "DoGet"]
            assert len(head) == 1 and head[0]["shard"] == -1
            assert len(shard) >= 2  # one per shard holding a slice
            assert {s["shard"] for s in shard} >= {0, 1}
            # stitched hierarchy: client root -> head planning -> shard
            # execution; 1 (client) + 1 (head) + >=2 (shards) >= 3 spans
            assert head[0]["parent_id"] == ctx.span_id
            for s in shard:
                assert s["parent_id"] == head[0]["span_id"]
            for s in head + shard:
                assert s["duration_s"] > 0
                assert s["stages"].get("handler", 0) > 0
                assert s["stages"].get("queue", 0) > 0
        finally:
            cl.shutdown()

    def test_cluster_metrics_scrape_is_epoch_and_shard_stamped(self):
        cl = FlightClusterServer(num_shards=2, replicas=2).serve_tcp()
        try:
            cl.add_dataset("d", seq_batches(4))
            cli = FlightClusterClient(f"tcp://127.0.0.1:{cl.port}")
            t, _ = cli.read("d")
            assert t.num_rows == 400
            res = cli.head.do_action(Action("cluster-metrics", b""))
            rows = batch_to_rows(decode_telemetry_batch(res[0].body))
            assert rows
            assert {r["shard"] for r in rows} >= {-1, 0, 1}  # head + shards
            assert {r["epoch"] for r in rows} == {cl.membership.epoch}
            verbs = {(r["shard"], r["name"]) for r in rows if r["scope"] == "verb"}
            assert (0, "DoGet") in verbs and (1, "DoGet") in verbs
        finally:
            cl.shutdown()

    def test_2pc_commit_records_shard_subtxn_spans(self):
        cl = FlightClusterServer(num_shards=2, replicas=2).serve_tcp()
        try:
            cli = FlightClusterClient(f"tcp://127.0.0.1:{cl.port}")
            tracer = Tracer()
            with tracer.trace("write") as ctx:
                cli.write("d", seq_batches(4), transactional=True)
            res = cli.head.do_action(Action("cluster-trace", b""))
            spans = [s for s in batch_to_spans(decode_telemetry_batch(res[0].body))
                     if s["trace_id"] == ctx.trace_id]
            txn = [s for s in spans if s["name"].startswith("txn:")]
            assert {s["name"] for s in txn} >= {"txn:txn-prepare",
                                                "txn:txn-commit"}
            # sub-txn spans live on the shards that voted, parented under
            # the head's coordinating span (not the client root)
            head_ids = {s["span_id"] for s in spans if s["shard"] == -1}
            assert all(s["parent_id"] in head_ids for s in txn)
            assert all(s["status"] == "ok" for s in txn)
        finally:
            cl.shutdown()


# --------------------------------------------------------------------------
# server-rooted sampling, interval spans of a scoring request, ring drops
# --------------------------------------------------------------------------

SCORE_CHILDREN = ["flight.read", "serve.decode", "serve.dispatch", "serve.sync",
                  "serve.reply", "flight.reply"]  # in the order they run


def score_once(client, rows):
    """One DoExchange as the benchmark's load generator sends it: schema,
    batch and end of stream back to back."""
    req = RecordBatch.from_pydict({"tokens": rows})
    ex = client.do_exchange_stream(FlightDescriptor.for_path("score"), req.schema)
    ex.write_batch(req)
    ex.done_writing()
    out = list(ex)
    ex.close()
    return out


def scrape_trace(client, **opts):
    """(span rows, ring totals) from ``server-trace``."""
    res = client.do_action(Action("server-trace", json.dumps(opts).encode()))
    return batch_to_spans(decode_telemetry_batch(res[0].body)), json.loads(res[1].body)


@pytest.fixture(scope="module")
def scorer():
    import jax

    from repro.configs import get_smoke_config
    from repro.distributed.sharding import single_device_ctx
    from repro.models.lm import LM
    from repro.serving import LMScoringService

    model = LM(get_smoke_config("internlm2_1_8b"), single_device_ctx())
    params, _ = model.init(jax.random.key(0))
    svc = LMScoringService(model, params, max_seq=32).serve_tcp()
    client = FlightClient(f"tcp://127.0.0.1:{svc.port}")
    score_once(client, [[1, 2, 3]])  # compiles the one program shape
    yield svc, client
    svc.shutdown()


def traced_score(client, sample, caller):
    """Score one prompt under ``sample``, with trace headers if ``caller``;
    returns the spans of that call and the caller's context (or None)."""
    scrape_trace(client, sample=sample, clear=True)
    ctx = None
    if caller:
        tracer = Tracer()
        with tracer.trace("score") as ctx:
            score_once(client, [[5, 6, 7, 8]])
    else:
        score_once(client, [[5, 6, 7, 8]])
    spans, _ = scrape_trace(client, sample="caller", clear=True)
    return [s for s in spans if not s["name"].startswith("DoAction")], ctx


class TestServerRootedSampling:
    @pytest.mark.parametrize("sample", ["caller", "all"])
    def test_header_less_exchange_is_traced_only_under_all(self, scorer, sample):
        _, client = scorer
        spans, _ = traced_score(client, sample, caller=False)
        if sample == "caller":
            assert spans == []
            return
        [rpc] = [s for s in spans if s["name"] == "DoExchange:path:score"]
        assert rpc["parent_id"] == ""  # a root of its own trace
        children = sorted((s for s in spans if s is not rpc), key=lambda s: s["mono_s"])
        assert [s["name"] for s in children] == SCORE_CHILDREN
        assert {s["parent_id"] for s in children} == {rpc["span_id"]}
        assert {s["trace_id"] for s in spans} == {rpc["trace_id"]}
        assert rpc["stages"]["queue"] >= 0  # the opening frame's inbox dwell

    @pytest.mark.parametrize("sample", ["caller", "all"])
    def test_caller_traced_exchange_keeps_the_callers_trace(self, scorer, sample):
        _, client = scorer
        spans, ctx = traced_score(client, sample, caller=True)
        [rpc] = [s for s in spans if s["name"] == "DoExchange:path:score"]
        assert rpc["trace_id"] == ctx.trace_id and rpc["parent_id"] == ctx.span_id
        assert sorted(s["name"] for s in spans if s is not rpc) == sorted(SCORE_CHILDREN)

    @pytest.mark.parametrize("sample", ["caller", "all"])
    def test_children_nest_in_order_inside_the_rpc_span(self, scorer, sample):
        _, client = scorer
        spans, _ = traced_score(client, sample, caller=True)
        [rpc] = [s for s in spans if s["name"] == "DoExchange:path:score"]
        children = sorted((s for s in spans if s is not rpc), key=lambda s: s["mono_s"])
        lo, hi = rpc["mono_s"], rpc["mono_s"] + rpc["duration_s"]
        end = lo
        for s in children:
            assert s["mono_s"] >= end  # siblings follow one another
            end = s["mono_s"] + s["duration_s"]
            assert end <= hi
            assert s["status"] == "ok"

    def test_bad_sampling_is_refused(self, scorer):
        _, client = scorer
        with pytest.raises(FlightInvalidArgument):
            scrape_trace(client, sample="some")
        assert scorer[0].telemetry.sample == "caller"

    def test_span_queue_stage_is_the_wait_and_inbox_dwell_is_not(self):
        """The event loop's ``queue_wait`` histogram times every frame's
        inbox dwell; a DoExchange's end of stream waits there behind its own
        handler.  The span's ``queue`` stage is the opening frame's alone."""
        from repro.serving import ScoringService

        def slow(batch):
            time.sleep(0.04)
            return batch

        srv = ScoringService(slow).serve_tcp()
        try:
            c = FlightClient(f"tcp://127.0.0.1:{srv.port}")
            scrape_trace(c, sample="all", clear=True)
            before = srv._listener.histograms()["queue_wait"].snapshot()
            for _ in range(10):
                score_once(c, [[1, 2]])
            after = srv._listener.histograms()["queue_wait"].snapshot()
            spans, _ = scrape_trace(c, sample="caller", clear=True)
        finally:
            srv.shutdown()
        queue = [s["stages"]["queue"] for s in spans if s["name"] == "DoExchange:path:score"]
        assert len(queue) == 10
        dwell = (after["sum"] - before["sum"]) / (after["count"] - before["count"])
        assert sum(queue) / len(queue) < 0.005
        assert dwell >= 0.005

    def test_ring_counts_drops_and_the_scrape_reports_them(self):
        ring = SpanRecorder(maxlen=4)
        for i in range(6):
            ring.record(Span("t", f"s{i}", None, "x"))
        assert (ring.recorded, ring.dropped) == (6, 2)
        assert [s.span_id for s in ring.snapshot()] == ["s2", "s3", "s4", "s5"]

        srv = InMemoryFlightServer()
        srv.telemetry.spans = SpanRecorder(maxlen=2)
        srv.serve_tcp()
        try:
            c = FlightClient(f"tcp://127.0.0.1:{srv.port}")
            with Tracer().trace("list"):
                for _ in range(3):
                    c.list_flights()
            spans, totals = scrape_trace(c, clear=True)
        finally:
            srv.shutdown()
        assert len(spans) == 2
        assert totals == {"sample": "caller", "recorded": 3, "dropped": 1, "returned": 2}

    def test_mono_s_column_round_trips(self):
        span = Span("t", "s", None, "x", start_s=1.7e9, mono_s=12.5)
        [row] = batch_to_spans(decode_telemetry_batch(
            encode_telemetry_batch(spans_to_batch([span]))))
        assert row["mono_s"] == 12.5 and row["start_s"] == 1.7e9

    def test_untraced_span_is_one_shared_null_context(self):
        tel = ServerTelemetry("full")
        assert tel.span("a") is tel.span("b")
        with tel.span("a") as sp:
            assert sp is None
        assert len(tel.spans) == 0
