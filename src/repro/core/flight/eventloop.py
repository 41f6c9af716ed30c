"""Event-loop server transport — the C10k core under Flight serving.

``SocketListener`` (transport.py) burns one handler thread per accepted
connection, so concurrent-client scaling is bounded by GIL contention and
thread churn long before the wire saturates (the paper's headline numbers —
~6000 MB/s DoGet at ~95% of link bandwidth — are about *many parallel
streams*, which a thread-per-connection Python server cannot sustain).
``EventLoopListener`` replaces it with the classic selector architecture:

* **one dispatch thread** owns every socket: non-blocking accept, framed
  reads (the incremental parser mirrors ``FrameConnection``'s buffered
  receive — header+metadata accumulate in a small buffer, large bodies are
  ``recv_into``'d straight into ``BufferPool`` slabs), and
  writability-gated sends (queued iovec batches flushed on EPOLLOUT);
* **a small worker pool** runs handler/encode work.  A worker is attached
  to a connection only while it has an RPC in progress; between RPCs the
  connection costs one epoll registration, not a thread.  Server thread
  count is O(worker pool), never O(clients);
* **provably-fast RPCs dispatch inline on the loop thread** (the nginx
  move): when the server's ``inline_ok`` predicate certifies a request as
  non-blocking and cheap — a cache-warm DoGet is pure memoryview queueing —
  it runs right inside the parse loop on an idle connection, skipping the
  worker handoff entirely (two GIL/condvar round-trips per RPC on a busy
  box).  Everything else — DoPut/DoExchange (they read further input),
  cold-cache or user-overridden handlers (arbitrary latency) — still goes
  to the pool;
* **the wire format is untouched**: ``ChannelConnection`` subclasses
  ``FrameConnection`` and overrides only the syscall layer (``_flush`` →
  outbox queue, ``recv_frame`` → parsed inbox), so frame construction —
  ``_frame_parts``, ``send_data_many`` coalescing under ``IOV_MAX`` and the
  byte budget — is inherited verbatim and stays byte-identical.

Flow control, both directions:

* **reads** — when a connection's parsed-but-unconsumed inbox exceeds the
  frame/byte high-water marks (a DoPut flood outrunning its worker), the
  loop drops the socket's read interest; the worker re-arms it when the
  inbox drains below half.  Backpressure lands on the peer's TCP window,
  exactly like the blocked ``recv`` it replaces.
* **writes** — handler sends are non-blocking: iovecs queue on the
  connection's outbox and flush inline while the socket accepts them, with
  EPOLLOUT picking up the remainder.  A sender blocks (that RPC only —
  never the loop, never other connections) once the outbox passes
  ``OUT_HIGH_WATER``, so one stalled reader pins one worker and a bounded
  buffer, not the server.

``receive_ready`` on a channel is answered from the inbox — the event loop
already knows readiness, so the exchange serve loop's flush-before-block
probe costs zero syscalls (it was one ``select`` per batch).
"""
from __future__ import annotations

import json
import os
import selectors
import socket
import threading
import time
from collections import deque
from itertools import islice
from typing import Callable

from ..ipc import parse_metadata
from .errors import FlightError
from .telemetry import HDR_TRACE, LogHistogram, add_stage
from .transport import (
    FRAME,
    FRAME_MAGIC,
    IOV_MAX,
    KIND_CTRL,
    KIND_DATA,
    RECV_CHUNK,
    FrameConnection,
)

# Flow-control water marks.  Resume points are half the limit so a
# connection hovering at the boundary doesn't thrash interest changes.
OUT_HIGH_WATER = 4 << 20   # queued unsent bytes before a sending RPC blocks
INBOX_MAX_FRAMES = 256     # parsed frames awaiting a worker before reads pause
INBOX_MAX_BYTES = 8 << 20

# Deferred-output batching: sends below this stay queued until the RPC
# reaches a flush point (handler returns, or blocks waiting for input), so
# a small response — ctrl ok + schema + a few batches + eos — leaves in ONE
# sendmsg / one peer wakeup instead of one per send_* call.  Wire bytes are
# identical; only the syscall grouping changes.  Correctness hinges on the
# flush points covering every wait: `_drain` flushes before detaching and
# `recv_frame` flushes before blocking, so the peer always holds everything
# it is owed before the server waits on it.
FLUSH_SMALL = 64 << 10

_READ = selectors.EVENT_READ
_WRITE = selectors.EVENT_WRITE


def default_workers() -> int:
    """Half the cores (the paper's serving sweet spot), floor 2, cap 8."""
    return max(2, min(8, (os.cpu_count() or 2) // 2 or 1))


class ChannelConnection(FrameConnection):
    """A ``FrameConnection`` whose socket belongs to the event loop.

    Handler code keeps the exact ``FrameConnection`` surface it already
    uses (``send_ctrl`` / ``send_data`` / ``send_data_many`` /
    ``recv_frame`` / ``receive_ready`` / ``close``) but never performs a
    blocking socket operation: frames arrive pre-parsed in ``_inbox`` (fed
    by the loop thread) and sends are queued iovecs flushed non-blocking
    inline and on EPOLLOUT.
    """

    def __init__(self, sock: socket.socket, listener: "EventLoopListener"):
        super().__init__(sock)
        sock.setblocking(False)
        self._listener = listener
        self.fd = sock.fileno()
        # loop-side incremental frame parser (loop thread only)
        self._phase = 0  # 0 = header, 1 = metadata, 2 = body
        self._acc = bytearray()  # header+meta accumulation (and body over-read)
        self._acc_pos = 0
        self._kind = 0
        self._meta_len = 0
        self._body_len = 0
        self._meta_raw = b""
        self._body = None
        self._body_filled = 0
        # worker-facing receive queue
        self._in_cv = threading.Condition()
        # (kind, meta_raw bytes, Buffer | None, arrival perf_counter or 0.0)
        self._inbox: deque = deque()
        self._inbox_bytes = 0
        self.last_queue_wait_s = 0.0  # inbox dwell of the last popped frame
        self._submit_t = 0.0          # when this channel was last scheduled
        self._active = False   # a pool worker is draining this channel
        self._paused = False   # read interest dropped (inbox over high water)
        # worker-facing send queue
        self._out_cv = threading.Condition()
        self._outq: deque = deque()  # memoryviews in frame order
        self._out_bytes = 0
        self._want_write = False
        self.closed = False
        self._fd_closed = False
        self._events = _READ  # current selector interest (loop thread only)

    # ------------------------------------------------------------- send --
    def _flush(self, parts: list, total: int) -> None:
        """Queue one frame group and flush as far as the socket allows.

        Called by the inherited ``send_ctrl``/``send_data``/
        ``send_data_many`` — frame construction and coalescing upstream of
        this point are ``FrameConnection``'s, byte for byte."""
        with self._out_cv:
            if self.closed:
                raise ConnectionError("connection closed")
            self._outq.extend(parts)
            self._out_bytes += total
            self.bytes_sent += total
            # small outputs stay queued until a flush point; bulk streams
            # pump inline as soon as a syscall's worth has accumulated
            if self._out_bytes >= FLUSH_SMALL:
                self._pump_or_arm_locked()
            # writability-gated backpressure: a peer slower than we produce
            # blocks this RPC's worker, never the loop or other connections.
            # The loop thread itself (inline RPCs) must never park here — it
            # is the thread that drains the outbox, so waiting would be a
            # self-deadlock.  Inline sends queue past the mark instead;
            # cached DoGet streams queue memoryviews over the encode-once
            # cache, so the overshoot is frame headers, not data copies.
            if threading.get_ident() == self._listener._loop_ident:
                return
            if self._out_bytes > OUT_HIGH_WATER and not self.closed:
                # backpressure stall: the peer is slower than we produce —
                # measured only when actually waiting, and attributed to the
                # active span (if any) so slow-consumer time is attributable
                t0 = time.perf_counter()
                while self._out_bytes > OUT_HIGH_WATER and not self.closed:
                    self._out_cv.wait(0.1)
                stall = time.perf_counter() - t0
                self._listener.stall_seconds += stall
                self._listener.hist_stall.observe(stall)
                add_stage("stall", stall)
            if self.closed:
                raise ConnectionError("connection closed")

    def flush_output(self) -> None:
        """Push any deferred output to the wire (or arm EPOLLOUT).

        The RPC-boundary flush: called when a handler finishes or is about
        to block waiting on the peer."""
        if not self._outq:
            return
        with self._out_cv:
            if self.closed or not self._outq:
                return
            self._pump_or_arm_locked()

    def _pump_or_arm_locked(self) -> None:
        if not self._want_write:
            if not self._pump_out_locked():
                self._want_write = True
                self._listener.write_arms += 1
                self._listener._post("write", self)

    def _pump_out_locked(self) -> bool:
        """Non-blocking drain of the outbox; True when fully flushed.

        Caller holds ``_out_cv``.  Takes up to ``IOV_MAX`` iovecs per
        ``sendmsg`` and resumes after short writes, like
        ``_sendall_vectored`` — just without ever blocking."""
        while self._outq:
            window = list(islice(self._outq, 0, IOV_MAX))
            try:
                sent = self.sock.sendmsg(window)
            except BlockingIOError:
                return False
            except OSError as e:
                self.closed = True
                self._out_cv.notify_all()
                self._listener._post("close", self)
                raise ConnectionError(f"send failed: {e}") from e
            self.sendmsg_calls += 1
            self._out_bytes -= sent
            while sent:
                head = self._outq[0]
                if sent >= len(head):
                    sent -= len(head)
                    self._outq.popleft()
                else:
                    self._outq[0] = head[sent:]
                    sent = 0
            self._out_cv.notify_all()  # senders blocked on the high-water mark
        return True

    # ------------------------------------------------------------- recv --
    def receive_ready(self) -> bool:
        """Readiness from the loop's last events — zero syscalls (the
        thread-mode path paid one ``select`` per probe)."""
        with self._in_cv:
            return bool(self._inbox) or self.closed

    def recv_frame(self):
        if not self._inbox:
            # about to wait on the peer: everything we owe it goes out
            # first (mid-RPC reads — DoPut / exchange acks — depend on it)
            self.flush_output()
        with self._in_cv:
            while not self._inbox:
                if self.closed:
                    raise ConnectionError("peer closed")
                self._in_cv.wait(0.1)
            kind, meta_raw, body, t_arr = self._inbox.popleft()
            self._inbox_bytes -= FRAME.size + len(meta_raw) + (
                body.nbytes if body is not None else 0)
            if t_arr:
                # inbox dwell: parsed-to-consumed (the accept-queue number)
                qw = time.perf_counter() - t_arr
                self.last_queue_wait_s = qw
                self._listener.hist_queue_wait.observe(qw)
            else:
                self.last_queue_wait_s = 0.0
            if self._paused and (len(self._inbox) <= INBOX_MAX_FRAMES // 2
                                 and self._inbox_bytes <= INBOX_MAX_BYTES // 2):
                self._paused = False
                self._listener._post("resume", self)
        self.bytes_received += FRAME.size + len(meta_raw) + (
            body.nbytes if body is not None else 0)
        meta = parse_metadata(meta_raw) if kind == KIND_DATA else json.loads(meta_raw)
        return kind, meta, body

    def close(self) -> None:
        """Thread-safe teardown request; the loop owns the actual fd."""
        with self._out_cv:
            if self._outq and not self._want_write:
                try:  # best-effort: a deferred error reply still gets out
                    self._pump_out_locked()
                except ConnectionError:
                    pass
            self.closed = True
            self._out_cv.notify_all()
        with self._in_cv:
            self._in_cv.notify_all()
        self._listener._post("close", self)

    # ---------------------------------------------- loop-thread parsing --
    def _loop_readable(self) -> bool:
        """Drain the socket (bounded per event) into parsed frames.

        Returns False on EOF / error / protocol violation — the loop then
        closes the connection.  Large bodies bypass the accumulation buffer
        and ``recv_into`` straight into their pooled slab (the zero-copy
        receive path of ``FrameConnection``, preserved)."""
        budget = 16
        while budget > 0 and not self._paused:
            budget -= 1
            if self._phase == 2 and self._acc_pos >= len(self._acc):
                view = memoryview(self._body.data)[self._body_filled:]
                try:
                    n = self.sock.recv_into(view, len(view))
                except BlockingIOError:
                    return True
                except OSError:
                    return False
                self.recv_calls += 1
                if n == 0:
                    return False
                self._body_filled += n
                if self._body_filled == self._body_len:
                    self._complete_frame()
                continue
            try:
                chunk = self.sock.recv(RECV_CHUNK)
            except BlockingIOError:
                return True
            except OSError:
                return False
            self.recv_calls += 1
            if not chunk:
                return False
            if self._acc_pos and self._acc_pos == len(self._acc):
                self._acc.clear()
                self._acc_pos = 0
            self._acc += chunk
            if not self._parse_acc():
                return False
        return True

    def _parse_acc(self) -> bool:
        """Consume complete header/meta/body spans from the accumulation
        buffer; False on bad frame magic (kill the connection)."""
        while True:
            avail = len(self._acc) - self._acc_pos
            if self._phase == 0:
                if avail < FRAME.size:
                    return True
                magic, kind, meta_len, body_len = FRAME.unpack_from(
                    self._acc, self._acc_pos)
                if magic != FRAME_MAGIC:
                    return False
                self._acc_pos += FRAME.size
                self._kind, self._meta_len, self._body_len = kind, meta_len, body_len
                self._phase = 1
            elif self._phase == 1:
                if avail < self._meta_len:
                    return True
                self._meta_raw = bytes(
                    self._acc[self._acc_pos:self._acc_pos + self._meta_len])
                self._acc_pos += self._meta_len
                if self._body_len:
                    self._body = self.pool.acquire(self._body_len)
                    self._body_filled = 0
                    self._phase = 2
                else:
                    self._body = None
                    self._complete_frame()
            else:
                if not avail:
                    return True
                take = min(avail, self._body_len - self._body_filled)
                memoryview(self._body.data)[
                    self._body_filled:self._body_filled + take
                ] = memoryview(self._acc)[self._acc_pos:self._acc_pos + take]
                self._acc_pos += take
                self._body_filled += take
                if self._body_filled < self._body_len:
                    return True
                self._complete_frame()
            if self._acc_pos == len(self._acc):
                self._acc.clear()
                self._acc_pos = 0

    def _complete_frame(self) -> None:
        self._listener.frames_parsed += 1
        frame = (self._kind, self._meta_raw, self._body)
        self._body = None
        self._meta_raw = b""
        self._phase = 0
        # fast path: an RPC-opening control frame on an idle connection
        # (no worker attached, nothing queued ahead of it) runs right here
        # on the loop thread when its verb can't block on further input.
        # `_active`/`_inbox` are safe to read lock-free: only this thread
        # sets `_active` True, and a worker that set it False has already
        # detached for good.
        if (frame[0] == KIND_CTRL and frame[2] is None and not self._active
                and not self._inbox
                and self._listener._try_inline(self, frame[1])):
            return
        # arrival stamp: queue-wait = pop time minus this (0.0 = untimed)
        t_arr = time.perf_counter() if self._listener._telemetry else 0.0
        with self._in_cv:
            self._inbox.append((frame[0], frame[1], frame[2], t_arr))
            self._inbox_bytes += FRAME.size + len(frame[1]) + (
                frame[2].nbytes if frame[2] is not None else 0)
            if (len(self._inbox) > INBOX_MAX_FRAMES
                    or self._inbox_bytes > INBOX_MAX_BYTES):
                self._paused = True  # interest applied by the loop after this
            schedule = not self._active
            if schedule:
                self._active = True
            self._in_cv.notify_all()
        if schedule:
            self._listener.submits += 1
            self._listener._submit(self)


class EventLoopListener:
    """Selector dispatch thread + worker pool (the server side).

    ``rpc`` is called as ``rpc(conn, kind, req)`` for each RPC-opening
    frame — ``FlightServerBase._dispatch_rpc``.  API-compatible with
    ``SocketListener``: ``start()`` / ``stop()`` / ``.host`` / ``.port``.
    """

    def __init__(self, rpc: Callable, host: str = "127.0.0.1", port: int = 0,
                 workers: int | None = None,
                 inline_ok: Callable[[dict], bool] | None = None,
                 telemetry: bool = True):
        self._rpc = rpc
        # per-frame/RPC clock reads cost ~50ns each; telemetry=False skips
        # them entirely (histograms stay allocated so scrapes always work)
        self._telemetry = telemetry
        # server-supplied certificate that a request is safe to run on the
        # loop thread: never reads another frame, never blocks, cheap
        self._inline_ok = inline_ok
        self._workers = workers or default_workers()
        self._lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._lsock.bind((host, port))
        self._lsock.listen(1024)
        self._lsock.setblocking(False)
        self.host, self.port = self._lsock.getsockname()
        self._sel = selectors.DefaultSelector()
        self._wake_r, self._wake_w = socket.socketpair()
        self._wake_r.setblocking(False)
        self._wake_w.setblocking(False)
        self._cmds: deque = deque()  # (op, channel) from worker threads
        self._conns: dict[int, ChannelConnection] = {}
        # lean worker pool: a shared runnable-channel deque + one Condition.
        # An RPC activation is one append+notify — no Future / work-item /
        # executor-queue allocation on the per-request hot path.
        self._run_cv = threading.Condition()
        self._runnable: deque = deque()
        self._pool_stop = False
        self._pool = [
            threading.Thread(target=self._worker, daemon=True,
                             name=f"flight-io-{i}")
            for i in range(self._workers)
        ]
        self._thread: threading.Thread | None = None
        self._loop_ident = -1  # set by the loop thread before serving
        self._stopping = False
        self.connections_accepted = 0
        # diagnostics (approximate: bumped without dedicated locks)
        self.loop_wakeups = 0
        self.write_arms = 0
        self.submits = 0
        self.inline_rpcs = 0
        self.frames_parsed = 0
        # io-layer latency histograms (exported by ``server-metrics``):
        # where a request's wall time goes *before/around* the handler
        self.hist_queue_wait = LogHistogram()      # inbox dwell (accept queue)
        self.hist_inline = LogHistogram()          # inline fast-path RPC time
        self.hist_dispatch = LogHistogram()        # submit -> worker pickup
        self.hist_depth = LogHistogram(scale=1)    # runnable-queue depth
        self.hist_stall = LogHistogram()           # backpressure stall time
        self.stall_seconds = 0.0
        # structured handler-crash records (replaces stderr tracebacks)
        self.handler_errors = 0
        self.recent_errors: deque = deque(maxlen=64)

    # ------------------------------------------------------- lifecycle --
    def start(self) -> "EventLoopListener":
        self._sel.register(self._lsock, _READ, None)
        self._sel.register(self._wake_r, _READ, None)
        for w in self._pool:
            w.start()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="flight-eventloop")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._post("stop", None)
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        with self._run_cv:
            self._pool_stop = True
            self._run_cv.notify_all()
        for w in self._pool:
            w.join(timeout=1.0)

    def open_connections(self) -> int:
        return len(self._conns)

    def drop_connections(self) -> int:
        """Sever every live connection (fault injection / admin drain).

        Runs on the loop thread — selector mutation mid-``select`` is not
        thread-safe — so this only *posts* the drop; returns the number of
        connections that were live when asked."""
        n = len(self._conns)
        self._post("dropconns", None)
        return n

    def stats(self) -> dict:
        return {
            "io_mode": "eventloop",
            "open_connections": len(self._conns),
            # every fd this listener owns: conns + listening socket + the
            # wakeup socketpair — the c10k headroom number an operator wants
            "open_fds": len(self._conns) + 3,
            "worker_queue_depth": len(self._runnable),
            "workers": self._workers,
            "accepted": self.connections_accepted,
            "loop_wakeups": self.loop_wakeups,
            "write_arms": self.write_arms,
            "submits": self.submits,
            "inline_rpcs": self.inline_rpcs,
            "frames_parsed": self.frames_parsed,
            "stall_seconds": round(self.stall_seconds, 6),
            "handler_errors": self.handler_errors,
            "recent_errors": list(self.recent_errors),
        }

    def histograms(self) -> dict:
        """IO-layer histograms for the ``server-metrics`` Arrow export."""
        return {
            "queue_wait": self.hist_queue_wait,
            "inline_rpc": self.hist_inline,
            "dispatch": self.hist_dispatch,
            "worker_queue_depth": self.hist_depth,
            "backpressure_stall": self.hist_stall,
        }

    def _record_error(self, ch: ChannelConnection, req: dict | None,
                      exc: Exception) -> None:
        """Structured record of a handler crash (was a stderr traceback):
        connection fd, verb, trace id when the request carried one."""
        self.handler_errors += 1
        rec = {
            "fd": ch.fd,
            "verb": (req or {}).get("method", "?"),
            "error": f"{type(exc).__name__}: {exc}",
        }
        trace = (((req or {}).get("options") or {}).get("headers")
                 or {}).get(HDR_TRACE)
        if trace:
            rec["trace_id"] = trace
        self.recent_errors.append(rec)

    # --------------------------------------------------- worker plumbing --
    def _post(self, op: str, ch: ChannelConnection | None) -> None:
        """Hand a selector mutation to the loop thread (selectors are not
        thread-safe to modify mid-``select``)."""
        self._cmds.append((op, ch))
        try:
            self._wake_w.send(b"x")
        except (BlockingIOError, OSError):
            pass  # wakeup pipe full: the loop is already awake

    def _submit(self, ch: ChannelConnection) -> None:
        if self._telemetry:
            ch._submit_t = time.perf_counter()
        with self._run_cv:
            self._runnable.append(ch)
            if self._telemetry:
                self.hist_depth.observe(len(self._runnable))
            self._run_cv.notify()

    def _try_inline(self, ch: ChannelConnection, meta_raw: bytes) -> bool:
        """Run a certified-fast RPC on the loop thread; False defers to the
        pool.  Mirrors ``_drain``'s error containment: any failure closes
        this channel only — the loop must survive arbitrary handler bugs."""
        if self._inline_ok is None:
            return False
        try:
            req = json.loads(meta_raw)
        except ValueError:
            return False  # let the worker path produce the protocol error
        try:
            if not self._inline_ok(req):
                return False
        except Exception:
            return False  # a broken predicate degrades to the worker path
        ch.bytes_received += FRAME.size + len(meta_raw)
        ch.last_queue_wait_s = 0.0  # the opening frame never sat in the inbox
        self.inline_rpcs += 1
        t0 = time.perf_counter() if self._telemetry else 0.0
        try:
            self._rpc(ch, KIND_CTRL, req)
            ch.flush_output()
        except FlightError as e:
            try:
                ch.send_ctrl(e.to_wire())
            except (ConnectionError, OSError):
                pass
            ch.close()
        except (ConnectionError, OSError):
            ch.close()
        except Exception as e:
            self._record_error(ch, req, e)
            ch.close()
        if self._telemetry:
            self.hist_inline.observe(time.perf_counter() - t0)
        return True

    def _worker(self) -> None:
        while True:
            with self._run_cv:
                while not self._runnable:
                    if self._pool_stop:
                        return
                    self._run_cv.wait()
                ch = self._runnable.popleft()
            if self._telemetry and ch._submit_t:
                self.hist_dispatch.observe(time.perf_counter() - ch._submit_t)
                ch._submit_t = 0.0
            try:
                self._drain(ch)
            except Exception:
                # handler bug: _drain already closed the channel and recorded
                # a structured error; the worker itself must survive
                pass
            ch = None  # no stale channel ref while parked on the condvar

    def _drain(self, ch: ChannelConnection) -> None:
        """Worker entry: serve RPCs off this channel until its inbox runs
        dry, then detach (the loop re-attaches a worker on the next frame)."""
        while True:
            if not ch._inbox:
                try:
                    ch.flush_output()  # responses out before we detach
                except ConnectionError:
                    pass
            with ch._in_cv:
                if not ch._inbox:
                    ch._active = False
                    return
            try:
                kind, req, _ = ch.recv_frame()
            except (ConnectionError, OSError):
                with ch._in_cv:
                    ch._active = False
                return
            try:
                self._rpc(ch, kind, req)
            except FlightError as e:
                # protocol violation (e.g. data frame opening an RPC):
                # report if the peer can still hear, then drop the channel
                try:
                    ch.send_ctrl(e.to_wire())
                except (ConnectionError, OSError):
                    pass
                ch.close()
                with ch._in_cv:
                    ch._active = False
                return
            except (ConnectionError, OSError):
                ch.close()
                with ch._in_cv:
                    ch._active = False
                return
            except Exception as e:
                # handler bug: contain it to this connection — the loop and
                # the worker pool must survive arbitrary handler failures
                self._record_error(ch, req if isinstance(req, dict) else None, e)
                ch.close()
                with ch._in_cv:
                    ch._active = False
                raise

    # ------------------------------------------------------ loop thread --
    def _loop(self) -> None:
        self._loop_ident = threading.get_ident()
        ch = key = None
        while not self._stopping:
            self.loop_wakeups += 1
            for key, mask in self._sel.select(timeout=1.0):
                ch = key.data
                if ch is None:
                    if key.fileobj is self._wake_r:
                        try:
                            while self._wake_r.recv(4096):
                                pass
                        except (BlockingIOError, OSError):
                            pass
                    else:
                        self._accept_ready()
                    continue
                try:
                    if mask & _READ and not ch._loop_readable():
                        self._close_channel(ch)
                        continue
                    if mask & _WRITE:
                        self._loop_writable(ch)
                    self._apply_interest(ch)
                except Exception:
                    self._close_channel(ch)
            self._run_cmds()
            # drop channel refs before blocking in select, so a closed
            # channel's BufferPool frees as soon as its last frame is consumed
            ch = key = None
        # shutdown: every channel closes (waking any blocked worker)
        for ch in list(self._conns.values()):
            self._close_channel(ch)
        for sock in (self._lsock, self._wake_r, self._wake_w):
            try:
                sock.close()
            except OSError:
                pass
        self._sel.close()

    def _accept_ready(self) -> None:
        while True:
            try:
                sock, _ = self._lsock.accept()
            except (BlockingIOError, OSError):
                return
            ch = ChannelConnection(sock, self)
            self._conns[ch.fd] = ch
            self._sel.register(sock, _READ, ch)
            self.connections_accepted += 1

    def _loop_writable(self, ch: ChannelConnection) -> None:
        with ch._out_cv:
            try:
                if ch._pump_out_locked():
                    ch._want_write = False
            except ConnectionError:
                pass  # _pump_out_locked already posted the close

    def _apply_interest(self, ch: ChannelConnection) -> None:
        if ch._fd_closed:
            return
        events = (0 if ch._paused else _READ) | (_WRITE if ch._want_write else 0)
        if events == ch._events:
            return
        try:
            if ch._events and events:
                self._sel.modify(ch.sock, events, ch)
            elif ch._events:
                self._sel.unregister(ch.sock)
            else:
                self._sel.register(ch.sock, events, ch)
        except (KeyError, ValueError, OSError):
            return
        ch._events = events

    def _close_channel(self, ch: ChannelConnection) -> None:
        # never block the loop on a lock a worker is holding mid-sendmsg
        # (GIL priority inversion): re-post and serve other channels instead
        if not ch._out_cv.acquire(blocking=False):
            self._post("close", ch)
            return
        try:
            if ch._fd_closed:
                return
            ch._fd_closed = True
            ch.closed = True
            try:
                # close first: the kernel drops the epoll registration with
                # the fd, and selectors' unregister tolerates the dead fd —
                # one epoll_ctl saved per connection
                ch.sock.close()
            except OSError:
                pass
            if ch._events:
                try:
                    # by fd, not socket object: the closed socket's
                    # fileno() is -1, which would force a linear key scan
                    self._sel.unregister(ch.fd)
                except (KeyError, ValueError, OSError):
                    pass
                ch._events = 0
            ch._outq.clear()
            ch._out_bytes = 0
            ch._out_cv.notify_all()
        finally:
            ch._out_cv.release()
        with ch._in_cv:
            ch._in_cv.notify_all()
        self._conns.pop(ch.fd, None)

    def _run_cmds(self) -> None:
        while True:
            try:
                op, ch = self._cmds.popleft()
            except IndexError:
                return
            if op == "stop":
                self._stopping = True
            elif op == "dropconns":
                # fault injection: sever every live connection (listener
                # stays up, so clients see a reset — not a refused dial)
                for c in list(self._conns.values()):
                    self._close_channel(c)
            elif ch is None or ch._fd_closed:
                continue
            elif op == "close":
                self._close_channel(ch)
            else:  # "write" arm / "resume" reads: recompute interest
                self._apply_interest(ch)
