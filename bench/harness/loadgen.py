"""The load generator of an open-loop cell, run as a process of its own.

  python3 bench/harness/loadgen.py --port <p> --seed <n> --seconds <s> --vocab <v> --mix '<json>'

It draws the cell's schedule from the seed (``traffic.open_loop``), opens one
persistent connection per client thread and sends one request on each
(set-up), then prints ``ready`` and reads the window's start from stdin: a
``time.perf_counter`` reading of the benchmark's process, on the same
monotonic clock.  Request i goes out at start + ``due_s[i]`` on the first free
connection, one request in flight per connection, each one prompt in its own
DoExchange call.  Requests still out when the schedule ends are waited for up
to ``LATE_S``.  It prints one JSON line: per request its send and reply times
and its answer ``[next_token, logprob]``, or null; and the window's host
stalls in this process (``common.Heartbeat``).

Apart from the service, the client threads never wait for the service's
interpreter lock, nor it for theirs.  It imports nothing of JAX, so the chip
stays the benchmark process's alone.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from harness import traffic  # noqa: E402
from harness.common import Heartbeat  # noqa: E402

LATE_S = 60.0  # how long past the schedule's end a reply is waited for


def answer(reply) -> list | None:
    """[next_token, logprob] of a one-row reply, or None if it is not one."""
    if reply is None or len(reply) != 1 or reply[0].num_rows != 1:
        return None
    return [int(reply[0].column("next_token").to_numpy()[0]),
            float(reply[0].column("logprob").to_numpy()[0])]


class OpenLoop:
    """Sends request i at ``t0 + due_s[i]`` on the first free connection."""

    def __init__(self, clients, batches, due_s):
        from repro.core.flight import FlightDescriptor

        self.clients, self.batches, self.due = clients, batches, due_s
        self.desc = FlightDescriptor.for_path("score")
        n = len(batches)
        self.sent: list = [None] * n
        self.done: list = [None] * n
        self.answers: list = [None] * n
        self.errors: list[str] = []
        self._next = 0
        self._lock = threading.Lock()

    def call(self, client, batch):
        ex = client.do_exchange_stream(self.desc, batch.schema)
        ex.write_batch(batch)
        ex.done_writing()
        out = list(ex)
        ex.close()
        return out

    def _worker(self, client, t0: float) -> None:
        while True:
            with self._lock:
                i = self._next
                self._next += 1
            if i >= len(self.batches):
                return
            wait = t0 + self.due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self.sent[i] = time.perf_counter()
            try:
                reply = self.call(client, self.batches[i])
                self.done[i] = time.perf_counter()
                self.answers[i] = answer(reply)
            except Exception as e:  # noqa: BLE001 — a failed request is counted, not fatal
                self.errors.append(f"request {i}: {type(e).__name__}: {e}")

    def run(self, t0: float) -> None:
        threads = [threading.Thread(target=self._worker, args=(c, t0), daemon=True)
                   for c in self.clients]
        for t in threads:
            t.start()
        deadline = t0 + float(self.due[-1]) + LATE_S
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.perf_counter()))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--mix", required=True, help="the traffic mix as JSON")
    args = ap.parse_args()

    from repro.core import RecordBatch
    from repro.core.flight import FlightClient

    mix = json.loads(args.mix)
    sched = traffic.open_loop(args.seed, mix, args.seconds, args.vocab)
    batches = [RecordBatch.from_pydict({"tokens": [p.tolist()]}) for p in sched["prompts"]]
    url = f"tcp://127.0.0.1:{args.port}"
    clients = [FlightClient(url) for _ in range(mix["connections"])]
    loop = OpenLoop(clients, batches, sched["due_s"])
    for c in clients:  # opens every connection; the service's first call compiles
        loop.call(c, batches[0])
    print("ready", flush=True)
    t0 = float(sys.stdin.readline())
    beat = Heartbeat()
    loop.run(t0)
    print(json.dumps({"sent": loop.sent, "done": loop.done, "answers": loop.answers,
                      "errors": loop.errors[:20], "stalls": beat.stop()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
