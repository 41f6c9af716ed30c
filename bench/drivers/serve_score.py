"""Scoring cell: an open loop of single-prompt DoExchange calls to ``LMScoringService``.

Set-up makes the weights from the seed and starts the service over TCP; the
load generator (``harness/loadgen.py``, a process of its own) draws the same
schedule from the seed, opens its persistent connections and warms the
service's one program shape.  The window sends each request at its due time,
one request in flight per connection, and times it from its due time to its
reply.  Requests still out when the schedule ends are waited for, up to a
minute; one never answered counts as failed and as infinitely late.  A traced
run traces the whole window.

After the window the service is shut down and its weights freed; the
reference (float32, ``highest``) scores a sample of the answered requests,
drawn from the seed and holding the longest, from the same seed's weights.
Two numbers are compared: the widest gap by which a served token's logit lies
below the reference's best logit for that prompt, and the widest gap between
a served logprob and the reference's log-softmax at the served token.
"""
from __future__ import annotations

import contextlib
import gc
import json
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np

from harness import program, trace, traffic, weights
from harness.common import Heartbeat, log, memory_peak, quantile

SPAN_REQUEST = "bench.request"
LOADGEN = Path(__file__).resolve().parents[1] / "harness" / "loadgen.py"
START_S = 0.05  # the window starts this long after the load generator is told


def scrape_queue_wait(client) -> tuple[int, float]:
    """(count, seconds) of the event loop's ``queue_wait`` histogram."""
    from repro.core.flight.telemetry import decode_telemetry_batch

    rows = decode_telemetry_batch(client.do_action("server-metrics")[0].body).to_pydict()
    for scope, name, count, total in zip(rows["scope"], rows["name"], rows["count"], rows["sum_s"]):
        if scope == "io" and name == "queue_wait":
            return int(count), float(total)
    return 0, 0.0


class LoadGen:
    """The load generator's process: started and warmed in set-up, then run once."""

    def __init__(self, port: int, seed: int, seconds: float, vocab: int, mix: dict):
        self.proc = subprocess.Popen(
            [sys.executable, str(LOADGEN), "--port", str(port), "--seed", str(seed),
             "--seconds", repr(seconds), "--vocab", str(vocab), "--mix", json.dumps(mix)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        if self.proc.stdout.readline().strip() != "ready":
            self.close()
            raise RuntimeError(f"load generator failed in set-up (exit {self.proc.returncode})")

    def run(self, t0: float) -> dict:
        self.proc.stdin.write(f"{t0!r}\n")
        self.proc.stdin.flush()
        line = self.proc.stdout.readline()
        self.close()
        if not line:
            raise RuntimeError(f"load generator gave no result (exit {self.proc.returncode})")
        return json.loads(line)

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()


def log_softmax(x: np.ndarray) -> np.ndarray:
    m = x.max()
    return x - m - np.log(np.sum(np.exp(x - m)))


def reference_logits(ref, params, model: dict, prompts: list, quant=None) -> list:
    """Next-token logits of each prompt, batched by padded length."""
    f = jax.jit(jax.vmap(lambda p, t, n: ref.logits(p, t, n, model, quant),
                         in_axes=(None, 0, 0)))
    out = [None] * len(prompts)
    by_len: dict[int, list[int]] = {}
    for i, p in enumerate(prompts):
        by_len.setdefault(max(16, 1 << (len(p) - 1).bit_length()), []).append(i)
    for L, idx in sorted(by_len.items()):
        for s in range(0, len(idx), 8):
            chunk = idx[s:s + 8]
            toks = np.zeros((8, L), np.int32)
            lens = np.ones(8, np.int32)
            for r, i in enumerate(chunk):
                toks[r, :len(prompts[i])] = prompts[i]
                lens[r] = len(prompts[i])
            with jax.default_matmul_precision("highest"):
                lg = np.asarray(f(params, toks, lens), np.float64)
            for r, i in enumerate(chunk):
                out[i] = lg[r]
    return out


def answer_gaps(ref_lg: list, answers: list) -> dict:
    """The widest gaps of served answers [(token, logprob)] under the reference."""
    logit = [float(l.max() - l[t]) for l, (t, _) in zip(ref_lg, answers)]
    lp = [abs(p - float(log_softmax(l)[t])) for l, (t, p) in zip(ref_lg, answers)]
    return {"logit_gap": max(logit, default=float("inf")),
            "logprob_gap": max(lp, default=float("inf"))}


def silences(sent: list, done: list, answered: list[int], over: float = 0.2) -> list:
    """(start, seconds) of each stretch over ``over`` s in which a request was
    out and no reply came back, as the load generator saw it."""
    spans = trace.merge([(sent[i], done[i]) for i in answered])
    replies = sorted(done[i] for i in answered)
    out = []
    for a, b in spans:
        marks = [a] + [t for t in replies if a < t <= b]
        out += [(s, e - s) for s, e in zip(marks, marks[1:]) if e - s > over]
    return out


def check_sample(seed: int, prompts: list, answered: list[int], n: int) -> list[int]:
    """Up to ``n`` answered requests drawn from the seed, the longest among them."""
    if not answered:
        return []
    longest = max(answered, key=lambda i: len(prompts[i]))
    rest = [i for i in answered if i != longest]
    g = traffic.rng(seed, "check")
    pick = g.choice(len(rest), size=min(n - 1, len(rest)), replace=False) if rest else []
    return [longest] + [rest[j] for j in pick]


def run(cell, *, seed: int, seconds: float, trace_dir, devs, clock, events) -> dict:
    from repro.core.flight import FlightClient
    from repro.serving import LMScoringService

    conf, mix = cell.config, cell.traffic
    ref, flops = cell.reference(), cell.flops()
    specs = ref.param_specs(conf["model"])
    vocab = conf["model"]["vocab_size"]

    with clock.phase("weights"):
        model, p_sh = program.build_model(conf)
        program.check_layout(model, weights.shapes(specs))
        params = weights.make_params(specs, seed, sharding=p_sh)
        jax.block_until_ready(params)
    with clock.phase("traffic"):
        sched = traffic.open_loop(seed, mix, seconds, vocab)
        prompts = sched["prompts"]
    with clock.phase("server"):
        svc = LMScoringService(model, params, max_seq=conf["service"]["max_seq"]).serve_tcp()
    gen = None
    try:
        admin = FlightClient(f"tcp://127.0.0.1:{svc.port}")
        with clock.phase("warmup"):  # the load generator's set-up; its first call compiles
            gen = LoadGen(svc.port, seed, seconds, vocab, mix)
            scrape_queue_wait(admin)
        setup_s = clock.total()

        snap = events.snapshot()
        qw0 = scrape_queue_wait(admin)
        with contextlib.ExitStack() as traced:
            if trace_dir is not None:
                trace.start(trace_dir)
                traced.callback(jax.profiler.stop_trace)
                t_span = time.perf_counter()
                traced.enter_context(jax.profiler.TraceAnnotation(trace.WINDOW_SPAN))
                t_span = (t_span + time.perf_counter()) / 2
            t0 = time.perf_counter() + START_S
            beat = Heartbeat()
            got = gen.run(t0)
            stalls = {"service process": beat.stop(), "load generator": got["stalls"]}
        stalls["replies silent"] = silences(got["sent"], got["done"],
                                            [i for i, a in enumerate(got["answers"]) if a])
        qw1 = scrape_queue_wait(admin)
        compiles = events.since(snap)
        mem = memory_peak(devs)
    finally:
        if gen is not None:
            gen.close()
        svc.shutdown()

    n = len(prompts)
    sent, done, answers = got["sent"], got["done"], got["answers"]
    answered = [i for i in range(n) if answers[i] is not None]
    t_end = max([done[i] for i in answered], default=time.perf_counter())
    tr = None
    if trace_dir is not None:
        tr = trace.load(trace_dir)
        shift = tr.window[0] - t_span  # the load generator's clock onto the trace's
        tr.host_spans[SPAN_REQUEST] = [(sent[i] + shift, done[i] + shift) for i in answered]

    due = t0 + np.asarray(sched["due_s"])
    ok = set(answered)
    lat = [done[i] - due[i] if i in ok else None for i in range(n)]
    late = [sent[i] - due[i] for i in range(n) if sent[i] is not None]
    window_s = t_end - t0
    real = sum(len(prompts[i]) for i in answered)
    log(f"[serve] {n} requests due over {seconds}s on {mix['connections']} connections, "
        f"{len(answered)} answered, window {window_s:.3f}s; generator lateness "
        f"p50 {1e3 * quantile(late, 0.5):.3f} ms p99 {1e3 * quantile(late, 0.99):.3f} ms "
        f"max {1e3 * max(late, default=0):.3f} ms")
    log(f"[serve] stalls (s after the window's start at {t0:.3f}, s): heartbeat over 0.1 s late in "
        "the service process and the load generator; replies silent over 0.2 s: " + "; ".join(
        f"{k} {[(round(t - t0, 3), round(d, 3)) for t, d in v]}" for k, v in stalls.items()))
    for e in got["errors"][:5]:
        log(f"[serve] {e}")

    del svc, params
    gc.collect()
    sample = check_sample(seed, prompts, answered, conf["check_requests"])
    t_ref = time.perf_counter()
    ref_params = weights.make_params(specs, seed)
    lg = reference_logits(ref, ref_params, conf["model"], [prompts[i] for i in sample])
    served = [answers[i] for i in sample]
    gaps = answer_gaps(lg, served)
    log(f"[serve] reference over {len(sample)} requests ({sum(len(prompts[i]) for i in sample)} "
        f"prompt tokens) in {time.perf_counter() - t_ref:.1f}s; served token below the "
        f"reference's best in {sum(float(l.max() - l[t]) > 0 for l, (t, _) in zip(lg, served))}")
    checks = {k: {"value": v, "limit": conf["limits"][k]} for k, v in gaps.items()}
    return {
        "setup_s": setup_s, "attempted": n, "failed": n - len(answered),
        "detail": {"prompts": [prompts[i] for i in sample], "ref_logits": lg, "served": served,
                   "latency_s": lat, "lateness_s": late, "stalls": stalls},
        "memory_peak": mem, "compiles_in_window": compiles, "checks": checks, "trace": tr,
        "end_to_end": {"score_p95_ms": 1e3 * quantile(lat, 0.95),
                       "score_tokens_per_s": real / window_s},
        "counters": {
            "serve_required_flops": sum(flops.forward_flops(conf["model"], len(prompts[i]))
                                        for i in answered),
            "queue_wait_count": qw1[0] - qw0[0], "queue_wait_sum_s": qw1[1] - qw0[1]},
    }
