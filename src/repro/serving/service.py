"""Batch-scoring microservice over Flight — the XGBatch analogue (Fig 11).

``ScoringService`` is a FlightServer whose ``DoExchange`` scores incoming
RecordBatches with a JAX model function and streams scored batches back:
clients stream requests in, results out, with zero (de)serialization at
either boundary — the paper's microservice pattern.

``LMScoringService`` wires it to an ``LM``: request batches carry a
``tokens`` list column, responses add ``next_token``/``logprob`` columns
(prefill scoring).  ``Batcher`` coalesces many small client requests into
model-shaped batches (the latency/throughput knob real scoring services
expose; requests are padded into fixed slots so one jit'd function serves
every shape).
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..core.flight.server import InMemoryFlightServer
from ..core.recordbatch import RecordBatch


class ScoringService(InMemoryFlightServer):
    """DoExchange(batch) -> score_fn(batch).  score_fn: RecordBatch -> RecordBatch.

    ``requests`` counts the batches scored; ``server-metrics`` exports it
    in the ``serve`` scope (``serve_counters``), a monotone count whose
    scrape deltas give rates."""

    def __init__(self, score_fn: Callable[[RecordBatch], RecordBatch], **kw):
        super().__init__(**kw)
        self.score_fn = score_fn
        self.requests = 0
        self._count_lock = threading.Lock()  # handlers run on many workers

    def do_exchange_impl(self, descriptor, schema, batch) -> RecordBatch:
        out = self.score_fn(batch)
        with self._count_lock:
            self.requests += 1
        return out

    def serve_counters(self) -> dict:
        return {"requests": self.requests}


@dataclass
class BatcherConfig:
    max_batch: int = 8         # model batch slots
    max_wait_s: float = 0.005  # coalescing window
    pad_to: int = 128          # sequence padding bucket


class Batcher:
    """Coalesces single requests into padded model batches (thread-safe)."""

    def __init__(self, cfg: BatcherConfig, model_fn: Callable[[np.ndarray, np.ndarray], np.ndarray]):
        self.cfg = cfg
        self.model_fn = model_fn  # (tokens (B,L) int32, lens (B,)) -> scores
        self._lock = threading.Lock()
        self._pending: list[tuple[np.ndarray, threading.Event, list]] = []

    def score(self, tokens: np.ndarray) -> np.ndarray:
        """Blocking single-request API; coalesced under the hood."""
        done = threading.Event()
        slot: list = []
        with self._lock:
            self._pending.append((tokens, done, slot))
            if len(self._pending) >= self.cfg.max_batch:
                self._flush_locked()
        if not done.wait(self.cfg.max_wait_s):
            with self._lock:
                if not done.is_set():
                    self._flush_locked()
            done.wait()
        return slot[0]

    def _flush_locked(self) -> None:
        if not self._pending:
            return
        batch, self._pending = self._pending[: self.cfg.max_batch], self._pending[self.cfg.max_batch:]
        lens = np.array([len(t) for t, _, _ in batch], np.int32)
        L = int(np.ceil(max(int(lens.max()), 1) / self.cfg.pad_to) * self.cfg.pad_to)
        toks = np.zeros((self.cfg.max_batch, L), np.int32)  # fixed slots: one jit shape
        for i, (t, _, _) in enumerate(batch):
            toks[i, : len(t)] = t[:L]
        scores = self.model_fn(toks, np.pad(lens, (0, self.cfg.max_batch - len(batch))))
        for i, (_, done, slot) in enumerate(batch):
            slot.append(np.asarray(scores[i]))
            done.set()


@partial(jax.jit, static_argnums=0)
def score_tokens(model, params, tokens, lengths):
    """Greedy next token and its logprob for right-padded rows.

    tokens (B, S) int32, lengths (B,) int32 -> (next_token (B,), logprob (B,)).
    ``params`` is an argument, so the weights never become program constants.
    """
    lgts, _ = model.prefill(params, {"tokens": tokens}, lengths=lengths)
    lp = jax.nn.log_softmax(lgts.astype(jnp.float32), axis=-1)
    return jnp.argmax(lgts, axis=-1).astype(jnp.int32), jnp.max(lp, axis=-1)


def compute_params(model, params):
    """``params`` with each leaf ``model`` declares a matmul operand in bf16.

    The forward pass reads those leaves only through a cast to bf16, so
    casting them once here hands its matmuls the same operands, bit for bit,
    that a program given float32 weights would cast again on every call.
    Other leaves (norms, gates, recurrent weights) keep their dtype.  Works
    on a tree of arrays and, under ``jax.eval_shape``, of shapes."""
    marked = model.matmul_leaves()

    def cast(path, x):
        return x.astype(jnp.bfloat16) if tuple(k.key for k in path) in marked else x

    return jax.tree_util.tree_map_with_path(cast, params)


class LMScoringService(ScoringService):
    """Scores ``tokens`` list-columns with an LM prefill (greedy next token).

    The service holds ``compute_params(model, params)``: its matmul weights
    arrive at ``score_tokens`` in bf16.  ``weight_bytes`` (every leaf held)
    and ``weight_bytes_compute`` (the bf16 matmul leaves among them) are set
    at load, not counted per call.  Every batch is padded to
    ``(rows, max_seq)``: ``tokens_real`` counts the prompt tokens scored,
    ``tokens_padded`` the slots they were padded into, so their ratio is the
    padding's fill.  All four are exported beside ``requests``.  A traced
    request leaves four child spans of its RPC span: ``serve.decode`` (rows
    to the padded array), ``serve.dispatch`` (the jitted call returning; the
    device runs on asynchronously), ``serve.sync`` (waiting for the outputs,
    behind any other worker's programs too) and ``serve.reply`` (the reply
    batch)."""

    def __init__(self, model, params, max_seq: int = 512, **kw):
        self.model = model
        self.params = compute_params(model, params)
        leaves = jax.tree.leaves(self.params)
        self.weight_bytes = sum(x.nbytes for x in leaves)
        self.weight_bytes_compute = sum(x.nbytes for x in leaves if x.dtype == jnp.bfloat16)
        self.max_seq = max_seq
        self.tokens_real = 0
        self.tokens_padded = 0
        super().__init__(self._score_batch, **kw)

    def serve_counters(self) -> dict:
        return {**super().serve_counters(), "tokens_real": self.tokens_real,
                "tokens_padded": self.tokens_padded, "weight_bytes": self.weight_bytes,
                "weight_bytes_compute": self.weight_bytes_compute}

    def _score_batch(self, batch: RecordBatch) -> RecordBatch:
        span = self.telemetry.span
        with span("serve.decode"):
            rows = batch.column("tokens").to_pylist()
            toks = np.zeros((len(rows), self.max_seq), np.int32)
            lens = np.zeros(len(rows), np.int32)
            for i, r in enumerate(rows):
                r = (r or [])[: self.max_seq]
                toks[i, : len(r)] = r
                lens[i] = len(r)
        with self._count_lock:
            self.tokens_real += int(lens.sum())
            self.tokens_padded += toks.size
        with span("serve.dispatch"):
            nxt, lp = score_tokens(self.model, self.params, toks, lens)
        with span("serve.sync"):
            nxt, lp = np.asarray(nxt), np.asarray(lp, np.float32)
        with span("serve.reply"):
            return RecordBatch.from_pydict({"next_token": nxt, "logprob": lp})
