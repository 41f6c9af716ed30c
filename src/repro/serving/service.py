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
    """DoExchange(batch) -> score_fn(batch).  score_fn: RecordBatch -> RecordBatch."""

    def __init__(self, score_fn: Callable[[RecordBatch], RecordBatch], **kw):
        super().__init__(**kw)
        self.score_fn = score_fn
        self.requests_served = 0

    def do_exchange_impl(self, descriptor, schema, batch) -> RecordBatch:
        out = self.score_fn(batch)
        self.requests_served += 1
        return out


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


class LMScoringService(ScoringService):
    """Scores ``tokens`` list-columns with an LM prefill (greedy next token)."""

    def __init__(self, model, params, max_seq: int = 512, **kw):
        self.model = model
        self.params = params
        self.max_seq = max_seq
        super().__init__(self._score_batch, **kw)

    def _score_batch(self, batch: RecordBatch) -> RecordBatch:
        rows = batch.column("tokens").to_pylist()
        toks = np.zeros((len(rows), self.max_seq), np.int32)
        lens = np.zeros(len(rows), np.int32)
        for i, r in enumerate(rows):
            r = (r or [])[: self.max_seq]
            toks[i, : len(r)] = r
            lens[i] = len(r)
        nxt, lp = score_tokens(self.model, self.params, toks, lens)
        return RecordBatch.from_pydict({
            "next_token": np.asarray(nxt),
            "logprob": np.asarray(lp, np.float32),
        })
