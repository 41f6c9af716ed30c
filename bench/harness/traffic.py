"""The one traffic generator: every mix is a parameter file under ``bench/traffic``.

Each draw takes only the seed and the mix's parameters, so the same seed gives
the same traffic.  Prompt tokens are Zipf over the vocabulary, as in the
program's own corpus generator (``repro.data.dataset.synthesize_corpus``),
copied here so that no program change moves the yardstick.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent stream per purpose; seeds may exceed 64 bits or be negative."""
    return np.random.default_rng([seed % (1 << 64), seed // (1 << 64) % (1 << 32),
                                  *stream.encode()])


def zipf_probs(vocab: int, a: float) -> np.ndarray:
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** a
    return p / p.sum()


def open_loop(seed: int, mix: dict, seconds: float, vocab: int) -> dict:
    """Requests due at Poisson times for ``seconds``.

    The arrival times are the same for every seed: exponential quantiles in
    the order that the mix's ``arrival_seed`` draws, since the order of the
    gaps decides the bursts and so the tail.  Every seed gets the same
    multiset of prompt lengths (log-normal quantiles) in its own order, with
    its own tokens: seeds change which prompt comes when, not the work."""
    g = rng(seed, "open_loop")
    n = max(2, round(mix["rate_per_s"] * seconds))
    u = (np.arange(n) + 0.5) / n
    v = (np.arange(n - 1) + 0.5) / (n - 1)
    gaps = -np.log1p(-v) / mix["rate_per_s"]
    z = np.array([NormalDist().inv_cdf(x) for x in u])
    lens = np.exp(math.log(mix["prompt_median"]) + mix["prompt_sigma"] * z)
    lens = np.clip(np.rint(lens), mix["prompt_min"], mix["prompt_max"]).astype(np.int64)
    gaps = rng(mix["arrival_seed"], "arrivals").permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)])
    lens = g.permutation(lens)
    probs = zipf_probs(vocab, mix["zipf_a"])
    tokens = g.choice(vocab, size=int(lens.sum()), p=probs).astype(np.int32)
    split = np.split(tokens, np.cumsum(lens)[:-1])
    return {"due_s": due, "prompts": split}
