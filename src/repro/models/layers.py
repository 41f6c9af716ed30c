"""Shared neural-net building blocks (pure functions over param pytrees).

Params are nested dicts of jnp arrays; a parallel tree of *logical axis
tuples* (see distributed/sharding.py) describes how each leaf shards.  The
``ParamBuilder`` keeps both trees in sync during init.

Precision policy (framework-wide):
  * params: ``cfg.param_dtype`` (f32 small models, bf16 for the ≥30 B ones)
  * matmul compute: bf16 inputs, f32 accumulation (``preferred_element_type``)
  * norms / softmax / router / scan carries: f32
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------------------
# param construction
# --------------------------------------------------------------------------


class ParamBuilder:
    """Collects params and their logical axes; splits keys deterministically.

    ``abstract=True`` builds ShapeDtypeStructs instead of arrays — used by the
    dry-run to get the full param tree of 100B+ models with zero allocation.
    ``matmul`` holds the path of every leaf declared ``matmul=True``: one the
    forward pass reads only as a bf16 matmul operand (``dot``/``einsum``/
    ``embed``), so it may be held in bf16 without changing any matmul's
    operands.  All scopes of one builder share the set.
    """

    def __init__(self, key: jax.Array, dtype=jnp.float32, abstract: bool = False):
        self._key = key
        self.dtype = dtype
        self.abstract = abstract
        self.params: dict[str, Any] = {}
        self.axes: dict[str, Any] = {}
        self.path: tuple[str, ...] = ()
        self.matmul: set[tuple[str, ...]] = set()

    def _next_key(self) -> jax.Array:
        self._key, k = jax.random.split(self._key)
        return k

    def param(
        self,
        name: str,
        shape: tuple[int, ...],
        logical: tuple,
        init: str | float = "normal",
        scale: float | None = None,
        dtype=None,
        matmul: bool = False,
    ):
        assert len(shape) == len(logical), f"{name}: {shape} vs {logical}"
        dtype = dtype or self.dtype
        if matmul:
            self.matmul.add(self.path + (name,))
        if self.abstract:
            self.params[name] = jax.ShapeDtypeStruct(shape, dtype)
            self.axes[name] = logical
            return self.params[name]
        if init == "normal":
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
            std = scale if scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
            w = jax.random.normal(self._next_key(), shape, jnp.float32) * std
        elif init == "zeros":
            w = jnp.zeros(shape, jnp.float32)
        elif init == "ones":
            w = jnp.ones(shape, jnp.float32)
        elif isinstance(init, float):
            w = jnp.full(shape, init, jnp.float32)
        else:
            raise ValueError(init)
        self.params[name] = w.astype(dtype)
        self.axes[name] = logical
        return self.params[name]

    def scope(self, name: str) -> "ParamBuilder":
        sub = ParamBuilder(self._next_key(), self.dtype, self.abstract)
        sub.path, sub.matmul = self.path + (name,), self.matmul
        self.params[name] = sub.params
        self.axes[name] = sub.axes
        return sub

    def set(self, name: str, params: dict, axes: dict) -> None:
        self.params[name] = params
        self.axes[name] = axes


def count_params(params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))


# --------------------------------------------------------------------------
# numerics
# --------------------------------------------------------------------------


# Accumulation dtype for matmuls.  f32 default; bf16 halves the backward
# activation psums (GSPMD reduces the pre-cast partials) — a §Perf lever.
_ACCUM_DTYPE = jnp.float32


def set_matmul_accum_dtype(dtype) -> None:
    global _ACCUM_DTYPE
    _ACCUM_DTYPE = dtype


def dot(x, w, compute_dtype=jnp.bfloat16):
    """Matmul with bf16 inputs and configurable accumulation."""
    return jax.lax.dot_general(
        x.astype(compute_dtype),
        w.astype(compute_dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=_ACCUM_DTYPE,
    )


def einsum(spec: str, *args, compute_dtype=jnp.bfloat16):
    args = [a.astype(compute_dtype) for a in args]
    return jnp.einsum(spec, *args, preferred_element_type=_ACCUM_DTYPE)


def rms_norm(x, weight, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    return out.astype(x.dtype)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def swiglu(gate, up):
    return jax.nn.silu(gate) * up


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


# --------------------------------------------------------------------------
# rotary embeddings
# --------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jax.Array:
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)  # (head_dim/2,)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    freqs = rope_frequencies(x.shape[-1], theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# FFN blocks
# --------------------------------------------------------------------------


def init_mlp(pb: ParamBuilder, d_model: int, d_ff: int, stack: int | None = None,
             activation: str = "swiglu") -> None:
    """SwiGLU (gate+up+down) or GELU (up+down) MLP, optionally layer-stacked."""
    lead = (stack,) if stack is not None else ()
    lax = ("layers",) if stack is not None else ()
    if activation == "swiglu":
        pb.param("w_gate", lead + (d_model, d_ff), lax + ("embed", "ff"), matmul=True)
        pb.param("w_up", lead + (d_model, d_ff), lax + ("embed", "ff"), matmul=True)
    else:
        pb.param("w_up", lead + (d_model, d_ff), lax + ("embed", "ff"), matmul=True)
    pb.param("w_down", lead + (d_ff, d_model), lax + ("ff", "embed"), matmul=True)


def mlp(params: dict, x: jax.Array, ctx, activation: str = "swiglu") -> jax.Array:
    """x: (B, S, D) -> (B, S, D).  TP over the ff dim; psum via GSPMD on w_down."""
    if activation == "swiglu":
        h = swiglu(dot(x, params["w_gate"]), dot(x, params["w_up"]))
    else:
        h = gelu(dot(x, params["w_up"]))
    h = ctx.constrain(h.astype(x.dtype), ("batch", "seq", "ff"))
    out = dot(h, params["w_down"])
    return ctx.constrain(out.astype(x.dtype), ("batch", "seq", "embed_nosplit"))


# --------------------------------------------------------------------------
# embeddings / lm head
# --------------------------------------------------------------------------


def init_embedding(pb: ParamBuilder, vocab: int, d_model: int) -> None:
    pb.param("embedding", (vocab, d_model), ("vocab", "embed"), scale=0.02, matmul=True)


def embed(params: dict, tokens: jax.Array, ctx) -> jax.Array:
    out = params["embedding"].astype(jnp.bfloat16)[tokens]
    return ctx.constrain(out, ("batch", "seq", "embed_nosplit"))


def logits(params: dict, x: jax.Array, ctx) -> jax.Array:
    """(B, S, D) -> (B, S, V) f32, vocab-sharded over model."""
    out = einsum("bsd,vd->bsv", x, params["embedding"])
    return ctx.constrain(out, ("batch", "seq", "vocab"))


def cross_entropy_loss(lgts: jax.Array, labels: jax.Array, mask: jax.Array | None = None):
    """Mean token NLL; logits f32 (B, S, V), labels int (B, S)."""
    lse = jax.nn.logsumexp(lgts, axis=-1)
    picked = jnp.take_along_axis(lgts, labels[..., None], axis=-1)[..., 0]
    nll = lse - picked
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def chunked_lm_loss(emb_params: dict, x: jax.Array, labels: jax.Array, ctx,
                    chunk: int = 512, mask: jax.Array | None = None):
    """LM head + xent scanned over seq chunks so (B,S,V) never materializes."""
    B, S, D = x.shape
    n = max(1, S // chunk)
    while S % n:  # nearest divisor ≤ desired chunk count (static python)
        n -= 1
    chunk = S // n
    xs = x.reshape(B, n, chunk, D).swapaxes(0, 1)  # (n, B, c, D)
    ls = labels.reshape(B, n, chunk).swapaxes(0, 1)
    ms = None if mask is None else mask.reshape(B, n, chunk).swapaxes(0, 1)

    def body(carry, inp):
        tot, cnt = carry
        if ms is None:
            xc, lc = inp
            mc = jnp.ones_like(lc, jnp.float32)
        else:
            xc, lc, mc = inp
            mc = mc.astype(jnp.float32)
        lg = logits(emb_params, xc, ctx)
        lse = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, lc[..., None], axis=-1)[..., 0]
        tot = tot + jnp.sum((lse - picked) * mc)
        cnt = cnt + jnp.sum(mc)
        return (tot, cnt), None

    inps = (xs, ls) if ms is None else (xs, ls, ms)
    # remat: recompute each chunk's logits in backward instead of saving the
    # (B, chunk, V/shard) f32 stack (1.5 GB/device on internlm2 — see §Perf)
    body = jax.checkpoint(body, prevent_cse=False)
    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)), inps)
    return tot / jnp.maximum(cnt, 1.0)
