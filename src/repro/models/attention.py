"""GQA attention: TP-divisible grouped layout, flash prefill, two decode paths.

The production mesh has model=16, but the assigned archs have q/kv head
counts that don't all divide 16 (deepseek 56q/8kv, phi4 24q/8kv, qwen3 4kv…).
We therefore compute attention in a **grouped layout** ``(Ke, Gq, hd)``:

  * ``Ke`` ("effective kv heads") = true kv heads K replicated up to
    ``shard_groups`` (=16) when K < 16.  Replicating a kv head and splitting
    its q-group across the replicas is *exact* — each q head still sees its
    original kv head.
  * ``Gq`` = ceil(G / R) q heads per effective kv head (G = q per true kv
    head, R = replication).  When G doesn't divide evenly, the layout is
    zero-padded and a constant ``head_mask`` kills the padded heads' outputs
    (and their gradients), so the math equals the unpadded model exactly.

Sharding is then always over ``Ke`` (divisible by 16 by construction).
wk/wv stay at the *true* K (faithful params; replication happens on
activations, post-RoPE, where it commutes).

Three attention paths:
  * ``flash_attention``  — train/prefill: double-scan online softmax
    (q-chunks × kv-chunks), O(qc·kc) memory, causal or bidirectional.
  * ``decode_attention`` — serve_step when batch shards: plain einsum over
    the (batch-sharded, head-sharded) KV cache.
  * ``flash_decode_shardmap`` — serve_step when the KV cache is
    *sequence-sharded* (long-context, batch=1): partial softmax per shard +
    psum combine (distributed flash-decode).  The Pallas kernel
    ``kernels/flash_decode.py`` is the single-shard TPU version of the same
    loop.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .layers import ParamBuilder, apply_rope, einsum


@dataclass(frozen=True)
class HeadLayout:
    n_heads: int            # true q heads H
    n_kv_heads: int         # true kv heads K
    head_dim: int
    shard_groups: int       # target divisibility (16 in production, 1 in smoke)

    @property
    def repl(self) -> int:  # kv replication factor R
        if self.n_kv_heads >= self.shard_groups:
            return 1
        assert self.shard_groups % self.n_kv_heads == 0, (self.n_kv_heads, self.shard_groups)
        return self.shard_groups // self.n_kv_heads

    @property
    def eff_kv(self) -> int:  # Ke
        return self.n_kv_heads * self.repl

    @property
    def group(self) -> int:  # true q heads per true kv head
        assert self.n_heads % self.n_kv_heads == 0
        return self.n_heads // self.n_kv_heads

    @property
    def q_per_kv(self) -> int:  # Gq (padded)
        return -(-self.group // self.repl)

    @property
    def padded_heads(self) -> int:
        return self.eff_kv * self.q_per_kv

    def head_mask(self) -> np.ndarray:
        """(Ke, Gq) 1.0 for real q heads, 0.0 for pads (constant, not a param)."""
        m = np.zeros((self.eff_kv, self.q_per_kv), np.float32)
        for k in range(self.n_kv_heads):
            for g in range(self.group):
                m[k * self.repl + g // self.q_per_kv, g % self.q_per_kv] = 1.0
        return m

    @property
    def kv_logical(self) -> str:
        # true-K projections shard over model only when K divides the groups
        return "kv_heads" if self.repl == 1 else "kv_heads_rep"


def init_attention(pb: ParamBuilder, d_model: int, layout: HeadLayout,
                   stack: int | None = None, qk_norm: bool = False) -> None:
    lead = (stack,) if stack is not None else ()
    lax_ = ("layers",) if stack is not None else ()
    hd, Ke, Gq, K = layout.head_dim, layout.eff_kv, layout.q_per_kv, layout.n_kv_heads
    pb.param("wq", lead + (d_model, Ke, Gq, hd),
             lax_ + ("embed", "kv_heads", "q_per_kv", "head_dim"), matmul=True)
    pb.param("wk", lead + (d_model, K, hd), lax_ + ("embed", layout.kv_logical, "head_dim"),
             matmul=True)
    pb.param("wv", lead + (d_model, K, hd), lax_ + ("embed", layout.kv_logical, "head_dim"),
             matmul=True)
    pb.param("wo", lead + (Ke, Gq, hd, d_model),
             lax_ + ("kv_heads", "q_per_kv", "head_dim", "embed"), matmul=True)
    if qk_norm:
        pb.param("q_norm", lead + (hd,), lax_ + ("head_dim",), init="ones")
        pb.param("k_norm", lead + (hd,), lax_ + ("head_dim",), init="ones")


def _rope_kg(x, positions, theta):
    """RoPE over (..., S, A, B, hd) by flattening the two head dims."""
    B, S = x.shape[0], x.shape[1]
    a, b, hd = x.shape[2], x.shape[3], x.shape[4]
    flat = x.reshape(B, S, a * b, hd)
    return apply_rope(flat, positions, theta).reshape(B, S, a, b, hd)


def _qk_norm(x, w, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def project_qkv(params, x, positions, layout: HeadLayout, ctx, rope_theta=10000.0,
                use_rope=True):
    """x (B,S,D) -> q (B,S,Ke,Gq,hd), k/v (B,S,Ke,hd) — all model-sharded."""
    q = einsum("bsd,dkgh->bskgh", x, params["wq"])
    k = einsum("bsd,dkh->bskh", x, params["wk"])
    v = einsum("bsd,dkh->bskh", x, params["wv"])
    if "q_norm" in params:
        q, k = _qk_norm(q, params["q_norm"]), _qk_norm(k, params["k_norm"])
    if use_rope:
        q = _rope_kg(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    if layout.repl > 1:
        k = jnp.repeat(k, layout.repl, axis=2)
        v = jnp.repeat(v, layout.repl, axis=2)
    q = ctx.constrain(q.astype(jnp.bfloat16), ("batch", "seq", "kv_heads", "q_per_kv", "head_dim"))
    k = ctx.constrain(k.astype(jnp.bfloat16), ("batch", "seq", "kv_heads", "head_dim"))
    v = ctx.constrain(v.astype(jnp.bfloat16), ("batch", "seq", "kv_heads", "head_dim"))
    return q, k, v


def output_proj(params, attn, layout: HeadLayout, ctx):
    """attn (B,S,Ke,Gq,hd) -> (B,S,D); head_mask kills padded heads exactly."""
    mask = jnp.asarray(layout.head_mask())[None, None, :, :, None]
    attn = attn * mask
    out = einsum("bskgh,kghd->bsd", attn, params["wo"])
    return ctx.constrain(out.astype(jnp.bfloat16), ("batch", "seq", "embed_nosplit"))


# ---------------------------------------------------------------------------
# flash attention (train / prefill)
# ---------------------------------------------------------------------------


def flash_attention(q, k, v, *, causal: bool, q_chunk: int = 512, kv_chunk: int = 1024,
                    softmax_scale: float | None = None):
    """Double-scan online-softmax attention.

    q: (B, S, Ke, Gq, hd); k/v: (B, S, Ke, hd).  Returns (B, S, Ke, Gq, hd).
    Memory per step is O(q_chunk × kv_chunk) — never the S×S matrix.
    """
    B, S, Ke, Gq, hd = q.shape
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(hd)
    qc, kc = min(q_chunk, S), min(kv_chunk, S)
    nq, nk = S // qc, S // kc
    assert S % qc == 0 and S % kc == 0, (S, qc, kc)

    qs = q.reshape(B, nq, qc, Ke, Gq, hd).transpose(1, 0, 3, 4, 2, 5)  # (nq,B,Ke,Gq,qc,hd)
    ks = k.reshape(B, nk, kc, Ke, hd).transpose(1, 0, 3, 2, 4)          # (nk,B,Ke,kc,hd)
    vs = v.reshape(B, nk, kc, Ke, hd).transpose(1, 0, 3, 2, 4)

    q_pos = jnp.arange(S, dtype=jnp.int32).reshape(nq, qc)
    k_pos = jnp.arange(S, dtype=jnp.int32).reshape(nk, kc)

    def q_step(_, qi):
        qb, qp = qi  # (B,Ke,Gq,qc,hd), (qc,)

        def kv_step(carry, ki):
            m, l, acc = carry
            kb, vb, kp = ki
            s = jnp.einsum("bkgqh,bkch->bkgqc", qb.astype(jnp.bfloat16),
                           kb.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32) * scale
            if causal:
                msk = qp[:, None] >= kp[None, :]  # (qc, kc)
                s = jnp.where(msk[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))          # (b,Ke,Gq,qc)
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqc,bkch->bkgqh", p.astype(jnp.bfloat16),
                            vb.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Ke, Gq, qc), -1e30, jnp.float32)
        l0 = jnp.zeros((B, Ke, Gq, qc), jnp.float32)
        a0 = jnp.zeros((B, Ke, Gq, qc, hd), jnp.float32)
        # remat the kv step: without it, scan-vjp stacks the (qc,kc) score
        # blocks across all kv chunks for backward — the exact memory blow-up
        # flash attention exists to avoid (measured: 21.5 GB -> see §Perf).
        (m, l, acc), _ = jax.lax.scan(jax.checkpoint(kv_step, prevent_cse=False),
                                      (m0, l0, a0), (ks, vs, k_pos))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(jax.checkpoint(q_step, prevent_cse=False),
                           None, (qs, q_pos))  # (nq,B,Ke,Gq,qc,hd)
    return outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, Ke, Gq, hd)


# ---------------------------------------------------------------------------
# decode paths
# ---------------------------------------------------------------------------


def decode_attention(q, k_cache, v_cache, cache_len, *, softmax_scale=None):
    """One-token attention over a (B, Smax, Ke, hd) cache (batch-sharded path).

    q: (B, 1, Ke, Gq, hd); cache_len: scalar or (B,) — valid prefix length.
    """
    B, _, Ke, Gq, hd = q.shape
    Smax = k_cache.shape[1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(hd)
    s = jnp.einsum("bokgh,bskh->bkgs", q.astype(jnp.bfloat16), k_cache.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(Smax, dtype=jnp.int32)
    valid = pos[None, :] < jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32).reshape(-1, 1), (B, Smax))
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", p.astype(jnp.bfloat16), v_cache.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return out[:, None].astype(q.dtype)  # (B,1,Ke,Gq,hd)


def flash_decode_shardmap(q, k_cache, v_cache, cache_len, ctx, *, softmax_scale=None):
    """Distributed flash-decode: KV cache sharded on sequence over the data
    (and pod) axes; each shard computes a partial softmax, combined via psum.
    q: (B,1,Ke,Gq,hd) replicated over data; caches (B,Smax,Ke,hd) seq-sharded.
    """
    mesh = ctx.mesh
    seq_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    model_ax = "model" if "model" in mesh.axis_names else None
    B, _, Ke, Gq, hd = q.shape
    Smax = k_cache.shape[1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(hd)
    n_shards = int(np.prod([mesh.shape[a] for a in seq_axes])) if seq_axes else 1
    S_loc = Smax // max(n_shards, 1)

    qspec = P(None, None, model_ax, None, None)
    kvspec = P(None, seq_axes if seq_axes else None, model_ax, None)
    outspec = P(None, None, model_ax, None, None)

    def kernel(q_l, k_l, v_l, clen):
        # global offset of this shard's sequence slice
        if seq_axes:
            idx = jnp.int32(0)
            for a in seq_axes:  # row-major linearization over the seq axes
                idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
            off = idx * S_loc
        else:
            off = 0
        s = jnp.einsum("bokgh,bskh->bkgs", q_l.astype(jnp.bfloat16), k_l.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32) * scale
        pos = off + jnp.arange(S_loc, dtype=jnp.int32)
        valid = pos[None, :] < jnp.broadcast_to(jnp.asarray(clen, jnp.int32).reshape(-1, 1), (s.shape[0], S_loc))
        s = jnp.where(valid[:, None, None, :], s, -1e30)
        m_loc = jnp.max(s, axis=-1)                       # (b,Ke,Gq)
        p = jnp.exp(s - m_loc[..., None])
        l_loc = jnp.sum(p, axis=-1)
        o_loc = jnp.einsum("bkgs,bskh->bkgh", p.astype(jnp.bfloat16), v_l.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)
        if seq_axes:
            m_g = jax.lax.pmax(m_loc, seq_axes)
            corr = jnp.exp(m_loc - m_g)
            o = jax.lax.psum(o_loc * corr[..., None], seq_axes)
            l = jax.lax.psum(l_loc * corr, seq_axes)
        else:
            o, l = o_loc, l_loc
        return (o / jnp.maximum(l, 1e-30)[..., None])[:, None].astype(q_l.dtype)

    fn = jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(qspec, kvspec, kvspec, P()),
        out_specs=outspec,
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, jnp.asarray(cache_len, jnp.int32).reshape(-1))


def update_kv_cache(k_cache, v_cache, k_new, v_new, position):
    """Insert one step's (B,1,Ke,hd) at ``position`` (scalar int32)."""
    idx = (0, position, 0, 0)
    k_cache = jax.lax.dynamic_update_slice(k_cache, k_new.astype(k_cache.dtype), idx)
    v_cache = jax.lax.dynamic_update_slice(v_cache, v_new.astype(v_cache.dtype), idx)
    return k_cache, v_cache
