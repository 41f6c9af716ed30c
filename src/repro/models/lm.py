"""Unified language-model assembly for all 10 assigned architectures.

One config → one model.  Layers are **stacked and scanned**: parameters carry
a leading ``n_superblocks`` dim and ``jax.lax.scan`` + ``jax.checkpoint``
(remat) run the stack, so HLO size and compile time are O(1) in depth — the
property that makes 62 production-mesh dry-run compiles feasible and what
MaxText-class frameworks do in production.

A *superblock* is the smallest repeating pattern of heterogeneous layers:
  dense/moe/vlm : 1 layer  (attention + FFN/MoE)
  hybrid(jamba) : 8 layers (attn at index 4, mamba elsewhere; MoE every 2nd)
  ssm(xlstm)    : 4 layers (3 mLSTM + 1 sLSTM)
  audio(hubert) : 1 layer  (bidirectional attention + FFN)

Modality frontends (vlm patch embeddings / audio frames) are stubs per the
assignment: ``input_specs()`` supplies precomputed embeddings, the model owns
only the projection into d_model.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.sharding import ShardingCtx
from . import layers as L
from .attention import (
    HeadLayout,
    decode_attention,
    flash_attention,
    flash_decode_shardmap,
    init_attention,
    output_proj,
    project_qkv,
    update_kv_cache,
)
from .mamba import MambaConfig, init_mamba, mamba_init_state, mamba_mix
from .moe import MoEConfig, init_moe, moe_ffn
from .xlstm import (
    XLSTMConfig,
    init_mlstm,
    init_slstm,
    mlstm_init_state,
    mlstm_mix,
    slstm_init_state,
    slstm_mix,
)


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | hybrid | ssm | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None
    rope_theta: float = 10000.0
    norm_type: str = "rms"           # rms | ln
    norm_eps: float = 1e-5
    activation: str = "swiglu"
    causal: bool = True
    qk_norm: bool = False
    # MoE
    moe: MoEConfig | None = None
    moe_every: int = 1               # apply MoE at layer idx % moe_every == moe_offset
    moe_offset: int = 0
    # hybrid (jamba)
    mamba: MambaConfig | None = None
    attn_period: int = 8             # 1 attention layer per this many (jamba 1:7)
    attn_index: int = 4
    # ssm (xlstm)
    xlstm: XLSTMConfig | None = None
    slstm_period: int = 4            # 1 sLSTM per this many blocks
    # frontends
    frontend: str | None = None      # vision | audio
    frontend_dim: int = 0
    frontend_tokens: int = 0         # vlm: patches prepended
    # engineering
    shard_groups: int = 16           # attention TP divisibility target
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: str = "nothing"    # nothing | dots
    scan_layers: bool = True
    force_seq_sharded_decode: bool = False
    lm_loss_chunk: int = 512
    attn_q_chunk: int = 512
    attn_kv_chunk: int = 1024
    mamba_chunk: int = 64
    logical_rules: dict = field(default_factory=dict)

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def superblock(self) -> int:
        if self.family == "hybrid":
            return self.attn_period
        if self.family == "ssm":
            return self.slstm_period
        return 1

    @property
    def n_superblocks(self) -> int:
        assert self.n_layers % self.superblock == 0, (self.n_layers, self.superblock)
        return self.n_layers // self.superblock

    @property
    def head_layout(self) -> HeadLayout:
        return HeadLayout(self.n_heads, self.n_kv_heads, self.resolved_head_dim,
                          self.shard_groups)

    def layer_kind(self, idx_in_superblock: int) -> dict:
        """What sub-layers layer ``idx`` of a superblock contains."""
        i = idx_in_superblock
        if self.family == "hybrid":
            mixer = "attn" if i == self.attn_index else "mamba"
            ffn = "moe" if (self.moe is not None and i % self.moe_every == self.moe_offset) else "mlp"
            return {"mixer": mixer, "ffn": ffn}
        if self.family == "ssm":
            return {"mixer": "slstm" if i == self.slstm_period - 1 else "mlstm", "ffn": None}
        mixer = "attn"
        ffn = "moe" if self.moe is not None else "mlp"
        return {"mixer": mixer, "ffn": ffn}

    def param_count(self) -> int:
        """Analytic parameter count (true heads, not padded)."""
        D, hd = self.d_model, self.resolved_head_dim
        n_attn = sum(1 for i in range(self.superblock)
                     if self.layer_kind(i)["mixer"] == "attn") * self.n_superblocks
        attn = n_attn * (D * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * D)
        total = attn + self.vocab * D
        for i in range(self.superblock):
            kind = self.layer_kind(i)
            per = 0
            if kind["mixer"] == "mamba":
                m = self.mamba
                per += D * 2 * m.d_inner + m.d_inner * (m.dt_rank + 2 * m.d_state)
                per += m.dt_rank * m.d_inner + m.d_inner * m.d_state + m.d_inner * D
            if kind["mixer"] == "mlstm":
                xc = self.xlstm
                Di = xc.d_inner_m
                per += D * 2 * Di + 3 * Di * Di + Di * D
            if kind["mixer"] == "slstm":
                xc = self.xlstm
                dff = int(D * xc.proj_factor_s)
                per += D * 4 * D + self.n_heads * (D // self.n_heads) * 4 * (D // self.n_heads)
                per += D * 2 * dff + dff * D
            if kind["ffn"] == "mlp":
                per += D * self.d_ff * (3 if self.activation == "swiglu" else 2)
            if kind["ffn"] == "moe":
                mo = self.moe
                per += D * mo.n_experts + mo.n_experts * 3 * D * mo.d_ff
                per += mo.n_shared_experts * 3 * D * mo.d_ff
            total += per * self.n_superblocks
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k + shared experts only)."""
        if self.moe is None:
            return self.param_count()
        full = self.param_count()
        mo = self.moe
        n_moe = sum(1 for i in range(self.superblock) if self.layer_kind(i)["ffn"] == "moe")
        n_moe *= self.n_superblocks
        unused = n_moe * (mo.n_experts - mo.top_k) * 3 * self.d_model * mo.d_ff
        return full - unused


# ---------------------------------------------------------------------------


class LM:
    """Pure-function model: ``init`` → params/axes, ``loss``/``prefill``/``decode_step``."""

    def __init__(self, cfg: ModelConfig, ctx: ShardingCtx):
        self.cfg = cfg
        self.ctx = ctx

    # ---------------------------------------------------------------- init --
    def init(self, key: jax.Array, abstract: bool = False):
        pb = self._build(key, abstract)
        return pb.params, pb.axes

    def matmul_leaves(self) -> frozenset[tuple[str, ...]]:
        """Paths of the param leaves the forward pass reads only as bf16
        matmul operands (declared ``matmul=True`` where they are built)."""
        return frozenset(self._build(jax.random.key(0), abstract=True).matmul)

    def _build(self, key: jax.Array, abstract: bool) -> L.ParamBuilder:
        cfg = self.cfg
        pb = L.ParamBuilder(key, cfg.param_dtype, abstract=abstract)
        L.init_embedding(pb, cfg.vocab, cfg.d_model)
        nsb = cfg.n_superblocks

        for i in range(cfg.superblock):
            kind = cfg.layer_kind(i)
            sb = pb.scope(f"layer{i}")
            if kind["mixer"] == "attn":
                init_attention(sb.scope("attn"), cfg.d_model, cfg.head_layout,
                               stack=nsb, qk_norm=cfg.qk_norm)
                self._init_norm(sb, "norm_attn", nsb)
            elif kind["mixer"] == "mamba":
                init_mamba(sb.scope("mamba"), cfg.mamba, stack=nsb)
                self._init_norm(sb, "norm_mixer", nsb)
            elif kind["mixer"] == "mlstm":
                init_mlstm(sb.scope("mlstm"), cfg.xlstm, stack=nsb)
                self._init_norm(sb, "norm_mixer", nsb)
            elif kind["mixer"] == "slstm":
                init_slstm(sb.scope("slstm"), cfg.xlstm, stack=nsb)
                self._init_norm(sb, "norm_mixer", nsb)
            if kind["ffn"] == "mlp":
                L.init_mlp(sb.scope("mlp"), cfg.d_model, cfg.d_ff, stack=nsb,
                           activation=cfg.activation)
                self._init_norm(sb, "norm_ffn", nsb)
            elif kind["ffn"] == "moe":
                init_moe(sb.scope("moe"), cfg.moe, stack=nsb)
                self._init_norm(sb, "norm_ffn", nsb)

        fb = pb.scope("final")
        self._init_norm(fb, "norm_out", None)
        if cfg.frontend == "vision":
            pb.param("patch_proj", (cfg.frontend_dim, cfg.d_model), ("patch", "embed"),
                     matmul=True)
        elif cfg.frontend == "audio":
            pb.param("frame_proj", (cfg.frontend_dim, cfg.d_model), ("patch", "embed"),
                     matmul=True)
        return pb

    def _init_norm(self, pb: L.ParamBuilder, name: str, stack: int | None):
        lead = (stack,) if stack is not None else ()
        lax_ = ("layers",) if stack is not None else ()
        sub = pb.scope(name)
        sub.param("w", lead + (self.cfg.d_model,), lax_ + ("embed_nosplit",), init="ones")
        if self.cfg.norm_type == "ln":
            sub.param("b", lead + (self.cfg.d_model,), lax_ + ("embed_nosplit",), init="zeros")

    def _norm(self, p, x):
        if self.cfg.norm_type == "ln":
            return L.layer_norm(x, p["w"], p["b"], self.cfg.norm_eps)
        return L.rms_norm(x, p["w"], self.cfg.norm_eps)

    # ------------------------------------------------------------- embed --
    def _embed_inputs(self, params, batch) -> tuple[jax.Array, jax.Array]:
        """Returns (x (B,S,D), positions (B,S))."""
        cfg, ctx = self.cfg, self.ctx
        if cfg.frontend == "audio":
            # encoder-only masked prediction: inputs are frames alone
            frames = batch["frames"].astype(jnp.bfloat16)    # (B,S,frontend_dim)
            x = L.dot(frames, params["frame_proj"]).astype(jnp.bfloat16)
        else:
            x = L.embed(params, batch["tokens"], ctx)
        if cfg.frontend == "vision":
            patches = batch["patches"].astype(jnp.bfloat16)  # (B,P,frontend_dim)
            pe = L.dot(patches, params["patch_proj"]).astype(x.dtype)
            x = jnp.concatenate([pe, x], axis=1)
        B, S = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        x = ctx.constrain(x, ("batch", "seq", "embed_nosplit"))
        return x, positions

    # -------------------------------------------------------------- block --
    def _superblock(self, sb_params: dict, x: jax.Array, positions: jax.Array,
                    mode: str, caches: dict | None):
        """Run one superblock.  mode: train | prefill | decode.
        ``caches``: this superblock's cache slice (decode/prefill-out)."""
        cfg, ctx = self.cfg, self.ctx
        aux_total = jnp.float32(0)
        new_caches: dict = {}
        for i in range(cfg.superblock):
            kind = cfg.layer_kind(i)
            p = sb_params[f"layer{i}"]
            if kind["mixer"] == "attn":
                h = self._norm(p["norm_attn"], x)
                attn_out, kv = self._attention(p["attn"], h, positions, mode, caches)
                if kv is not None:
                    new_caches.update(kv)
                x = x + attn_out
            else:
                h = self._norm(p["norm_mixer"], x)
                if kind["mixer"] == "mamba":
                    st = None if caches is None else caches.get(f"mamba{i}")
                    out, st_new = mamba_mix(p["mamba"], h, ctx, cfg.mamba_chunk, st)
                    if caches is not None or mode != "train":
                        new_caches[f"mamba{i}"] = st_new
                elif kind["mixer"] == "mlstm":
                    st = None if caches is None else caches.get(f"mlstm{i}")
                    out, st_new = mlstm_mix(p["mlstm"], h, ctx, cfg.mamba_chunk, st)
                    if caches is not None or mode != "train":
                        new_caches[f"mlstm{i}"] = st_new
                else:
                    st = None if caches is None else caches.get(f"slstm{i}")
                    out, st_new = slstm_mix(p["slstm"], h, ctx, st)
                    if caches is not None or mode != "train":
                        new_caches[f"slstm{i}"] = st_new
                x = x + out
            if kind["ffn"] == "mlp":
                h = self._norm(p["norm_ffn"], x)
                x = x + L.mlp(p["mlp"], h, ctx, cfg.activation)
            elif kind["ffn"] == "moe":
                h = self._norm(p["norm_ffn"], x)
                out, aux = moe_ffn(p["moe"], h, cfg.moe, ctx)
                aux_total = aux_total + aux
                x = x + out
        return x, aux_total, new_caches

    def _attention(self, p, h, positions, mode, caches):
        cfg, ctx = self.cfg, self.ctx
        layout = cfg.head_layout
        q, k, v = project_qkv(p, h, positions, layout, ctx, cfg.rope_theta,
                              use_rope=cfg.family != "audio")
        if mode in ("train", "prefill"):
            attn = flash_attention(q, k, v, causal=cfg.causal,
                                   q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk)
            out = output_proj(p, attn, layout, ctx)
            kv = None
            if mode == "prefill":
                kv = {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}
            return out, kv
        # decode: one token; caches carry (B, Smax, Ke, hd)
        k_cache, v_cache = caches["k"], caches["v"]
        pos = caches["pos"]  # scalar int32 current length
        k_cache, v_cache = update_kv_cache(k_cache, v_cache, k, v, pos)
        cache_len = pos + 1
        if self._seq_sharded_decode(k_cache.shape):
            attn = flash_decode_shardmap(q, k_cache, v_cache,
                                         jnp.full((q.shape[0],), cache_len, jnp.int32), ctx)
        else:
            attn = decode_attention(q, k_cache, v_cache,
                                    jnp.full((q.shape[0],), cache_len, jnp.int32))
        out = output_proj(p, attn, layout, ctx)
        return out, {"k": k_cache, "v": v_cache}

    def _seq_sharded_decode(self, cache_shape) -> bool:
        """Shard the KV cache on sequence when batch can't cover the dp axes
        (or when the config forces it — a serving-latency optimization)."""
        if self.cfg.force_seq_sharded_decode:
            return True
        B = cache_shape[0]
        dp = self.ctx.data_parallelism
        return B % max(dp, 1) != 0 or B < dp

    # ------------------------------------------------------------ forward --
    def _run_stack(self, params, x, positions, mode, caches=None):
        """Scan over superblocks.  caches: pytree with leading (nsb,) dim.

        Decode carries the stacked caches through the scan *carry* with
        per-layer dynamic slice/update — passing them as scan xs/ys makes
        XLA rewrite the entire multi-GB cache every token (measured 1.08 TB
        per token on deepseek decode_32k; see EXPERIMENTS.md §Perf)."""
        cfg = self.cfg
        sb_keys = [k for k in params if k.startswith("layer")]
        sb_params = {k: params[k] for k in sb_keys}
        decode = mode == "decode"

        pos = None
        if decode:
            pos = caches["pos"]
            caches = {k: v for k, v in caches.items() if k != "pos"}

        def body(carry, scanned):
            if decode:
                xc, aux, cache_full, i = carry
                sbp = scanned
                cache_slice = jax.tree.map(
                    lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False),
                    cache_full)
                cache_slice["pos"] = pos
                xo, aux_sb, new_cache = self._superblock(sbp, xc, positions, mode, cache_slice)
                cache_full = jax.tree.map(
                    lambda full, new: jax.lax.dynamic_update_index_in_dim(
                        full, new.astype(full.dtype), i, 0),
                    cache_full, new_cache)
                return (xo, aux + aux_sb, cache_full, i + 1), None
            xc, aux = carry
            sbp, cache_slice = scanned
            xo, aux_sb, new_cache = self._superblock(sbp, xc, positions, mode, cache_slice)
            return (xo, aux + aux_sb), new_cache

        if cfg.remat and not decode:
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if cfg.remat_policy == "dots" else None)
            body = jax.checkpoint(body, policy=policy, prevent_cse=False)

        if cfg.scan_layers:
            if decode:
                (x, aux, out_caches, _), _ = jax.lax.scan(
                    body, (x, jnp.float32(0), caches, jnp.int32(0)), sb_params)
            else:
                (x, aux), out_caches = jax.lax.scan(
                    body, (x, jnp.float32(0)), (sb_params, caches))
        else:
            aux = jnp.float32(0)
            if decode:
                out_caches = caches
                for i in range(cfg.n_superblocks):
                    sbp = jax.tree.map(lambda t: t[i], sb_params)
                    (x, aux, out_caches, _), _ = body((x, aux, out_caches, jnp.int32(i)), sbp)
            else:
                out_list = []
                for i in range(cfg.n_superblocks):
                    sbp = jax.tree.map(lambda t: t[i], sb_params)
                    csl = None if caches is None else jax.tree.map(lambda t: t[i], caches)
                    (x, aux), oc = body((x, aux), (sbp, csl))
                    out_list.append(oc)
                out_caches = (jax.tree.map(lambda *ts: jnp.stack(ts), *out_list)
                              if out_list and out_list[0] else None)
        x = self._norm(params["final"]["norm_out"], x)
        return x, aux, out_caches

    # -------------------------------------------------------------- modes --
    def loss_fn(self, params, batch):
        """Training loss. batch: tokens (B,S), labels (B,S), [mask, patches, frames]."""
        cfg, ctx = self.cfg, self.ctx
        x, positions = self._embed_inputs(params, batch)
        x, aux, _ = self._run_stack(params, x, positions, "train", self._empty_caches_like(x))
        labels = batch["labels"]
        mask = batch.get("mask")
        if cfg.frontend == "vision":  # loss over text positions only
            P = cfg.frontend_tokens
            x = x[:, P:]
        nll = L.chunked_lm_loss(params, x, labels, ctx, cfg.lm_loss_chunk, mask)
        loss = nll + (0.01 * aux if cfg.moe is not None else 0.0)
        return loss, {"nll": nll, "aux": aux}

    def prefill(self, params, batch, lengths=None):
        """Forward building decode state; returns (next_token_logits, caches).

        ``lengths`` (B,) int32: real tokens per right-padded row; each row's
        logits come from its own last real position (default: position -1).
        """
        cfg, ctx = self.cfg, self.ctx
        x, positions = self._embed_inputs(params, batch)
        caches = self._empty_caches_like(x)
        x, _, out_caches = self._run_stack(params, x, positions, "prefill", caches)
        if lengths is None:
            last = x[:, -1:]
        else:
            idx = jnp.clip(jnp.asarray(lengths, jnp.int32) - 1, 0, x.shape[1] - 1)
            last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        lgts = L.logits(params, last, ctx)[:, 0]
        return lgts, out_caches

    def decode_step(self, params, caches, tokens, pos, return_logits: bool = False):
        """tokens (B,1) int32, pos scalar int32 → (next_tokens (B,), new caches)."""
        cfg, ctx = self.cfg, self.ctx
        x = L.embed(params, tokens, ctx)
        B = x.shape[0]
        pos = jnp.asarray(pos, jnp.int32)
        positions = jnp.full((B, 1), pos, jnp.int32)
        withpos = {**{k: v for k, v in caches.items() if k != "pos"}, "pos": pos}
        x, _, new_caches = self._run_stack(params, x, positions, "decode", withpos)
        lgts = L.logits(params, x, ctx)[:, 0]
        next_tokens = jnp.argmax(lgts, axis=-1).astype(jnp.int32)
        out_caches = {**new_caches, "pos": pos + 1}
        if return_logits:
            return next_tokens, out_caches, lgts
        return next_tokens, out_caches

    # -------------------------------------------------------------- caches --
    def _empty_caches_like(self, x) -> dict | None:
        """Scan requires xs pytrees even in train mode (None works)."""
        return None

    def init_caches(self, batch_size: int, max_seq: int, dtype=jnp.bfloat16,
                    seq_sharded: bool | None = None) -> dict:
        """Decode caches with leading (n_superblocks,) for the layer scan."""
        cfg = self.cfg
        nsb = cfg.n_superblocks
        layout = cfg.head_layout
        caches: dict[str, Any] = {}
        for i in range(cfg.superblock):
            kind = cfg.layer_kind(i)
            if kind["mixer"] == "attn":
                shape = (nsb, batch_size, max_seq, layout.eff_kv, layout.head_dim)
                caches["k"] = jnp.zeros(shape, dtype)
                caches["v"] = jnp.zeros(shape, dtype)
            elif kind["mixer"] == "mamba":
                st = mamba_init_state(batch_size, cfg.mamba)
                caches[f"mamba{i}"] = jax.tree.map(
                    lambda t: jnp.broadcast_to(t[None], (nsb, *t.shape)), st)
            elif kind["mixer"] == "mlstm":
                st = mlstm_init_state(batch_size, cfg.xlstm)
                caches[f"mlstm{i}"] = jax.tree.map(
                    lambda t: jnp.broadcast_to(t[None], (nsb, *t.shape)), st)
            elif kind["mixer"] == "slstm":
                st = slstm_init_state(batch_size, cfg.d_model)
                caches[f"slstm{i}"] = jax.tree.map(
                    lambda t: jnp.broadcast_to(t[None], (nsb, *t.shape)), st)
        caches["pos"] = jnp.int32(0)
        return caches

    def cache_logical_axes(self, seq_sharded: bool) -> dict:
        """Logical axes for cache pytree leaves (for pjit in/out shardings)."""
        cfg = self.cfg
        kv_seq = "kv_seq" if seq_sharded else "seq"
        batch = None if seq_sharded else "batch"
        axes: dict[str, Any] = {}
        for i in range(cfg.superblock):
            kind = cfg.layer_kind(i)
            if kind["mixer"] == "attn":
                axes["k"] = ("layers", batch, kv_seq, "kv_heads", "head_dim")
                axes["v"] = ("layers", batch, kv_seq, "kv_heads", "head_dim")
            elif kind["mixer"] == "mamba":
                axes[f"mamba{i}"] = {
                    "h": ("layers", batch, "inner", "state"),
                    "conv": ("layers", batch, "conv", "inner"),
                }
            elif kind["mixer"] == "mlstm":
                axes[f"mlstm{i}"] = {
                    "C": ("layers", batch, "heads_nosplit", "head_dim", "head_dim"),
                    "n": ("layers", batch, "heads_nosplit", "head_dim"),
                    "m": ("layers", batch, "heads_nosplit"),
                }
            elif kind["mixer"] == "slstm":
                axes[f"slstm{i}"] = {k: ("layers", batch, "inner")
                                     for k in ("c", "n", "h", "m")}
        axes["pos"] = ()
        return axes
