"""Mixture-of-Experts: top-k router + capacity-bounded sort-based dispatch.

Distribution (DESIGN.md §4): activations between blocks are TP-replicated
over ``model``, so each model shard already *has* every token.  Experts are
sharded over ``model``; each shard locally gathers the tokens routed to its
local experts (argsort grouping, fixed capacity, dropped overflow), runs the
expert FFNs as one batched einsum, scatters back weighted by router probs,
and a single ``psum`` over ``model`` combines shards — the same collective
pattern as Megatron TP, with **no all-to-all** on the critical path.

Memory never materializes the (B,S,E,C) one-hot dispatch tensor that the
GShard-style formulation needs — at E=128, k=8 that tensor is ~4e13 elements.
The sort-based grouping is O(N·k) and is also the *numerics-exact* approach
(capacity drops aside, which are standard).

FSDP composition: expert weights are additionally sharded over ``data`` on
d_model; the shard_map body all-gathers the current layer's local-expert
weights over ``data`` just-in-time (classic FSDP; re-gathered in backward
under remat).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .layers import ParamBuilder, swiglu


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_model: int
    d_ff: int                   # per-expert hidden
    n_shared_experts: int = 0   # dense "shared expert" path (DeepSeek/Moonlight)
    capacity_factor: float = 1.25
    router_jitter: float = 0.0


def init_moe(pb: ParamBuilder, cfg: MoEConfig, stack: int | None = None) -> None:
    lead = (stack,) if stack is not None else ()
    lax_ = ("layers",) if stack is not None else ()
    E, D, F = cfg.n_experts, cfg.d_model, cfg.d_ff
    # router weights replicated: an experts-sharded router drags a softmax +
    # top_k across the model axis into EVERY layer (measured 0.4 s/step of
    # collectives on moonshot train_4k — §Perf C-cell)
    pb.param("w_router", lead + (D, E), lax_ + ("embed_nosplit", "experts_rep"), scale=0.02)
    pb.param("w_gate", lead + (E, D, F), lax_ + ("experts", "embed", "ff_nosplit"), matmul=True)
    pb.param("w_up", lead + (E, D, F), lax_ + ("experts", "embed", "ff_nosplit"), matmul=True)
    pb.param("w_down", lead + (E, F, D), lax_ + ("experts", "ff_nosplit", "embed"), matmul=True)
    if cfg.n_shared_experts:
        Fs = F * cfg.n_shared_experts
        pb.param("ws_gate", lead + (D, Fs), lax_ + ("embed", "ff"), matmul=True)
        pb.param("ws_up", lead + (D, Fs), lax_ + ("embed", "ff"), matmul=True)
        pb.param("ws_down", lead + (Fs, D), lax_ + ("ff", "embed"), matmul=True)


def _group_by_expert(expert_idx: jax.Array, weights: jax.Array, n_local: int, capacity: int):
    """Sort-based grouping of N·k routed assignments into (n_local, capacity)
    token slots.  ``expert_idx``: (N, k) local expert id or -1; returns
    (slot_token[n_local*capacity], slot_weight[n_local*capacity]) where
    slot_token indexes the flat token list (N) and -1 marks empty slots.
    """
    N, k = expert_idx.shape
    flat_e = expert_idx.reshape(-1)                       # (N*k,)
    flat_w = weights.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(N, dtype=jnp.int32), k)
    # stable sort by expert id; -1 (not-local) sorts first
    order = jnp.argsort(flat_e, stable=True)
    se, sw, st = flat_e[order], flat_w[order], flat_t[order]
    # position of each assignment within its expert's run
    same = jnp.cumsum(jnp.ones_like(se), dtype=jnp.int32) - 1
    run_start = jnp.where(se != jnp.concatenate([jnp.array([-2], se.dtype), se[:-1]]),
                          same, -1)
    run_start = jax.lax.associative_scan(jnp.maximum, run_start)
    pos_in_run = same - run_start
    keep = (se >= 0) & (pos_in_run < capacity)
    slot = jnp.where(keep, se * capacity + pos_in_run, n_local * capacity)  # overflow slot
    slot_token = jnp.full((n_local * capacity + 1,), -1, jnp.int32).at[slot].set(
        jnp.where(keep, st, -1))[:-1]
    slot_weight = jnp.zeros((n_local * capacity + 1,), jnp.float32).at[slot].set(
        jnp.where(keep, sw, 0.0))[:-1]
    return slot_token, slot_weight


def _expert_ffn(x_g, wg, wu, wd):
    """x_g: (E_loc, C, D); weights (E_loc, D, F)/(E_loc, F, D)."""
    h = swiglu(
        jnp.einsum("ecd,edf->ecf", x_g.astype(jnp.bfloat16), wg.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32),
        jnp.einsum("ecd,edf->ecf", x_g.astype(jnp.bfloat16), wu.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32),
    )
    return jnp.einsum("ecf,efd->ecd", h.astype(jnp.bfloat16), wd.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def moe_ffn(params: dict, x: jax.Array, cfg: MoEConfig, ctx) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) TP-replicated / batch-sharded. Returns (out, aux_loss)."""
    mesh = ctx.mesh
    model_ax = "model" if "model" in mesh.axis_names else None
    tp = mesh.shape[model_ax] if model_ax else 1
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    fsdp_ax = "data" if "data" in mesh.axis_names else None
    E, k = cfg.n_experts, cfg.top_k
    assert E % tp == 0, (E, tp)
    E_loc = E // tp
    B, S, D = x.shape
    dp = int(np.prod([mesh.shape[a] for a in batch_axes])) if batch_axes else 1
    if B % dp:  # decode with batch < data parallelism: replicate over data
        batch_axes, dp = (), 1
    N_loc = (B // dp) * S
    capacity = max(8, int(np.ceil(N_loc * k * cfg.capacity_factor / E)))

    # ---- router (replicated, f32) — aux load-balancing loss (Switch-style)
    router_logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                               params["w_router"].astype(jnp.float32))
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)                     # (B,S,k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)
    # aux loss: E * sum_e (fraction_tokens_e * mean_prob_e)
    counts = jnp.mean(jnp.sum(jax.nn.one_hot(top_e, E, dtype=jnp.float32), axis=2), axis=(0, 1))
    aux = E * jnp.sum(counts * jnp.mean(probs, axis=(0, 1)))

    in_x = P(batch_axes if batch_axes else None, None, None)

    def body(x_l, te_l, tw_l, wg, wu, wd):
        # gather this model-shard's expert weights over the FSDP axis
        if fsdp_ax is not None:
            wg = jax.lax.all_gather(wg, fsdp_ax, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp_ax, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp_ax, axis=2, tiled=True)
        b_l, s_l, d = x_l.shape
        n = b_l * s_l
        xf = x_l.reshape(n, d)
        shard = jax.lax.axis_index(model_ax) if model_ax else 0
        lo = shard * E_loc
        te = te_l.reshape(n, k)
        local = te - lo
        local = jnp.where((local >= 0) & (local < E_loc), local, -1)
        slot_token, slot_weight = _group_by_expert(local, tw_l.reshape(n, k), E_loc, capacity)
        safe_tok = jnp.maximum(slot_token, 0)
        x_g = xf[safe_tok].reshape(E_loc, capacity, d)
        x_g = jnp.where((slot_token >= 0).reshape(E_loc, capacity, 1), x_g, 0.0)
        y_g = _expert_ffn(x_g, wg, wu, wd)                      # (E_loc, C, D) f32
        y_g = y_g * slot_weight.reshape(E_loc, capacity, 1)
        y = jnp.zeros((n, d), jnp.float32).at[safe_tok.reshape(-1)].add(
            jnp.where((slot_token >= 0).reshape(-1, 1), y_g.reshape(-1, d), 0.0))
        y = y.astype(x_l.dtype)  # psum in bf16: halves the TP collective bytes
        if model_ax is not None:
            y = jax.lax.psum(y, model_ax)
        return y.reshape(b_l, s_l, d)

    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(in_x, in_x, in_x,
                  P(model_ax, fsdp_ax, None), P(model_ax, fsdp_ax, None), P(model_ax, None, fsdp_ax)),
        out_specs=in_x,
        check_vma=False,
    )(x, top_e.astype(jnp.int32), top_w.astype(jnp.float32),
      params["w_gate"], params["w_up"], params["w_down"])

    if cfg.n_shared_experts:
        h = swiglu(
            jnp.einsum("bsd,df->bsf", x.astype(jnp.bfloat16), params["ws_gate"].astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32),
            jnp.einsum("bsd,df->bsf", x.astype(jnp.bfloat16), params["ws_up"].astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32),
        )
        h = ctx.constrain(h.astype(x.dtype), ("batch", "seq", "ff"))
        shared = jnp.einsum("bsf,fd->bsd", h.astype(jnp.bfloat16),
                            params["ws_down"].astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
        out = out + shared.astype(out.dtype)

    return ctx.constrain(out, ("batch", "seq", "embed_nosplit")), aux
