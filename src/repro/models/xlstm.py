"""xLSTM blocks (arXiv:2405.04517): mLSTM (matrix memory) + sLSTM (scalar).

mLSTM is a linear-attention-class mixer: C_t = f_t·C_{t-1} + i_t·v_t k_tᵀ,
h_t = (C_t q_t) / max(|n_tᵀ q_t|, 1).  Training uses the **chunkwise
stabilized form** (GLA-style): a lax.scan carries (C, n, m) across chunks —
intra-chunk contributions use log-space cumulative gates with the running
max stabilizer m (exactly the paper's exponential-gating trick), so
exp() never overflows.  Decode is the O(1) recurrence — xLSTM runs the
``long_500k`` cell for this reason.

sLSTM keeps per-head scalar memories with a block-diagonal recurrent matrix
R_h; its recurrence is inherently sequential → lax.scan over time.  It's the
minority block (1:3 here), and its FLOPs are negligible; we keep its
recurrence replicated over ``model`` (documented in DESIGN.md §4) while all
projections are TP-sharded.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .layers import ParamBuilder, layer_norm


@dataclass(frozen=True)
class XLSTMConfig:
    d_model: int
    n_heads: int
    proj_factor_m: float = 2.0   # mLSTM up-projection
    proj_factor_s: float = 4 / 3  # sLSTM ffn factor
    conv_k: int = 4

    @property
    def d_inner_m(self) -> int:
        return int(self.d_model * self.proj_factor_m)

    @property
    def head_dim_m(self) -> int:
        return self.d_inner_m // self.n_heads

    @property
    def d_ff_s(self) -> int:
        """sLSTM ffn hidden, rounded up to 128 for TP divisibility (the 2730
        the exact 4/3 factor gives cannot shard 16 ways; noted in DESIGN)."""
        raw = int(self.d_model * self.proj_factor_s)
        return -(-raw // 128) * 128 if raw >= 128 else raw


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------


def init_mlstm(pb: ParamBuilder, cfg: XLSTMConfig, stack: int | None = None) -> None:
    lead = (stack,) if stack is not None else ()
    lax_ = ("layers",) if stack is not None else ()
    D, Di, H = cfg.d_model, cfg.d_inner_m, cfg.n_heads
    pb.param("w_up", lead + (D, 2 * Di), lax_ + ("embed", "inner"), matmul=True)
    pb.param("w_q", lead + (Di, Di), lax_ + ("inner", "inner_nosplit"), matmul=True)
    pb.param("w_k", lead + (Di, Di), lax_ + ("inner", "inner_nosplit"), matmul=True)
    pb.param("w_v", lead + (Di, Di), lax_ + ("inner", "inner_nosplit"), matmul=True)
    pb.param("w_if", lead + (Di, 2 * H), lax_ + ("inner", "heads_nosplit"), scale=0.02)
    pb.param("b_if", lead + (2 * H,), lax_ + ("heads_nosplit",), init="zeros")
    pb.param("ln_w", lead + (Di,), lax_ + ("inner",), init="ones")
    pb.param("ln_b", lead + (Di,), lax_ + ("inner",), init="zeros")
    pb.param("w_down", lead + (Di, D), lax_ + ("inner", "embed"), matmul=True)


def _mlstm_chunk(carry, inp, H, dh):
    """One chunk of the stabilized chunkwise mLSTM recurrence.

    carry: C (B,H,dh,dh) f32, n (B,H,dh), m (B,H)
    inp:   q,k,v (B,c,H,dh) bf16; logi, logf (B,c,H) f32
    """
    C, n, m = carry
    q, k, v, logi, logf = inp
    B, c = q.shape[0], q.shape[1]
    # cumulative forget products within the chunk (log space)
    F = jnp.cumsum(logf, axis=1)                      # (B,c,H): log prod_{1..t} f
    # stabilizer: per chunk running max of (m_prev + F_t ... , logi + ...)
    # intra-chunk decay for pair (t, s<=t): F_t - F_s + logi_s
    a = F + m[:, None]                                # log weight of initial state at t
    b_ts = logi - F                                   # (B,c,H): per-source term
    m_new = jnp.maximum(jnp.max(a, axis=1), m)        # (B,H) coarse stabilizer
    m_new = jnp.maximum(m_new, jnp.max(logi + 0.0, axis=1))

    # inter-chunk: h_inter_t = exp(a_t - m_new) * (C q_t)
    # C is [key, value]-indexed (update: k⊗v) — contract the KEY dim with q
    qf = q.astype(jnp.float32)
    inter = jnp.einsum("bhde,bthd->bthe", C, qf)      # (B,c,H,dh)
    inter_n = jnp.einsum("bhd,bthd->bth", n, qf)
    w_inter = jnp.exp(a - m_new[:, None])[..., None]  # (B,c,H,1)

    # intra-chunk: weights exp(F_t - F_s + logi_s - m_new) for s<=t
    logw = F[:, :, None] - F[:, None, :] + logi[:, None, :]  # (B,t,s,H)
    tri = jnp.tril(jnp.ones((c, c), bool))
    logw = jnp.where(tri[None, :, :, None], logw, -jnp.inf)
    w = jnp.exp(logw - m_new[:, None, None])          # (B,t,s,H)
    scores = jnp.einsum("bthd,bshd->btsh", qf, k.astype(jnp.float32))
    wscore = w * scores
    intra = jnp.einsum("btsh,bshd->bthd", wscore, v.astype(jnp.float32))
    intra_n = jnp.sum(wscore, axis=2)                 # (B,t,H)

    h_num = inter * w_inter + intra
    h_den = inter_n * w_inter[..., 0] + intra_n
    # xLSTM eq. (15): in stabilized space the |n| floor is exp(-m), not 1 —
    # a constant floor binds differently for different stabilizer
    # trajectories and breaks chunked==sequential equivalence.
    floor = jnp.exp(-m_new)[:, None, :]
    h = h_num / jnp.maximum(jnp.abs(h_den), floor)[..., None]

    # state update to end of chunk
    wk = jnp.exp(logi - F + F[:, -1:] - m_new[:, None])      # (B,c,H)
    C_new = C * jnp.exp(F[:, -1] + m - m_new)[..., None, None] + jnp.einsum(
        "bsh,bshd,bshe->bhde", wk, k.astype(jnp.float32), v.astype(jnp.float32))
    n_new = n * jnp.exp(F[:, -1] + m - m_new)[..., None] + jnp.einsum(
        "bsh,bshd->bhd", wk, k.astype(jnp.float32))
    return (C_new, n_new, m_new), h


def mlstm_mix(params: dict, x: jax.Array, ctx, chunk: int = 64, state: dict | None = None):
    """x: (B,S,D) -> (B,S,D); state carries (C,n,m,conv-free) for decode."""
    B, S, D = x.shape
    Di = params["w_q"].shape[-1]
    H = params["w_if"].shape[-1] // 2
    dh = Di // H

    up = jnp.einsum("bsd,de->bse", x.astype(jnp.bfloat16), params["w_up"].astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
    xin, z = jnp.split(up, 2, axis=-1)
    xin = ctx.constrain(xin.astype(jnp.bfloat16), ("batch", "seq", "inner"))
    z = ctx.constrain(z.astype(jnp.bfloat16), ("batch", "seq", "inner"))

    def proj(w):
        return jnp.einsum("bse,ef->bsf", xin, w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32).reshape(B, S, H, dh)

    q, k, v = proj(params["w_q"]), proj(params["w_k"]), proj(params["w_v"])
    k = k / jnp.sqrt(jnp.float32(dh))
    gates = jnp.einsum("bse,eg->bsg", xin.astype(jnp.float32),
                       params["w_if"].astype(jnp.float32)) + params["b_if"].astype(jnp.float32)
    logi, logf = gates[..., :H], jax.nn.log_sigmoid(gates[..., H:])

    if state is None:
        C0 = jnp.zeros((B, H, dh, dh), jnp.float32)
        n0 = jnp.zeros((B, H, dh), jnp.float32)
        m0 = jnp.full((B, H), -1e30, jnp.float32)
    else:
        C0, n0, m0 = state["C"], state["n"], state["m"]

    if S == 1:
        (C1, n1, m1), h = _mlstm_chunk((C0, n0, m0),
                                       (q, k, v, logi, logf), H, dh)
        new_state = {"C": C1, "n": n1, "m": m1}
        hs = h
    else:
        nc = S // chunk if S % chunk == 0 else 1
        c = S // nc
        r = lambda t: t.reshape(B, nc, c, *t.shape[2:]).swapaxes(0, 1)
        def step(carry, inp):
            return _mlstm_chunk(carry, inp, H, dh)
        (C1, n1, m1), hs = jax.lax.scan(step, (C0, n0, m0),
                                        (r(q), r(k), r(v), r(logi), r(logf)))
        hs = hs.swapaxes(0, 1).reshape(B, S, H, dh)
        new_state = {"C": C1, "n": n1, "m": m1}

    h = hs.reshape(B, S, Di)
    h = layer_norm(h.astype(jnp.float32), params["ln_w"], params["ln_b"]).astype(jnp.bfloat16)
    h = h * jax.nn.silu(z.astype(jnp.float32)).astype(jnp.bfloat16)
    h = ctx.constrain(h, ("batch", "seq", "inner"))
    out = jnp.einsum("bse,ed->bsd", h, params["w_down"].astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return ctx.constrain(out.astype(x.dtype), ("batch", "seq", "embed_nosplit")), new_state


def mlstm_init_state(B: int, cfg: XLSTMConfig) -> dict:
    H, dh = cfg.n_heads, cfg.head_dim_m
    return {
        "C": jnp.zeros((B, H, dh, dh), jnp.float32),
        "n": jnp.zeros((B, H, dh), jnp.float32),
        "m": jnp.full((B, H), -1e30, jnp.float32),
    }


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------


def init_slstm(pb: ParamBuilder, cfg: XLSTMConfig, stack: int | None = None) -> None:
    lead = (stack,) if stack is not None else ()
    lax_ = ("layers",) if stack is not None else ()
    D, H = cfg.d_model, cfg.n_heads
    dh = D // H
    pb.param("w_gates", lead + (D, 4 * D), lax_ + ("embed", "inner"), matmul=True)
    # fan-in init (std 1/sqrt(dh)): a fixed 0.4 made gradients through the
    # time scan overflow to NaN within 1024 steps at 4 heads of 256
    pb.param("r_gates", lead + (H, dh, 4 * dh), lax_ + ("heads_nosplit", "head_dim", "head_dim"))
    pb.param("b_gates", lead + (4 * D,), lax_ + ("inner",), init="zeros")
    pb.param("ln_w", lead + (D,), lax_ + ("embed_nosplit",), init="ones")
    pb.param("ln_b", lead + (D,), lax_ + ("embed_nosplit",), init="zeros")
    dff = cfg.d_ff_s
    pb.param("w_ff1", lead + (D, 2 * dff), lax_ + ("embed", "ff"), matmul=True)
    pb.param("w_ff2", lead + (dff, D), lax_ + ("ff", "embed"), matmul=True)


def _slstm_scan(pre, st0, r_gates, H: int):
    """The sequential time scan (factored so it can run inside shard_map)."""
    B, S, G4 = pre.shape
    D = G4 // 4
    dh = D // H

    def step(st, pre_t):
        # recurrent contribution: block-diagonal per head
        hprev = st["h"].reshape(B, H, dh)
        rec = jnp.einsum("bhd,hdg->bhg", hprev, r_gates.astype(jnp.float32))
        g = pre_t + rec.reshape(B, 4 * D)
        zi, ii, fi, oi = jnp.split(g, 4, axis=-1)
        zt = jnp.tanh(zi)
        ot = jax.nn.sigmoid(oi)
        logf = jax.nn.log_sigmoid(fi)
        m_new = jnp.maximum(logf + st["m"], ii)
        i_ = jnp.exp(ii - m_new)
        f_ = jnp.exp(logf + st["m"] - m_new)
        c_new = f_ * st["c"] + i_ * zt
        n_new = f_ * st["n"] + i_
        h_new = ot * c_new / jnp.maximum(n_new, 1.0)
        return {"c": c_new, "n": n_new, "h": h_new, "m": m_new}, h_new

    if S == 1:
        st1, h = step(st0, pre[:, 0])
        return st1, h[:, None]
    st1, hs = jax.lax.scan(step, st0, pre.swapaxes(0, 1))
    return st1, hs.swapaxes(0, 1)


def _batch_shard_axes(ctx, B: int) -> tuple:
    import numpy as _np
    spec = ctx.spec(("batch",))
    if not len(spec) or spec[0] is None:
        return ()
    ax = spec[0] if isinstance(spec[0], tuple) else (spec[0],)
    n = int(_np.prod([ctx.mesh.shape[a] for a in ax]))
    return ax if n > 1 and B % n == 0 else ()


def slstm_mix(params: dict, x: jax.Array, ctx, state: dict | None = None):
    """Sequential sLSTM over time.  x: (B,S,D).  State: {c,n,h,m} each (B,D).

    The time scan runs inside shard_map over the batch axes: under plain
    GSPMD the r_gates weight-gradient gets all-reduced *every time step*
    (measured 0.2 TB/step on xlstm train_4k — §Perf B-cell); per-shard
    accumulation syncs it once at the boundary instead.
    """
    from jax.sharding import PartitionSpec as P

    B, S, D = x.shape
    H = params["r_gates"].shape[0]
    pre = jnp.einsum("bsd,dg->bsg", x.astype(jnp.bfloat16), params["w_gates"].astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32) + params["b_gates"].astype(jnp.float32)

    if state is None:
        zeros = jnp.zeros((B, D), jnp.float32)
        st0 = {"c": zeros, "n": zeros, "h": zeros, "m": zeros - 1e30}
    else:
        st0 = state

    axes = _batch_shard_axes(ctx, B)
    if axes:
        bspec = P(axes)
        st_spec = {k: bspec for k in st0}
        st1, hs = jax.shard_map(
            lambda p, s, r: _slstm_scan(p, s, r, H),
            mesh=ctx.mesh,
            in_specs=(bspec, st_spec, P()),
            out_specs=(st_spec, bspec),
            check_vma=False,
        )(pre, st0, params["r_gates"])
    else:
        st1, hs = _slstm_scan(pre, st0, params["r_gates"], H)

    y = layer_norm(hs, params["ln_w"], params["ln_b"]).astype(jnp.bfloat16)
    # GEGLU-ish ffn (projects up 2*dff, gates, projects down)
    ff = jnp.einsum("bsd,df->bsf", y, params["w_ff1"].astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
    a, b = jnp.split(ff, 2, axis=-1)
    h = (jax.nn.gelu(a) * b).astype(jnp.bfloat16)
    h = ctx.constrain(h, ("batch", "seq", "ff"))
    out = jnp.einsum("bsf,fd->bsd", h, params["w_ff2"].astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return ctx.constrain(out.astype(x.dtype), ("batch", "seq", "embed_nosplit")), st1


def slstm_init_state(B: int, d_model: int) -> dict:
    zeros = jnp.zeros((B, d_model), jnp.float32)
    return {"c": zeros, "n": zeros, "h": zeros, "m": zeros - 1e30}
