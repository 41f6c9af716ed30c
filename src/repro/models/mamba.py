"""Mamba (S6) selective-state-space block — Jamba's majority mixer.

Training/prefill uses a **chunked selective scan**: the sequence is split
into chunks; within a chunk the recurrence h_t = Ā_t h_{t-1} + B̄_t x_t is
evaluated with an associative scan in log-space-stable f32, and a
``lax.scan`` carries the (B, d_inner, N) state across chunks.  This bounds
the materialized (B, c, d_inner, N) tensor to the chunk size — the memory
shape that makes 398 B Jamba trainable — and is TP-clean: everything is
elementwise over d_inner, which shards over ``model``.

Decode is the O(1) recurrence on the carried state (this is why Jamba runs
the ``long_500k`` cell that full-attention archs must skip).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .layers import ParamBuilder


@dataclass(frozen=True)
class MambaConfig:
    d_model: int
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def dt_rank(self) -> int:
        return -(-self.d_model // 16)


def init_mamba(pb: ParamBuilder, cfg: MambaConfig, stack: int | None = None) -> None:
    lead = (stack,) if stack is not None else ()
    lax_ = ("layers",) if stack is not None else ()
    D, Din, N, R = cfg.d_model, cfg.d_inner, cfg.d_state, cfg.dt_rank
    pb.param("w_in", lead + (D, 2 * Din), lax_ + ("embed", "inner"), matmul=True)
    pb.param("conv_w", lead + (cfg.d_conv, Din), lax_ + ("conv", "inner"), scale=0.5)
    pb.param("conv_b", lead + (Din,), lax_ + ("inner",), init="zeros")
    pb.param("w_x", lead + (Din, R + 2 * N), lax_ + ("inner", "dt"))
    pb.param("w_dt", lead + (R, Din), lax_ + ("dt", "inner"))
    pb.param("b_dt", lead + (Din,), lax_ + ("inner",), init=-4.6)  # softplus≈0.01
    pb.param("A_log", lead + (Din, N), lax_ + ("inner", "state"), init=0.5)
    pb.param("D_skip", lead + (Din,), lax_ + ("inner",), init="ones")
    pb.param("w_out", lead + (Din, D), lax_ + ("inner", "embed"), matmul=True)


def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv along seq via K shifted adds (K=4: cheap, TP-clean).

    x: (B, S, Din); w: (K, Din).  ``state``: (B, K-1, Din) tail of previous
    chunk/step (decode); returns (y, new_state).
    """
    K = w.shape[0]
    B, S, Din = x.shape
    if state is None:
        state = jnp.zeros((B, K - 1, Din), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)  # (B, S+K-1, Din)
    y = jnp.zeros((B, S, Din), jnp.float32)
    for i in range(K):
        y = y + xp[:, i : i + S].astype(jnp.float32) * w[i].astype(jnp.float32)
    new_state = xp[:, S:][:, -(K - 1):] if S >= K - 1 else xp[:, -(K - 1):]
    return (y + b.astype(jnp.float32)).astype(x.dtype), new_state


def _ssm_params(params, x):
    """x: (..., Din) post-conv activations -> (dt, B_in, C_out) f32."""
    N = params["A_log"].shape[-1]
    R = params["w_dt"].shape[-2 if params["w_dt"].ndim == 2 else 0]
    proj = jnp.einsum("...d,dr->...r", x.astype(jnp.float32), params["w_x"].astype(jnp.float32))
    dt_in, Bc = proj[..., :R], proj[..., R:]
    B_in, C_out = Bc[..., :N], Bc[..., N:]
    dt = jax.nn.softplus(
        jnp.einsum("...r,rd->...d", dt_in, params["w_dt"].astype(jnp.float32))
        + params["b_dt"].astype(jnp.float32)
    )
    return dt, B_in, C_out


def _scan_chunk(h0, dA, dBx):
    """Associative scan of h_t = dA_t * h_{t-1} + dBx_t within a chunk.

    dA, dBx: (B, c, Din, N) f32; h0: (B, Din, N).  Returns (hs, h_last).
    """
    def combine(a, b):
        (A1, X1), (A2, X2) = a, b
        return A1 * A2, X1 * A2 + X2

    As, Xs = jax.lax.associative_scan(combine, (dA, dBx), axis=1)
    hs = As * h0[:, None] + Xs
    return hs, hs[:, -1]


def mamba_mix(params: dict, x: jax.Array, ctx, chunk: int = 64,
              state: dict | None = None):
    """x: (B, S, D) -> (B, S, D).  ``state`` (decode): {h:(B,Din,N), conv:(B,K-1,Din)}.

    Returns (out, new_state).  Training path passes state=None and S % chunk == 0.
    """
    B, S, D = x.shape
    N = params["A_log"].shape[-1]
    Din = params["w_in"].shape[-1] // 2
    A = -jnp.exp(params["A_log"].astype(jnp.float32))  # (Din, N), negative

    xz = jnp.einsum("bsd,de->bse", x.astype(jnp.bfloat16), params["w_in"].astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
    xin, z = jnp.split(xz, 2, axis=-1)
    xin = ctx.constrain(xin.astype(jnp.bfloat16), ("batch", "seq", "inner"))
    z = ctx.constrain(z.astype(jnp.bfloat16), ("batch", "seq", "inner"))

    conv_state = None if state is None else state["conv"]
    xc, new_conv = _causal_conv(xin, params["conv_w"], params["conv_b"], conv_state)
    xc = jax.nn.silu(xc.astype(jnp.float32)).astype(jnp.bfloat16)

    dt, B_in, C_out = _ssm_params(params, xc)          # (B,S,Din) (B,S,N) (B,S,N)

    h0 = jnp.zeros((B, Din, N), jnp.float32) if state is None else state["h"]

    if S == 1:  # decode: plain recurrence
        dA = jnp.exp(dt[:, 0, :, None] * A[None])
        dBx = (dt[:, 0] * xc[:, 0].astype(jnp.float32))[..., None] * B_in[:, 0, None, :]
        h = dA * h0 + dBx
        ys = jnp.einsum("bdn,bn->bd", h, C_out[:, 0])[:, None]
        h_last = h
    else:
        nc = S // chunk if S % chunk == 0 else 1
        c = S // nc
        r3 = lambda t: t.reshape(B, nc, c, t.shape[-1]).swapaxes(0, 1)
        dt_c, x_c = r3(dt), r3(xc.astype(jnp.float32))
        B_c, C_c = r3(B_in), r3(C_out)

        def step(h, inp):
            # discretize *inside* the chunk: the (B,S,Din,N) dA/dBx tensors
            # never materialize across the whole sequence (2×2.1 GB/device on
            # jamba train_4k — §Perf D-cell), and under remat they rebuild
            # chunk-by-chunk in backward
            dtc, xcc, bc, cc = inp
            da = jnp.exp(dtc[..., None] * A[None, None])          # (B,c,Din,N)
            dbx = (dtc * xcc)[..., None] * bc[..., None, :]
            hs, h_next = _scan_chunk(h, da, dbx)
            return h_next, jnp.einsum("bcdn,bcn->bcd", hs, cc)

        h_last, ys = jax.lax.scan(step, h0, (dt_c, x_c, B_c, C_c))
        ys = ys.swapaxes(0, 1).reshape(B, S, Din)

    y = ys + xc.astype(jnp.float32) * params["D_skip"].astype(jnp.float32)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(jnp.bfloat16)
    y = ctx.constrain(y, ("batch", "seq", "inner"))
    out = jnp.einsum("bse,ed->bsd", y, params["w_out"].astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    out = ctx.constrain(out.astype(x.dtype), ("batch", "seq", "embed_nosplit"))
    new_state = {"h": h_last, "conv": new_conv}
    return out, new_state


def mamba_init_state(B: int, cfg: MambaConfig, dtype=jnp.bfloat16) -> dict:
    return {
        "h": jnp.zeros((B, cfg.d_inner, cfg.d_state), jnp.float32),
        "conv": jnp.zeros((B, cfg.d_conv - 1, cfg.d_inner), dtype),
    }
