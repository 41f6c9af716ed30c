"""Plain reference for ``internlm2_1_8b.json``: InternLM2 (arXiv:2403.17297) in jax.numpy.

Imports nothing of the program under test.  It defines the parameter layout the
benchmark makes weights in (``param_specs``) and the next-token logits of a
prompt at its last real position: pre-RMSNorm blocks of grouped-query
attention with rotary embeddings (rotate-half pairing) and a SwiGLU MLP, a
final RMSNorm and the head.

One departure from the published model, the program's and mirrored here:
the head is tied to the embedding (listed in ``reduced``).  RoPE's base is the
configuration's ``rope_theta``, the published one.  The attention weights are laid
out as the program holds them: ``wq`` (D, H, 1, hd), with query head h reading
key/value head h // (H / K), which is grouped-query attention.

``logits`` runs at float32 with ``highest`` matmul precision (the reference),
or with every matmul's operands rounded to float8 e4m3 with one scale per
tensor (``quant="fp8"``): the control, one step below the bfloat16 matmul
inputs the configuration states.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def dims(model: dict) -> dict:
    D, H = model["hidden_size"], model["num_attention_heads"]
    return {"D": D, "H": H, "K": model["num_key_value_heads"], "hd": D // H,
            "F": model["intermediate_size"], "V": model["vocab_size"],
            "L": model["num_hidden_layers"], "theta": model["rope_theta"],
            "eps": model["rms_norm_eps"]}


def param_specs(model: dict) -> dict:
    """{path: (shape, init)}; init is ("normal", std), "zeros" or "ones".

    Every matrix is drawn at std 1/sqrt(its input width)."""
    d = dims(model)
    D, H, K, hd, F, L = d["D"], d["H"], d["K"], d["hd"], d["F"], d["L"]
    fan = lambda n: ("normal", 1 / math.sqrt(n))
    return {
        "embedding": ((d["V"], D), ("normal", 0.02)),
        "final/norm_out/w": ((D,), "ones"),
        "layer0/norm_attn/w": ((L, D), "ones"),
        "layer0/norm_ffn/w": ((L, D), "ones"),
        "layer0/attn/wq": ((L, D, H, 1, hd), fan(D)),
        "layer0/attn/wk": ((L, D, K, hd), fan(D)),
        "layer0/attn/wv": ((L, D, K, hd), fan(D)),
        "layer0/attn/wo": ((L, H, 1, hd, D), fan(H * hd)),
        "layer0/mlp/w_gate": ((L, D, F), fan(D)),
        "layer0/mlp/w_up": ((L, D, F), fan(D)),
        "layer0/mlp/w_down": ((L, F, D), fan(F)),
    }


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, quant):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision="highest")


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (S, n, hd): rotate-half pairing of the first and second halves."""
    S, hd = x.shape[0], x.shape[-1]
    freq = 1.0 / theta ** (np.arange(0, hd, 2) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(freq, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(params, tokens, n_real, model: dict, quant: str | None = None):
    """Next-token logits (V,) of one prompt: ``tokens`` (S,) right-padded,
    ``n_real`` real tokens.  Causal attention makes the padding inert."""
    d = dims(model)
    H, K, hd, eps = d["H"], d["K"], d["hd"], d["eps"]
    S = tokens.shape[0]
    x = params["embedding"][tokens]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, p):
        a, m = p["attn"], p["mlp"]
        h = _rms(x, p["norm_attn"]["w"], eps)
        q = _rope(_mm("sd,dnh->snh", h, a["wq"][:, :, 0], quant), d["theta"])
        k = _rope(_mm("sd,dkh->skh", h, a["wk"], quant), d["theta"])
        v = _mm("sd,dkh->skh", h, a["wv"], quant)
        k, v = jnp.repeat(k, H // K, axis=1), jnp.repeat(v, H // K, axis=1)
        s = _mm("qnh,knh->nqk", q, k, quant) / math.sqrt(hd)
        s = jnp.where(causal[None], s, -jnp.inf)
        o = _mm("nqk,knh->qnh", jax.nn.softmax(s, -1), v, quant)
        x = x + _mm("snh,nhd->sd", o, a["wo"][:, 0], quant)
        h = _rms(x, p["norm_ffn"]["w"], eps)
        g = jax.nn.silu(_mm("sd,df->sf", h, m["w_gate"], quant)) * _mm("sd,df->sf", h, m["w_up"], quant)
        return x + _mm("sf,fd->sd", g, m["w_down"], quant), None

    x, _ = jax.lax.scan(layer, x, params["layer0"])
    last = _rms(x[n_real - 1], params["final"]["norm_out"]["w"], eps)
    return _mm("d,vd->v", last, params["embedding"], quant)
