"""Weights from the seed, made on the device in one jitted call.

The layout comes from the configuration's reference (``param_specs``); the
harness checks it against the program's own parameter tree before use, so the
program runs on these weights and the reference regenerates them, bit for bit,
from the same seed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from all bits of a seed wider than 32 bits (``jax.random.key``
    alone keeps only the low word)."""
    seed %= 1 << 64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        *head, leaf = path.split("/")
        node = out
        for h in head:
            node = node.setdefault(h, {})
        node[leaf] = v
    return out


def shapes(specs: dict) -> dict:
    return nest({p: tuple(shape) for p, (shape, _) in specs.items()})


def make_params(specs: dict, seed: int, dtype=jnp.float32, sharding=None) -> dict:
    """Every leaf of ``specs`` drawn from its own fold of the seed's key."""
    paths = sorted(specs)

    def build(key):
        flat = {}
        for i, p in enumerate(paths):
            shape, init = specs[p]
            if init == "zeros":
                v = jnp.zeros(shape, jnp.float32)
            elif init == "ones":
                v = jnp.ones(shape, jnp.float32)
            else:
                v = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * init[1]
            flat[p] = v.astype(dtype)
        return nest(flat)

    return jax.jit(build, out_shardings=sharding)(seed_key(seed))
