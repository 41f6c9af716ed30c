"""Compressed collectives: int8 ring all-reduce with error feedback.

The paper's thesis applied to the TPU fabric: if the wire is the bottleneck,
compress what crosses it.  ``compressed_psum_ring`` implements a
reduce-scatter/all-gather ring (`lax.ppermute` inside ``shard_map``) whose
hops carry **int8 blockwise-quantized** chunks (kernels/quantize.py is the
TPU kernel for the hop codec) — 4× fewer bytes on the dominant gradient
all-reduce at bf16, ~2× at int8-vs-bf16.

``compressed_grad_sync`` adds per-leaf **error feedback** (the quantization
residual is re-added next step), the standard trick that keeps convergence
within noise of exact all-reduce (1-bit Adam / EF-SGD lineage).

Engineering note: with pjit, gradient reduction normally happens *implicitly*
inside backward.  To substitute a custom collective we mark gradients as
per-shard partial sums via ``shard_map`` and reduce them ourselves — the
train step opts in with ``TrainConfig.compressed_allreduce``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .sharding import ShardingCtx


def _quant_chunk(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantization over flat chunks of 256 (jnp path; the
    Pallas kernel in kernels/quantize.py is the TPU version)."""
    n = x.shape[0]
    block = 256 if n % 256 == 0 else n
    xb = x.reshape(-1, block)
    amax = jnp.max(jnp.abs(xb), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xb / scale[:, None]), -127, 127).astype(jnp.int8)
    return q, scale


def _dequant_chunk(q: jax.Array, scale: jax.Array) -> jax.Array:
    return (q.astype(jnp.float32) * scale[:, None]).reshape(-1)


def compressed_psum_ring(x_local: jax.Array, axis_name: str) -> jax.Array:
    """Ring all-reduce of a flat f32 vector with int8-compressed hops.

    Runs INSIDE shard_map.  x_local: (n,) per-device partial sum, n divisible
    by axis size.  Returns the summed (n,) on every device.
    """
    n_dev = int(jax.lax.axis_size(axis_name))
    if n_dev == 1:
        return x_local
    n = x_local.shape[0]
    assert n % n_dev == 0, (n, n_dev)
    chunks = x_local.reshape(n_dev, n // n_dev)
    fwd = [(i, (i + 1) % n_dev) for i in range(n_dev)]
    me = jax.lax.axis_index(axis_name)

    # reduce-scatter phase: after n_dev-1 hops, chunk j is complete on dev j
    acc = chunks
    recv_idx = me  # which chunk index we accumulate this hop

    def rs_step(k, acc):
        # each device sends chunk (me - k) and receives chunk (me - k - 1)
        send_idx = (me - k) % n_dev
        q, s = _quant_chunk(acc[send_idx])
        q_r = jax.lax.ppermute(q, axis_name, perm=fwd)
        s_r = jax.lax.ppermute(s, axis_name, perm=fwd)
        add_idx = (me - k - 1) % n_dev
        contrib = _dequant_chunk(q_r, s_r)
        return acc.at[add_idx].add(contrib)

    acc = jax.lax.fori_loop(0, n_dev - 1, rs_step, acc)

    # all-gather phase: circulate completed chunks
    def ag_step(k, acc):
        send_idx = (me + 1 - k) % n_dev
        q, s = _quant_chunk(acc[send_idx])
        q_r = jax.lax.ppermute(q, axis_name, perm=fwd)
        s_r = jax.lax.ppermute(s, axis_name, perm=fwd)
        set_idx = (me - k) % n_dev
        return acc.at[set_idx].set(_dequant_chunk(q_r, s_r).reshape(acc.shape[1:]))

    acc = jax.lax.fori_loop(0, n_dev - 1, ag_step, acc)
    return acc.reshape(n)


def compressed_grad_sync(grads, ctx: ShardingCtx, axis: str = "data"):
    """Replace the implicit gradient all-reduce over ``axis`` with the
    compressed ring.  grads: pytree of *per-shard partial* gradients
    (replicated-spec leaves).  Error feedback is carried in optimizer state
    by the caller when enabled; here we apply plain compression."""
    mesh = ctx.mesh
    if axis not in mesh.axis_names or mesh.shape[axis] == 1:
        return grads
    n_dev = mesh.shape[axis]

    leaves, tree = jax.tree.flatten(grads)
    sizes = [int(np.prod(l.shape)) for l in leaves]
    total = sum(sizes)
    pad = (-total) % (n_dev * 256)

    def sync_flat(flat):
        return compressed_psum_ring(flat, axis)

    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
    flat = jnp.pad(flat, (0, pad))
    other_axes = [a for a in mesh.axis_names if a != axis]
    synced = jax.shard_map(
        sync_flat, mesh=mesh,
        in_specs=P(), out_specs=P(), check_vma=False,
    )(flat)
    synced = synced[:total]
    out, off = [], 0
    for l, s in zip(leaves, sizes):
        out.append(synced[off:off + s].reshape(l.shape).astype(l.dtype))
        off += s
    return jax.tree.unflatten(tree, out)


def quantized_error_feedback(grads, residuals):
    """EF update: g' = Q(g + r); r' = (g + r) - g'.  Returns (g', r')."""
    def leaf(g, r):
        gf = g.astype(jnp.float32) + r
        flat = gf.reshape(-1)
        n = flat.shape[0]
        block = 256 if n % 256 == 0 else n
        xb = flat.reshape(-1, block)
        amax = jnp.max(jnp.abs(xb), axis=-1)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        q = jnp.clip(jnp.round(xb / scale[:, None]), -127, 127)
        gq = (q * scale[:, None]).reshape(g.shape)
        return gq.astype(g.dtype), gf - gq

    pairs = jax.tree.map(leaf, grads, residuals)
    g2 = jax.tree.map(lambda p: p[0], pairs, is_leaf=lambda x: isinstance(x, tuple))
    r2 = jax.tree.map(lambda p: p[1], pairs, is_leaf=lambda x: isinstance(x, tuple))
    return g2, r2
