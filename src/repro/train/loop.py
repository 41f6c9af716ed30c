"""Training loop: Flight data plane + pjit step + checkpoint/fault hooks.

``Trainer`` is the single-controller view: it owns the jit'd step, the
FlightDataLoader, the CheckpointManager (async, with loader state in the
manifest), and the failure/straggler detectors.  ``build_dp_train_step``
is the pure-data-parallel variant whose gradient sync is the **compressed
int8 ring** (collectives.py) inside shard_map — the wire substitution the
pjit path can't express (GSPMD owns its collectives).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..data.loader import FlightDataLoader, LoaderState
from ..distributed.checkpoint import CheckpointManager
from ..distributed.collectives import compressed_psum_ring, quantized_error_feedback
from ..distributed.fault import FailureDetector, StragglerDetector
from ..distributed.sharding import tree_shardings
from ..models.lm import LM
from .optimizer import OptimizerConfig, make_optimizer, opt_state_axes_with_params
from .step import TrainConfig, build_train_step


@dataclass
class TrainerConfig:
    total_steps: int = 100
    log_every: int = 10
    checkpoint_every: int = 50
    keep_checkpoints: int = 3
    train: TrainConfig = field(default_factory=TrainConfig)


class Trainer:
    def __init__(self, model: LM, trainer_cfg: TrainerConfig, ckpt_dir: str,
                 loader: FlightDataLoader | None = None, log=print):
        self.model = model
        self.cfg = trainer_cfg
        self.loader = loader
        self.ckpt = CheckpointManager(ckpt_dir, keep=trainer_cfg.keep_checkpoints)
        self.log = log
        self.failure = FailureDetector()
        self.straggler = StragglerDetector()
        step_fn, opt_init = build_train_step(model, trainer_cfg.train, None)
        self._step = jax.jit(step_fn, donate_argnums=(0, 1))
        self._opt_init = opt_init

    def init_state(self, seed: int = 0):
        """Fresh params and optimizer state, placed with the mesh shardings the
        step returns them with, so the step compiles once and not again at step 2."""
        params, axes = self.model.init(jax.random.key(seed))
        ctx = self.model.ctx
        opt_axes = opt_state_axes_with_params(self.cfg.train.optimizer, params, axes)
        params = jax.device_put(params, tree_shardings(axes, ctx.mesh, ctx.rules))
        opt_state = jax.device_put(self._opt_init(params),
                                   tree_shardings(opt_axes, ctx.mesh, ctx.rules))
        return {"params": params, "opt": opt_state, "step": 0}

    def restore_or_init(self, seed: int = 0):
        latest = self.ckpt.latest_step()
        state = self.init_state(seed)
        if latest is None:
            return state, LoaderState()
        import json
        mani = json.loads((self.ckpt.dir / f"step_{latest:09d}" / "manifest.json").read_text())
        restored = self.ckpt.restore(latest, {"params": state["params"], "opt": state["opt"]})
        loader_state = LoaderState.from_json(mani["extra"].get("loader", {"epoch": 0, "cursor": 0}))
        return ({"params": restored["params"], "opt": restored["opt"], "step": latest},
                loader_state)

    def run(self, state, steps: int | None = None) -> dict:
        steps = steps or self.cfg.total_steps
        t_last = time.perf_counter()
        losses = []
        while state["step"] < steps:
            batch_np, loader_state = next(self.loader)
            batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
            params, opt, metrics = self._step(state["params"], state["opt"], batch)
            state = {"params": params, "opt": opt, "step": state["step"] + 1}
            losses.append(float(metrics["loss"]))
            if state["step"] % self.cfg.log_every == 0:
                dt = time.perf_counter() - t_last
                t_last = time.perf_counter()
                self.log(f"step {state['step']:5d} loss {np.mean(losses[-self.cfg.log_every:]):.4f} "
                         f"({dt / self.cfg.log_every:.2f}s/step)")
            if state["step"] % self.cfg.checkpoint_every == 0:
                self.ckpt.save_async(state["step"],
                                     {"params": state["params"], "opt": state["opt"]},
                                     extra={"loader": loader_state.to_json()})
        self.ckpt.wait()
        state["losses"] = losses
        return state


# ---------------------------------------------------------------------------
# pure-DP train step with compressed ring gradient sync (shard_map)
# ---------------------------------------------------------------------------


def build_dp_train_step(model: LM, opt_cfg: OptimizerConfig, mesh, axis: str = "data",
                        compressed: bool = True, error_feedback: bool = True):
    """Data-parallel step where *we* own the gradient collective: per-device
    grads -> int8 ring all-reduce (+error feedback) -> optimizer.

    Returns (step_fn, init_fn); state = {params, opt, residual}.
    params replicated; batch sharded on axis 0.
    """
    opt_init, opt_update = make_optimizer(opt_cfg)
    n_dev = mesh.shape[axis]

    def init_fn(params):
        return {"opt": opt_init(params),
                "residual": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)}

    def local_grads(params, batch):
        (loss, _), grads = jax.value_and_grad(model.loss_fn, has_aux=True)(params, batch)
        return loss, grads

    def step(params, opt_state, residual, batch):
        def body(params_l, batch_l, residual_l):
            loss, grads = local_grads(params_l, batch_l)
            if compressed:
                if error_feedback:
                    grads, new_res = quantized_error_feedback(grads, residual_l)
                else:
                    new_res = residual_l
                leaves, tree = jax.tree.flatten(grads)
                sizes = [int(np.prod(g.shape)) for g in leaves]
                flat = jnp.concatenate([g.reshape(-1).astype(jnp.float32) for g in leaves])
                pad = (-flat.shape[0]) % (n_dev * 256)
                flat = jnp.pad(flat, (0, pad))
                flat = compressed_psum_ring(flat, axis) / n_dev
                out, off = [], 0
                for g, s in zip(leaves, sizes):
                    out.append(flat[off:off + s].reshape(g.shape))
                    off += s
                grads = jax.tree.unflatten(tree, out)
            else:
                grads = jax.tree.map(lambda g: jax.lax.pmean(g, axis), grads)
                new_res = residual_l
            loss = jax.lax.pmean(loss, axis)
            return loss, grads, new_res

        other = [a for a in mesh.axis_names if a != axis]
        rep = P(*([None]))
        loss, grads, new_res = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(axis), P()),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )(params, batch, residual)
        new_params, new_opt, metrics = opt_update(grads, opt_state, params)
        return new_params, new_opt, new_res, {"loss": loss, **metrics}

    return step, init_fn
