"""Compile the two served device paths for a described TPU v5e chip.

Nothing runs: the TPU compiler, installed beside the CPU backend, compiles
for a chip that is described and not attached.  It refuses here what the chip
would refuse — a program that does not fit 16 GB of HBM among them — at the
widths and batch shapes ``chip_smoke.py`` runs.  The topology is described
inside a fixture, so only the worker that runs this file loads the TPU library.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from chip_smoke import SERVE_MAX_SEQ, SERVE_ROWS, TRAIN_BATCH, TRAIN_SEQ
from repro.configs import get_config
from repro.distributed.sharding import ShardingCtx, tree_shardings
from repro.models.lm import LM
from repro.serving import compute_params, score_tokens
from repro.train.loop import Trainer, TrainerConfig
from repro.train.optimizer import opt_state_axes_with_params


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def model_on_chip(topo):
    """LM at published widths whose sharding context is the described chip."""
    def build(arch: str) -> LM:
        cfg = get_config(arch)
        mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
        return LM(cfg, ShardingCtx(mesh, cfg.logical_rules))
    return build


def on(sharding, tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree)


def test_score_tokens_compiles_internlm2(model_on_chip, one_chip):
    model = model_on_chip("internlm2_1_8b")
    params, _ = model.init(jax.random.key(0), abstract=True)
    tokens = jax.ShapeDtypeStruct((SERVE_ROWS, SERVE_MAX_SEQ), jnp.int32)
    lengths = jax.ShapeDtypeStruct((SERVE_ROWS,), jnp.int32)
    compiled = score_tokens.lower(model, *on(one_chip, (params, tokens, lengths))).compile()
    param_bytes = sum(p.size * p.dtype.itemsize for p in jax.tree.leaves(params))
    # the weights are arguments of the program, not constants baked into it
    assert compiled.memory_analysis().argument_size_in_bytes >= param_bytes


def test_score_tokens_reads_matmul_weights_without_a_cast(model_on_chip, one_chip):
    """Given the service's tree, the program takes its matmul weights in bf16:
    no float32 value of a weight's shape, whole or one layer's slice, is left
    in it to cast, and its arguments are about half the float32 tree."""
    model = model_on_chip("internlm2_1_8b")
    params, _ = model.init(jax.random.key(0), abstract=True)
    held = jax.eval_shape(partial(compute_params, model), params)
    tokens = jax.ShapeDtypeStruct((SERVE_ROWS, SERVE_MAX_SEQ), jnp.int32)
    lengths = jax.ShapeDtypeStruct((SERVE_ROWS,), jnp.int32)
    compiled = score_tokens.lower(model, *on(one_chip, (held, tokens, lengths))).compile()
    hlo = compiled.as_text()
    stacked = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}  # (layers, ...)
    shapes = [params["embedding"].shape]
    for path, w in jax.tree_util.tree_flatten_with_path(params)[0]:
        if path[-1].key in stacked:
            shapes += [w.shape, w.shape[1:]]
    assert len(shapes) == 1 + 2 * len(stacked)
    for shape in shapes:
        assert f"f32[{','.join(map(str, shape))}]" not in hlo
    f32_bytes = sum(p.size * p.dtype.itemsize for p in jax.tree.leaves(params))
    assert compiled.memory_analysis().argument_size_in_bytes <= 0.55 * f32_bytes


def test_train_step_compiles_xlstm(model_on_chip, one_chip, tmp_path):
    model = model_on_chip("xlstm_350m")
    tcfg = TrainerConfig()
    trainer = Trainer(model, tcfg, str(tmp_path))
    params, axes = model.init(jax.random.key(0), abstract=True)
    opt = jax.eval_shape(trainer._opt_init, params)
    opt_axes = opt_state_axes_with_params(tcfg.train.optimizer, params, axes)
    ctx = model.ctx
    # placed as Trainer.init_state places them: on the mesh's shardings
    params, opt = (jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                                tree, tree_shardings(tree_axes, ctx.mesh, ctx.rules))
                   for tree, tree_axes in ((params, axes), (opt, opt_axes)))
    batch = on(one_chip, {k: jax.ShapeDtypeStruct((TRAIN_BATCH, TRAIN_SEQ), jnp.int32)
                          for k in ("tokens", "labels")})
    compiled = trainer._step.lower(params, opt, batch).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 0
