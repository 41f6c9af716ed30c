"""Each configuration's required-FLOP count is at or below XLA's own count
for the program's path, compiled for a described v5e chip.

XLA's cost analysis counts a loop body once, whatever its trip count, so the
program is compiled with its layers unrolled and at a length where every
remaining loop runs once: scoring at 512 positions (one attention block).
XLA also counts the head over every position, which the required count
leaves out.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from harness import program
from harness.spec import BENCH, load_module


@pytest.fixture(scope="module")
def mesh():
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))


def on(mesh, tree):
    rep = NamedSharding(mesh, P())
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep), tree)


def model_on(mesh, name):
    from repro.distributed.sharding import ShardingCtx
    from repro.models.lm import LM

    conf = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg = dataclasses.replace(program.model_config(conf), scan_layers=False)
    return conf, LM(cfg, ShardingCtx(mesh, cfg.logical_rules))


def xla_flops(compiled) -> float:
    ca = compiled.cost_analysis()
    return float((ca[0] if isinstance(ca, list) else ca)["flops"])


def test_score_count_at_most_xla(mesh):
    from repro.serving.service import score_tokens

    conf, model = model_on(mesh, "internlm2_1_8b")
    params = on(mesh, model.init(jax.random.key(0), abstract=True)[0])
    n = 512
    toks = on(mesh, jax.ShapeDtypeStruct((1, n), jnp.int32))
    lens = on(mesh, jax.ShapeDtypeStruct((1,), jnp.int32))
    xla = xla_flops(score_tokens.lower(model, params, toks, lens).compile())
    ours = load_module(BENCH / "flops" / "internlm2_1_8b.py").forward_flops(conf["model"], n)
    assert 0 < ours <= xla
