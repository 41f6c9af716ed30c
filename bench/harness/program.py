"""The system under test as a configuration file describes it.

The program's model configuration is its registry entry (``arch``) with the
fields of the file's ``program`` block replaced: the settings the program has
an option for and the registry entry does not state as published.
``check_layout`` refuses a program whose parameter tree differs from the
reference's layout, since the benchmark's weights are made in that layout.
"""
from __future__ import annotations

import dataclasses

import jax


def model_config(config: dict):
    from repro.configs import get_config

    return dataclasses.replace(get_config(config["arch"]), **config.get("program", {}))


def build_model(config: dict):
    """(model, param shardings) on a one-device mesh of the default device."""
    from repro.distributed.sharding import single_device_ctx, tree_shardings
    from repro.models.lm import LM

    cfg = model_config(config)
    model = LM(cfg, single_device_ctx(cfg.logical_rules))
    _, axes = model.init(jax.random.key(0), abstract=True)
    return model, tree_shardings(axes, model.ctx.mesh, model.ctx.rules)


def check_layout(model, want: dict) -> None:
    """``want``: the reference's nested {name: shape}."""
    got, _ = model.init(jax.random.key(0), abstract=True)
    got = jax.tree.map(lambda s: tuple(s.shape), got)
    if got != want:
        raise SystemExit(f"program parameter layout {got} is not the reference's {want}")
