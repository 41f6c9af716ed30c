"""End-to-end training launcher: Flight data service → loader → jitted trainer.

  PYTHONPATH=src python -m repro.launch.train --arch internlm2_1_8b --smoke \\
      --steps 200 --batch-size 8 --seq-len 256 [--d-model 512 --layers 8]

The launcher builds a one-device mesh on the first device JAX reports (the CPU
without an accelerator, one TPU chip on an accelerator host); ``--arch <id>`` without
``--smoke`` takes the config at its published widths.  The supervisor
restarts from the last committed checkpoint on failure.
"""
from __future__ import annotations

import argparse
import dataclasses

import numpy as np


def run(arch: str = "internlm2_1_8b", *, ckpt_dir: str, smoke: bool = False,
        steps: int = 100, batch_size: int = 8, seq_len: int = 256, d_model: int = 0,
        layers: int = 0, vocab: int = 0, lr: float = 3e-4, checkpoint_every: int = 100,
        docs: int = 0, seed: int = 0, streams: int = 4, log=print) -> dict:
    """Train ``steps`` steps fed over TCP by ``FlightDataLoader``.

    ``docs=0`` sizes the synthetic corpus to the run: two documents (of mean
    length ``seq_len``) per row the steps consume.  Returns the config name,
    parameter count, losses, final step and supervisor restart count, plus the
    final train state.
    """
    from ..configs import get_config, get_smoke_config
    from ..core.flight import FlightClient, InMemoryFlightServer
    from ..data import FlightDataLoader, synthesize_corpus
    from ..distributed.fault import RestartPolicy, TrainSupervisor
    from ..distributed.sharding import single_device_ctx
    from ..models.lm import LM
    from ..train.loop import Trainer, TrainerConfig
    from ..train.optimizer import OptimizerConfig
    from ..train.step import TrainConfig

    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    overrides = {k: v for k, v in (("d_model", d_model), ("n_layers", layers),
                                   ("vocab", vocab)) if v}
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)

    model = LM(cfg, single_device_ctx(cfg.logical_rules))
    n_params = cfg.param_count()
    log(f"[train] {cfg.name}: ~{n_params/1e6:.1f}M params, batch {batch_size}×{seq_len}")

    # data plane: local Flight service over a synthetic corpus
    data_srv = InMemoryFlightServer(batches_per_endpoint=1).serve_tcp()
    loader = None
    try:
        data_srv.add_dataset("corpus", synthesize_corpus(
            docs or 2 * steps * batch_size, cfg.vocab, mean_len=seq_len, seed=seed))
        loader = FlightDataLoader(FlightClient(f"tcp://127.0.0.1:{data_srv.port}"),
                                  "corpus", batch_size=batch_size,
                                  seq_len=seq_len, streams=streams)
        tcfg = TrainerConfig(
            total_steps=steps,
            checkpoint_every=checkpoint_every,
            train=TrainConfig(optimizer=OptimizerConfig(
                learning_rate=lr, warmup_steps=max(10, steps // 20), total_steps=steps)),
        )
        trainer = Trainer(model, tcfg, ckpt_dir, loader, log=log)

        def attempt(start_step: int) -> dict:
            state, _ = trainer.restore_or_init(seed)
            return trainer.run(state)

        sup = TrainSupervisor(RestartPolicy(max_restarts=3, backoff_s=1.0), trainer.ckpt,
                              logger=log)
        final = sup.run(attempt)
    finally:
        if loader is not None:
            loader.close()
        data_srv.shutdown()
    return {"config": cfg.name, "params": n_params, "losses": final["losses"],
            "step": final["step"], "restarts": sup.restarts, "state": final}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2_1_8b")
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--d-model", type=int, default=0, help="override width (0=config)")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--vocab", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--checkpoint-every", type=int, default=100)
    ap.add_argument("--docs", type=int, default=0, help="corpus size (0=sized to the run)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--streams", type=int, default=4)
    args = ap.parse_args()

    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    out = run(args.arch, ckpt_dir=args.ckpt_dir, smoke=args.smoke, steps=args.steps,
              batch_size=args.batch_size, seq_len=args.seq_len, d_model=args.d_model,
              layers=args.layers, vocab=args.vocab, lr=args.lr,
              checkpoint_every=args.checkpoint_every, docs=args.docs, seed=args.seed,
              streams=args.streams)
    losses = out["losses"]
    k = max(len(losses) // 10, 1)
    print(f"[train] loss first-{k}-mean {np.mean(losses[:k]):.4f} -> "
          f"last-{k}-mean {np.mean(losses[-k:]):.4f} "
          f"({out['step']} steps, {out['restarts']} restarts)")


if __name__ == "__main__":
    main()
