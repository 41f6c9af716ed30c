"""Serving driver: LM scoring microservice behind Flight (paper Fig 11).

  PYTHONPATH=src python -m repro.launch.serve --arch internlm2_1_8b --smoke \\
      --requests 64 --port 0
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np


def start_service(cfg, *, max_seq: int, port: int = 0, seed: int = 0):
    """Random-init ``cfg`` on one device and serve it over TCP; returns the service."""
    from ..distributed.sharding import single_device_ctx
    from ..models.lm import LM
    from ..serving import LMScoringService

    model = LM(cfg, single_device_ctx(cfg.logical_rules))
    params, _ = model.init(jax.random.key(seed))
    return LMScoringService(model, params, max_seq=max_seq).serve_tcp(port=port)


def run(arch: str = "internlm2_1_8b", *, smoke: bool = False, max_seq: int = 128,
        requests: int = 64, batch_rows: int = 16, port: int = 0, seed: int = 0) -> dict:
    """Start the service, stream ``requests`` seeded prompts through DoExchange,
    shut down.  Returns the config, the service's model and params, the
    prompts and the per-request ``next_token`` / ``logprob`` answers."""
    from ..configs import get_config, get_smoke_config
    from ..core import RecordBatch
    from ..core.flight import FlightClient, FlightDescriptor

    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    svc = start_service(cfg, max_seq=max_seq, port=port, seed=seed)
    try:
        rng = np.random.default_rng(seed)
        lens = rng.integers(4, max_seq, requests)
        reqs = [[int(t) for t in rng.integers(1, cfg.vocab, n)] for n in lens]
        schema = RecordBatch.from_pydict({"tokens": [reqs[0]]}).schema
        chunks = [
            RecordBatch.from_pydict({"tokens": reqs[s:s + batch_rows]}, schema)
            for s in range(0, requests, batch_rows)
        ]
        # pipelined streaming exchange: a feeder thread pushes request batches
        # while this thread drains scored results (no per-batch round trips)
        client = FlightClient(f"tcp://127.0.0.1:{svc.port}")
        ex = client.do_exchange_stream(FlightDescriptor.for_path("score"), schema)
        t0 = time.perf_counter()
        ex.feed(chunks)
        outs = list(ex)
        seconds = time.perf_counter() - t0
        ex.close()
    finally:
        svc.shutdown()
    return {
        "config": cfg.name, "model": svc.model, "params": svc.params,
        "requests": reqs, "seconds": seconds, "batch_rows": batch_rows,
        "next_token": np.concatenate([o.column("next_token").to_numpy() for o in outs]),
        "logprob": np.concatenate([o.column("logprob").to_numpy() for o in outs]),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2_1_8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--batch-rows", type=int, default=16)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--serve-forever", action="store_true")
    args = ap.parse_args()

    from ..configs import get_config, get_smoke_config
    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.serve_forever:
        cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
        svc = start_service(cfg, max_seq=args.max_seq, port=args.port, seed=args.seed)
        print(f"[serve] {cfg.name} scoring service on tcp://127.0.0.1:{svc.port}")
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            return
        finally:
            svc.shutdown()

    out = run(args.arch, smoke=args.smoke, max_seq=args.max_seq, requests=args.requests,
              batch_rows=args.batch_rows, port=args.port, seed=args.seed)
    n, dt = len(out["next_token"]), out["seconds"]
    print(f"[serve] {out['config']}: scored {n} requests in {dt:.2f}s "
          f"({n / dt:.1f} req/s, batched {out['batch_rows']}/exchange)")


if __name__ == "__main__":
    main()
