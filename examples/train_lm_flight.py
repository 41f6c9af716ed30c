"""End-to-end: train an LM with the Flight data plane (the paper's protocol
feeding the training loop), with checkpoint/restart fault tolerance.

  PYTHONPATH=src python examples/train_lm_flight.py [--steps 150]

This drives ``repro.launch.train`` on one device at a CPU-sized reduced
config (a ~100M-class run is the same command with --d-model 768
--layers 12 on one accelerator chip).
"""
import argparse
import sys

from repro.launch.train import main as train_main

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    args = ap.parse_args()
    sys.argv = [
        "train", "--arch", "internlm2_1_8b", "--smoke",
        "--d-model", "128", "--layers", "4", "--vocab", "2048",
        "--steps", str(args.steps), "--batch-size", str(args.batch_size),
        "--seq-len", str(args.seq_len), "--lr", "1e-3",
        "--ckpt-dir", "/tmp/repro_example_ckpt",
        "--checkpoint-every", str(max(args.steps // 2, 50)),
    ]
    train_main()
