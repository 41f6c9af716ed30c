"""Run the Flight-fed serve and train paths once on one TPU chip, and check them.

  python chip_smoke.py [--seed N]

Both phases run in this one process, since a chip belongs to one process:

  serve  internlm2_1_8b at its published widths, random weights from the seed,
         behind ``LMScoringService`` over TCP (``repro.launch.serve.run``).
         Seeded prompts of 4-127 tokens stream through DoExchange, 16 rows per
         batch, padded to 128.  Every answer must equal an unpadded prefill of
         that one prompt with the same params on the same chip, and every
         logprob must be finite and <= 0.  Equal means the same greedy token
         and a logprob within TOL; a different token passes only as a tie,
         where the reference ranks it within TOL of its own top logprob (the
         batched and the one-prompt programs round bf16 activations
         differently, by up to about 5e-3 in logprob on a v5e).
  train  xlstm_350m at its published widths, fed by ``FlightDataLoader`` over
         TCP (``repro.launch.train.run``), a few steps at batch 4 x 4096 with a
         fresh checkpoint directory.  Losses must be finite, the first within
         1.0 of ln(vocab), and the supervisor must not have restarted.

The last line of stdout is ``{"ok": true, "device": {...}}``, printed only when
every check passed on a TPU.  Anything else exits non-zero without it.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SERVE_ARCH, SERVE_REQUESTS, SERVE_MAX_SEQ, SERVE_ROWS = "internlm2_1_8b", 48, 128, 16
TRAIN_ARCH, TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = "xlstm_350m", 5, 4, 4096
TOL = 1e-2  # log space; see the module docstring


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"[chip_smoke] FAILED: {what}")


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Sums JAX's trace, lowering and backend-compile durations."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


def on_device(tree, device) -> bool:
    return all(leaf.devices() == {device} for leaf in jax.tree.leaves(tree))


def memory_line(device) -> str:
    st = device.memory_stats() or {}
    return (f"bytes_in_use {st.get('bytes_in_use')} "
            f"peak_bytes_in_use {st.get('peak_bytes_in_use')}")


def reference_answers(model, params, requests, served) -> tuple[np.ndarray, ...]:
    """From an unpadded prefill of each prompt alone: the greedy token, its
    logprob, and the logprob of the ``served`` token."""
    prefill = jax.jit(lambda p, t: model.prefill(p, {"tokens": t})[0])
    lengths = sorted({len(r) for r in requests})
    # one program per prompt length; XLA compiles them in parallel threads
    with ThreadPoolExecutor(max_workers=8) as pool:
        compiled = dict(zip(lengths, pool.map(
            lambda n: prefill.lower(params, jax.ShapeDtypeStruct((1, n), np.int32)).compile(),
            lengths)))
    toks, lps, served_lps = [], [], []
    for r, s in zip(requests, served):
        lg = np.asarray(compiled[len(r)](params, np.asarray([r], np.int32))[0], np.float64)
        m = lg.max()
        lse = m + math.log(np.exp(lg - m).sum())
        toks.append(int(lg.argmax()))
        lps.append(m - lse)
        served_lps.append(lg[s] - lse)
    return np.asarray(toks), np.asarray(lps), np.asarray(served_lps)


def serve_phase(device, clock, seed: int) -> None:
    from repro.launch import serve

    c0, t0 = clock.seconds, time.perf_counter()
    out = serve.run(SERVE_ARCH, max_seq=SERVE_MAX_SEQ, requests=SERVE_REQUESTS,
                    batch_rows=SERVE_ROWS, seed=seed)
    log(f"[serve] {out['config']}: {out['model'].cfg.param_count()} params, "
        f"{len(out['next_token'])}/{SERVE_REQUESTS} requests answered over DoExchange/TCP, "
        f"compile {clock.seconds - c0:.1f}s, wall {time.perf_counter() - t0:.1f}s")
    log(f"[serve] after serving: {memory_line(device)}")
    check(on_device(out["params"], device), "serve params are not all on the TPU device")
    tok, lp = out["next_token"], out["logprob"]
    check(len(tok) == SERVE_REQUESTS == len(lp), "not every request was answered")
    check(bool(np.all(np.isfinite(lp)) and np.all(lp <= 0)), f"logprobs not finite and <= 0: {lp}")

    c0 = clock.seconds
    ref_tok, ref_lp, ref_lp_served = reference_answers(
        out["model"], out["params"], out["requests"], tok)
    log(f"[serve] unpadded per-request reference: {len({len(r) for r in out['requests']})} "
        f"lengths, compile {clock.seconds - c0:.1f}s (summed over threads)")
    gap = ref_lp - ref_lp_served
    ties = np.flatnonzero((tok != ref_tok) & (gap <= TOL))
    bad = np.flatnonzero((tok != ref_tok) & (gap > TOL))
    err = float(np.max(np.abs(lp - ref_lp)))
    log(f"[serve] next_token equal on {int(np.sum(tok == ref_tok))}/{len(tok)}, "
        f"ties within {TOL} at rows {ties.tolist()} (reference gaps {gap[ties].tolist()}), "
        f"max |logprob - reference| {err:.3g}")
    check(bad.size == 0, f"next_token differs from the unpadded prefill at rows {bad.tolist()}: "
                         f"served {tok[bad].tolist()} vs reference {ref_tok[bad].tolist()}")
    check(err <= TOL, f"logprob differs from the unpadded prefill by {err}")
    log(f"[serve] {memory_line(device)}")


def train_phase(device, clock, seed: int) -> None:
    from repro.configs import get_config
    from repro.launch import train

    c0, t0 = clock.seconds, time.perf_counter()
    with tempfile.TemporaryDirectory() as ckpt_dir:
        out = train.run(TRAIN_ARCH, ckpt_dir=ckpt_dir, steps=TRAIN_STEPS,
                        batch_size=TRAIN_BATCH, seq_len=TRAIN_SEQ,
                        checkpoint_every=TRAIN_STEPS + 1, seed=seed, log=log)
        check(not any(Path(ckpt_dir).iterdir()), "a checkpoint was written inside the window")
    losses = out["losses"]
    log(f"[train] {out['config']}: {out['params']} params, {out['step']} steps "
        f"at {TRAIN_BATCH}x{TRAIN_SEQ} fed by FlightDataLoader over TCP, "
        f"compile {clock.seconds - c0:.1f}s, wall {time.perf_counter() - t0:.1f}s, "
        f"losses {[round(x, 4) for x in losses]}")
    check(out["restarts"] == 0, f"the supervisor restarted {out['restarts']} times")
    check(out["step"] == TRAIN_STEPS and len(losses) == TRAIN_STEPS, "not every step ran")
    check(on_device({k: out["state"][k] for k in ("params", "opt")}, device),
          "train state is not all on the TPU device")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    ln_vocab = math.log(get_config(TRAIN_ARCH).vocab)
    check(abs(losses[0] - ln_vocab) <= 1.0, f"first loss {losses[0]} not within 1.0 of {ln_vocab:.2f}")
    log(f"[train] {memory_line(device)}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="weights, prompts and corpus")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    device = jax.devices()[0]
    check(device.platform == "tpu", f"no TPU: JAX's first device is {device}")
    # host batches and requests are put where jnp.zeros lands: the default device
    check(jax.numpy.zeros(()).devices() == {device}, "the default device is not the TPU")
    log(f"[chip_smoke] {device.device_kind} x{len(jax.devices())}, "
        f"compile cache {enable_compile_cache()}")
    clock = CompileClock()

    serve_phase(device, clock, args.seed)
    gc.collect()  # drop the serve phase's params before the train phase
    log(f"[chip_smoke] between phases: {memory_line(device)}")
    train_phase(device, clock, args.seed)

    check("repro.kernels" not in sys.modules, "a Pallas kernel module was loaded on the path")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind, "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
