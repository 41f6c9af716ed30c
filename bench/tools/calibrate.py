"""Readings that the limits of a cell's ``correct`` are set from.

  python3 bench/tools/calibrate.py --workload <name> --seeds 1,2,3 [--control-seeds 3]
      [--seconds 10]

One process on the chip: for each seed, one run of the cell's driver (set-up,
a short window at the cell's own load, the reference), then on the first
``--control-seeds`` seeds the control in the program's place: the reference
with every matmul operand rounded to float8, read at the token it puts
first.  Prints one JSON line per seed with the program's and the control's
numbers; ``--out`` appends them to a file as well.  Not run by the benchmark
itself.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import jax  # noqa: E402

from harness import weights  # noqa: E402
from harness.common import CompileEvents, SetupClock, device_info, log  # noqa: E402
from harness.spec import load_cell, load_module  # noqa: E402

SERVE = load_module(BENCH / "drivers" / "serve_score.py")


def control_answers(cell, seed: int, prompts: list) -> list:
    """[(token, logprob)] that the float8 control would serve."""
    ref = cell.reference()
    specs = ref.param_specs(cell.config["model"])
    lg = SERVE.reference_logits(ref, weights.make_params(specs, seed), cell.config["model"],
                                prompts, quant="fp8")
    return [(int(c.argmax()), float(SERVE.log_softmax(c).max())) for c in lg]


def calibrate(cell, seed: int, seconds: float, devs, events, control: bool) -> dict:
    """One run of the cell's driver, then the program's (and the control's)
    numbers against the reference."""
    res = cell.driver().run(cell, seed=seed, seconds=seconds, trace_dir=None, devs=devs,
                            clock=SetupClock(time.perf_counter()), events=events)
    d = res["detail"]
    out = {"setup_s": res["setup_s"], "memory_peak_bytes": res["memory_peak"],
           "end_to_end": res["end_to_end"], "checks": res["checks"],
           "program": SERVE.answer_gaps(d["ref_logits"], d["served"])}
    if control:
        out["control_fp8"] = SERVE.answer_gaps(d["ref_logits"],
                                               control_answers(cell, seed, d["prompts"]))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    cell = load_cell(args.workload)
    dev, devs = device_info(cell.chips)
    if dev["platform"] != "tpu":
        log("[calibrate] needs a TPU")
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    events = CompileEvents()
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        line = {"workload": cell.name, "seed": seed,
                **calibrate(cell, seed, args.seconds, devs, events, k < args.control_seeds),
                "seconds": time.perf_counter() - t}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
