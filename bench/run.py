"""Run one benchmark cell once and print its result line.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name from
``BENCHMARK.json`` (see ``harness/spec.py``).  The run refuses any device but
a TPU, makes its weights and inputs from ``--seed``, warms up the cell's own
shapes (set-up), measures for ``--seconds``, then checks what the timed path
produced against the configuration's plain reference.  With ``--trace 0`` the
metrics are the cell's end-to-end ones; with ``--trace 1`` a profiled run
gives its per-layer metrics.  The last stdout line is one JSON object; the
last stderr lines give each compared number beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from harness.common import CompileEvents, SetupClock, device_info, log, report  # noqa: E402
from harness.spec import load_cell  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, root: Path | None = None, require_tpu: bool = True) -> int:
    """``root``: the checkout holding BENCHMARK.json (default: this file's).
    ``require_tpu=False`` lets a test drive a run on the CPU."""
    args = parse(argv)
    root = root or BENCH.parent
    cell = load_cell(args.workload, root)
    clock = SetupClock(T_START)
    with clock.phase("import"):
        src = str(root / "src")
        if src not in sys.path:
            sys.path.insert(0, src)
        import jax

        from repro.launch.compile_cache import enable_compile_cache

    with clock.phase("device"):
        dev, devs = device_info(cell.chips)
    if require_tpu and (dev["platform"] != "tpu" or len(jax.devices()) < cell.chips):
        log(f"[bench] refused: needs {cell.chips} TPU chip(s), JAX has "
            f"{len(jax.devices())} {dev['platform']} device(s)")
        return 2
    peak = cell.peaks(dev["kind"]) if require_tpu else {"bf16_flops": 197e12}
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    events = CompileEvents()
    log(f"[bench] {cell.name} seed {args.seed} on {dev['kind']} x{dev['count']}, "
        f"compile cache {cache}")

    with tempfile.TemporaryDirectory() as tmp:
        out = cell.driver().run(
            cell, seed=args.seed, seconds=args.seconds,
            trace_dir=Path(tmp) / "trace" if args.trace else None,
            devs=devs, clock=clock, events=events)

    log(f"[bench] setup_s {out['setup_s']!r} parts "
        f"{json.dumps({k: round(v, 3) for k, v in clock.parts.items()})} compile events "
        f"{json.dumps({k: round(v, 3) for k, v in events.seconds.items()})} "
        f"counts {json.dumps(events.count)}")
    log(f"[bench] compiles inside the window: {json.dumps(out['compiles_in_window'])}")

    device = {**dev, "memory_peak_bytes": out["memory_peak"]}
    result = {"correct": None, "attempted": out["attempted"], "failed": out["failed"]}
    if args.trace:
        tr = out["trace"]
        rec = SimpleNamespace(trace=tr, counters=out["counters"], peak=peak)
        metrics = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
        result["breakdown"] = {"device_ops": tr.top_ops(10), "idle_gaps": tr.idle_gaps(10)}
    else:
        values = {**out["end_to_end"], "setup_s": out["setup_s"]}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    checks = out["checks"]
    result["correct"] = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                            for c in checks.values())
    result.update(metrics=metrics, device=device)
    log(f"[bench] run took {clock.total():.1f}s")
    report(checks, result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
