"""Find the highest rate an open-loop scoring cell sustains, once, on the chip,
and read the host's stalls at each setting.

  python3 bench/tools/sweep_rate.py --workload <name> --rates 10,15,20 [--seconds 20]
      [--connections 8,32] [--trace]

One process: for each connection count and rate, one run of the cell's
driver with the mix's ``rate_per_s`` and ``connections`` replaced (and eight
requests checked).  Each prints completed / offered, latency percentiles,
the load generator's lateness and whether the backlog grew (mean latency of
the last third of requests against the first third).  Sustained means
completed / offered >= 0.99 and no growing backlog; the cell's rate is then
fixed by hand at about 0.8 of the highest sustained one.  With ``--trace``
each window is traced, and the line adds the device's idle share of the time
a request is in flight and every stretch over 0.1 s in which the device was
idle while a request was in flight.  Not run by the benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import numpy as np  # noqa: E402

from harness import trace  # noqa: E402
from harness.common import CompileEvents, SetupClock, device_info, log, quantile  # noqa: E402
from harness.spec import load_cell  # noqa: E402

STALL_S = 0.1


def stalls(tr) -> tuple[float, list[float]]:
    """(idle share % of in-flight time, idle stretches over STALL_S while in flight)."""
    inflight = trace.merge(trace.clip(tr.host_spans.get("bench.request", []), *tr.window))
    total = trace.length(inflight)
    out = []
    for s, e in inflight:
        t = s
        for bs, be in trace.clip(tr.busy[0], s, e) + [(e, e)]:
            if bs - t > STALL_S:
                out.append(bs - t)
            t = max(t, be)
    share = 100.0 * (1 - tr.busy_within(inflight) / total) if total > 0 else float("nan")
    return share, sorted(out, reverse=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--connections", default=None, help="comma-separated; default the mix's")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--trace", action="store_true")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    cell = load_cell(args.workload)
    dev, devs = device_info(cell.chips)
    if dev["platform"] != "tpu":
        log("[sweep] needs a TPU")
        return 2
    enable_compile_cache()
    events = CompileEvents()
    conns = [int(c) for c in args.connections.split(",")] if args.connections else [
        cell.traffic["connections"]]
    for c in conns:
        for r in [float(x) for x in args.rates.split(",")]:
            run = dataclasses.replace(cell, traffic={**cell.traffic, "rate_per_s": r,
                                                     "connections": c},
                                      config={**cell.config, "check_requests": 8})
            with tempfile.TemporaryDirectory() as tmp:
                out = run.driver().run(run, seed=args.seed, seconds=args.seconds,
                                       trace_dir=Path(tmp) / "t" if args.trace else None,
                                       devs=devs, clock=SetupClock(time.perf_counter()),
                                       events=events)
            d = out["detail"]
            lat, late = d["latency_s"], d["lateness_s"]
            n = len(lat)
            third = max(1, n // 3)
            line = {
                "rate_per_s": r, "connections": c, "offered": n,
                "completed_share": (n - out["failed"]) / n,
                "p50_ms": 1e3 * quantile(lat, 0.5), "p95_ms": 1e3 * quantile(lat, 0.95),
                "p99_ms": 1e3 * quantile(lat, 0.99),
                "first_third_mean_ms": 1e3 * np.mean([x for x in lat[:third] if x is not None]),
                "last_third_mean_ms": 1e3 * np.mean([x for x in lat[-third:] if x is not None]),
                "lateness_p99_ms": 1e3 * quantile(late, 0.99),
                "lateness_max_ms": 1e3 * max(late, default=0.0),
                "tokens_per_s": out["end_to_end"]["score_tokens_per_s"],
                "checks": {k: v["value"] for k, v in out["checks"].items()},
                "stalls_s": {k: [d for _, d in v] for k, v in d["stalls"].items()},
            }
            if out["trace"] is not None:
                share, over = stalls(out["trace"])
                line.update(inflight_idle_pct=share, stalls_over_100ms_s=over,
                            busy_s=out["trace"].busy_s, window_s=out["trace"].window_s)
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
