"""Finds a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix or per-layer metric
sits in files of its own, so a new cell, configuration or metric is new files
and new entries, never an edit:

  bench/configs/<config>.json   sizes as run, limits, settings
  bench/configs/<config>.py     its plain reference
  bench/flops/<config>.py       required operations per token
  bench/traffic/<traffic>.json  parameters of the traffic generator
  bench/metrics/<metric>.py     reader of one per-layer metric
  bench/drivers/<driver>.py     the system driver a configuration names
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parents[1]


def load_module(path: Path) -> ModuleType:
    """Import a file by path (metric files have dots in their names)."""
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem.replace('.', '_')}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict       # bench/configs/<config>.json
    traffic: dict      # bench/traffic/<traffic>.json
    end_to_end: list   # BENCHMARK.json entries this cell reports
    per_layer: list
    bench: Path        # the benchmark's directory

    def reference(self) -> ModuleType:
        return load_module(self.bench / "configs" / f"{self.config['name']}.py")

    def flops(self) -> ModuleType:
        return load_module(self.bench / "flops" / f"{self.config['name']}.py")

    def driver(self) -> ModuleType:
        return load_module(self.bench / "drivers" / f"{self.config['driver']}.py")

    def reader(self, metric: str) -> ModuleType:
        return load_module(self.bench / "metrics" / f"{metric}.py")

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.bench / "peaks.json").read_text())["devices"]
        if device_kind not in table:
            raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
        return table[device_kind]


def _applies(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def load_cell(workload: str, root: Path | None = None) -> Cell:
    """The cell named ``workload`` in ``<root>/BENCHMARK.json``."""
    root = root or BENCH.parent
    spec = json.loads((root / "BENCHMARK.json").read_text())
    bench = root / spec["paths"][0]
    entry = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    config = json.loads((bench / "configs" / f"{entry['config']}.json").read_text())
    traffic = json.loads((bench / "traffic" / f"{entry['traffic']}.json").read_text())
    return Cell(
        name=workload, chips=int(entry["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)],
        bench=bench)
