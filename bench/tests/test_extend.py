"""A cell, a configuration and a per-layer metric are added as new files and
entries: the harness finds them by name, with no edit to any file it had."""
import json
import shutil
from types import SimpleNamespace

from harness.spec import BENCH, load_cell


def test_new_cell_config_and_metric_by_files_alone(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    b = tmp_path / "bench"
    conf = json.loads((b / "configs" / "internlm2_1_8b.json").read_text())
    conf["name"] = "throwaway_cfg"
    (b / "configs" / "throwaway_cfg.json").write_text(json.dumps(conf))
    shutil.copy(b / "configs" / "internlm2_1_8b.py", b / "configs" / "throwaway_cfg.py")
    shutil.copy(b / "flops" / "internlm2_1_8b.py", b / "flops" / "throwaway_cfg.py")
    (b / "traffic" / "throwaway_mix.json").write_text(json.dumps({"kind": "open_loop", "rate_per_s": 1.0}))
    (b / "metrics" / "throwaway_metric.py").write_text("def read(rec):\n    return 42.0\n")
    spec["configs"].append({"name": "throwaway_cfg", "source": "https://example.org",
                            "file": "bench/configs/throwaway_cfg.json", "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "serve.throwaway", "config": "throwaway_cfg",
                              "traffic": "throwaway_mix", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "throwaway_metric", "unit": "%", "better": "higher",
                              "source": "program_counter", "layer": "device",
                              "moves": "score_p95_ms", "workloads": ["serve.throwaway"]})
    for m in spec["end_to_end"]:
        if "workloads" in m and m["name"].startswith("score"):
            m["workloads"].append("serve.throwaway")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = load_cell("serve.throwaway", tmp_path)
    assert cell.config["name"] == "throwaway_cfg" and cell.traffic["rate_per_s"] == 1.0
    assert [m["name"] for m in cell.per_layer] == ["throwaway_metric"]
    assert {m["name"] for m in cell.end_to_end} == {"score_p95_ms", "score_tokens_per_s", "setup_s"}
    assert cell.reader("throwaway_metric").read(SimpleNamespace()) == 42.0
    assert cell.driver().run.__module__ and cell.flops().forward_flops(conf["model"], 8) > 0
    assert cell.reference().param_specs(conf["model"])
    after = {p: p.read_bytes() for p in before}
    assert after == before  # every file the benchmark had is unchanged
