"""A copy of the benchmark with tiny sizes, for CPU tests of the harness."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
SERVE = "serve.internlm2_1_8b.single_poisson"

TINY = {
    "configs/internlm2_1_8b.json": {
        "model": {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
                  "num_key_value_heads": 2, "intermediate_size": 192, "vocab_size": 512,
                  "rope_theta": 1000000.0, "rms_norm_eps": 1e-5, "tie_word_embeddings": True},
        "program": {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "d_ff": 192,
                    "vocab": 512, "shard_groups": 4, "rope_theta": 1000000.0},
        "service": {"max_seq": 64},
        "check_requests": 40,
    },
    "traffic/single_poisson.json": {"rate_per_s": 20.0, "prompt_median": 16, "prompt_min": 4,
                                    "prompt_max": 64, "connections": 4},
}


def make_root(tmp: Path, limits: dict | None = None) -> Path:
    """``tmp`` gets BENCHMARK.json and a copy of bench/ at tiny sizes."""
    shutil.copy(REPO / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp / "bench", ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for rel, change in TINY.items():
        p = tmp / "bench" / rel
        doc = json.loads(p.read_text())
        doc.update(change)
        if rel.startswith("configs/") and limits:
            doc["limits"] = {**doc["limits"], **limits}
        p.write_text(json.dumps(doc))
    (tmp / "src").symlink_to(REPO / "src")
    return tmp
