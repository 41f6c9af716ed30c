import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax import: jax locks the device count on first init.
# A virtual CPU mesh: pinned to the CPU so it never takes an accelerator.
# This forcing is dry-run-only — tests/benches see the single real device.

"""Multi-pod dry-run: lower + compile every (arch × shape) on the production
meshes and extract the roofline terms from the compiled artifact.

Usage:
  python -m repro.launch.dryrun --arch internlm2_1_8b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod]   # every supported cell

Each cell writes experiments/artifacts/<arch>__<shape>__<mesh>.json with:
  memory_analysis (per-device bytes), cost_analysis (FLOPs/bytes),
  per-collective byte totals parsed from the partitioned HLO, model FLOPs,
  and the three roofline terms (seconds) with the dominant bottleneck.
"""
import argparse
import json
import re
import time
import traceback
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import SHAPES, ARCH_IDS, cell_supported, get_config, input_specs
from ..distributed.sharding import ShardingCtx, tree_shardings
from ..models.lm import LM
from ..train.optimizer import OptimizerConfig
from ..train.step import TrainConfig, build_train_step, step_shardings
from .mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh

ARTIFACTS = Path(__file__).resolve().parents[3] / "experiments" / "artifacts"

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_TYPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _type_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collectives(hlo_text: str) -> dict:
    """Sum per-device operand bytes of every collective op in partitioned HLO."""
    out = {op: {"count": 0, "bytes": 0} for op in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for op in _COLLECTIVES:
            marker = f" {op}("
            idx = stripped.find(marker)
            if idx < 0 or stripped.startswith("//"):
                continue
            # result types appear before the op name on the line
            types = _TYPE_RE.findall(stripped[:idx])
            nbytes = sum(_type_bytes(t, d) for t, d in types)
            mult = 2.0 if op == "all-reduce" else 1.0  # ring AR moves ~2x
            out[op]["count"] += 1
            out[op]["bytes"] += int(nbytes * mult)
            break
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items() if isinstance(v, dict))
    return out


def model_flops(cfg, shape) -> float:
    """6·N_active·tokens (train), 2·N_active·tokens (prefill/decode fwd)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch  # decode: one token per seq


def lower_cell(arch: str, shape_name: str, mesh, *, remat_policy=None, rules=None,
               extra_cfg=None, matmul_accum=None, cache_dtype=None):
    """Build + lower + compile one cell; returns (lowered, compiled, meta)."""
    import dataclasses

    if matmul_accum is not None:  # §Perf lever: bf16 halves backward psums
        from ..models.layers import set_matmul_accum_dtype
        set_matmul_accum_dtype(getattr(jnp, matmul_accum))

    cfg = get_config(arch)
    if remat_policy:
        cfg = dataclasses.replace(cfg, remat_policy=remat_policy)
    if extra_cfg:
        cfg = dataclasses.replace(cfg, **extra_cfg)
    shape = SHAPES[shape_name]
    ok, why = cell_supported(cfg, shape)
    if not ok:
        raise ValueError(f"unsupported cell: {why}")

    base_rules = dict(cfg.logical_rules)
    if rules:
        base_rules.update(rules)
    if shape.kind == "decode":
        # batch=1 long-context cells can't shard batch; the KV cache shards
        # on sequence instead (distributed flash-decode)
        dp = int(np.prod([mesh.shape[a] for a in ("pod", "data") if a in mesh.axis_names]))
        if shape.global_batch % dp:
            base_rules["batch"] = None
    ctx = ShardingCtx(mesh, base_rules)
    model = LM(cfg, ctx)
    params_abs, axes = model.init(jax.random.key(0), abstract=True)
    p_sh = tree_shardings(axes, mesh, ctx.rules)
    specs = input_specs(cfg, shape)

    if shape.kind == "train":
        opt_name = "adafactor" if cfg.param_count() > 20e9 else "adamw"
        tc = TrainConfig(optimizer=OptimizerConfig(name=opt_name))
        train_step, opt_init = build_train_step(model, tc, axes)
        opt_abs = jax.eval_shape(opt_init, params_abs)
        (p_s, o_s, b_s), (po_s, oo_s, m_s) = step_shardings(model, tc, axes, params_abs, shape)
        fn = jax.jit(train_step, in_shardings=(p_s, o_s, b_s),
                     out_shardings=(po_s, oo_s, m_s), donate_argnums=(0, 1))
        lowered = fn.lower(params_abs, opt_abs, specs)
    elif shape.kind == "prefill":
        def prefill_step(params, batch):
            return model.prefill(params, batch)
        from ..configs.base import batch_logical_axes
        b_sh = tree_shardings(batch_logical_axes(cfg, shape), mesh, ctx.rules)
        fn = jax.jit(prefill_step, in_shardings=(p_sh, b_sh), out_shardings=None)
        lowered = fn.lower(params_abs, specs)
    else:  # decode
        B = shape.global_batch
        kv_dtype = getattr(jnp, cache_dtype) if cache_dtype else jnp.bfloat16
        caches_abs = jax.eval_shape(
            partial(model.init_caches, B, shape.seq_len, dtype=kv_dtype))
        seq_sharded = model._seq_sharded_decode((B,))
        c_axes = model.cache_logical_axes(seq_sharded)
        c_sh = tree_shardings(c_axes, mesh, ctx.rules)
        tok_sh = tree_shardings({"tokens": (None if seq_sharded else "batch", None)},
                                mesh, ctx.rules)["tokens"]

        def serve_step(params, caches, tokens, pos):
            return model.decode_step(params, caches, tokens, pos)

        fn = jax.jit(serve_step, in_shardings=(p_sh, c_sh, tok_sh, None),
                     out_shardings=(None, c_sh), donate_argnums=(1,))
        lowered = fn.lower(params_abs, caches_abs, specs["tokens"], specs["pos"])

    compiled = lowered.compile()
    return lowered, compiled, {"cfg": cfg, "shape": shape}


def analyze(compiled, cfg, shape, mesh) -> dict:
    from .hloanalysis import analyze_module

    chips = mesh.devices.size
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # trip-count-aware analysis (XLA's own counts loop bodies once)
    c = analyze_module(hlo, default_trips=cfg.n_superblocks)
    flops_dev = c.flops
    bytes_dev = c.bytes
    mf = model_flops(cfg, shape)

    compute_s = flops_dev / PEAK_FLOPS_BF16
    memory_s = bytes_dev / HBM_BW
    collective_s = c.total_collective_bytes / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s, "collective_s": collective_s}
    dominant = max(terms, key=terms.get)

    return {
        "chips": chips,
        "memory_analysis": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
        },
        "hlo_flops_per_device": flops_dev,
        "hlo_bytes_per_device": bytes_dev,
        "xla_cost_analysis_raw": {"flops": float(cost.get("flops", 0.0)),
                                  "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
                                  "note": "loop bodies counted once by XLA"},
        "collectives": c.as_dict(),
        "model_flops_global": mf,
        "model_flops_per_device": mf / chips,
        "useful_flops_ratio": (mf / chips) / flops_dev if flops_dev else None,
        "roofline": {**terms, "dominant": dominant,
                     "step_time_lower_bound_s": max(terms.values()),
                     "roofline_fraction_vs_compute": (
                         compute_s / max(terms.values()) if max(terms.values()) > 0 else None)},
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Path = ARTIFACTS,
             **kw) -> dict:
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    record: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    t0 = time.time()
    try:
        cfg = get_config(arch)
        ok, why = cell_supported(cfg, SHAPES[shape_name])
        if not ok:
            record.update(status="skipped", reason=why)
        else:
            mesh = make_production_mesh(multi_pod=multi_pod)
            lowered, compiled, meta = lower_cell(arch, shape_name, mesh, **kw)
            record.update(status="ok", **analyze(compiled, meta["cfg"], meta["shape"], mesh))
            print(f"[dryrun] {arch} × {shape_name} × {mesh_name}: OK "
                  f"dominant={record['roofline']['dominant']}")
            print(f"  memory_analysis: {record['memory_analysis']}")
            print(f"  cost_analysis: flops/dev={record['hlo_flops_per_device']:.3e} "
                  f"bytes/dev={record['hlo_bytes_per_device']:.3e}")
    except Exception as e:  # a failed cell is a bug — record it loudly
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
        print(f"[dryrun] {arch} × {shape_name} × {mesh_name}: FAILED {e}")
    record["wall_s"] = round(time.time() - t0, 1)
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"{arch}__{shape_name}__{mesh_name}.json"
    path.write_text(json.dumps(record, indent=2, default=str))
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()

    if args.all:
        for arch in ARCH_IDS:
            for shape in SHAPES:
                run_cell(arch, shape, args.multi_pod)
        return
    assert args.arch and args.shape, "--arch/--shape or --all"
    rec = run_cell(args.arch, args.shape, args.multi_pod)
    if rec["status"] == "error":
        raise SystemExit(1)


if __name__ == "__main__":
    main()
