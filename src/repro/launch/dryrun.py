import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("REPRO_XLA_FLAGS",
                   "--xla_force_host_platform_device_count=512"))

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds abstract params/optimizer/cache specs (no
allocation), jits the train/prefill/serve step with shardings resolved
from the logical-axis plan, runs ``.lower().compile()``, and records:

* ``memory_analysis()`` — per-device bytes (proves the cell fits),
* ``cost_analysis()``   — FLOPs / bytes for §Roofline,
* collective bytes by op type, parsed from the optimized HLO,
* MODEL_FLOPS (6·N·D train / 2·N·D inference) for the usefulness ratio.

Results cache to ``benchmarks/results/dryrun/<arch>__<shape>__<mesh>.json``;
re-runs skip cached cells unless --force.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import time
import traceback
import zlib
from pathlib import Path

import jax

from repro.configs import ARCH_IDS, SHAPES, applicable, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.hlo_analysis import analyze as analyze_hlo
from repro.launch.mesh import make_production_mesh, mesh_chips
from repro.launch.specs import (
    build_decode_cell,
    build_prefill_cell,
    build_train_cell,
    plan_for_cell,
)
from repro.parallel import use_plan

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"


def model_flops(cfg, spec) -> float:
    n = cfg.param_count_estimate()
    if spec.kind == "train":
        return 6.0 * n * spec.global_batch * spec.seq_len
    if spec.kind == "prefill":
        return 2.0 * n * spec.global_batch * spec.seq_len
    return 2.0 * n * spec.global_batch  # decode: one token per sequence


def run_cell(arch: str, shape: str, multi_pod: bool, *, force: bool = False,
             plan_overrides: dict | None = None, tag: str = "",
             cfg_overrides: dict | None = None) -> dict:
    mesh_name = "multipod" if multi_pod else "pod"
    out_path = RESULTS_DIR / f"{arch}__{shape}__{mesh_name}{tag}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    cfg = get_config(arch)
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    spec = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = plan_for_cell(cfg, spec, mesh, overrides=plan_overrides)

    t0 = time.time()
    record = {
        "arch": arch, "shape": shape, "mesh": mesh_name,
        "mesh_shape": dict(mesh.shape), "chips": mesh_chips(mesh),
        "kind": spec.kind, "status": "error", "tag": tag,
    }
    try:
        with use_plan(plan), mesh:
            if spec.kind == "train":
                step, abstract, shardings = build_train_cell(cfg, spec, plan)
                jitted = jax.jit(step, in_shardings=shardings,
                                 out_shardings=(shardings[0], None))
                lowered = jitted.lower(*abstract)
            elif spec.kind == "prefill":
                step, abstract, shardings = build_prefill_cell(cfg, spec, plan)
                jitted = jax.jit(step, in_shardings=shardings)
                lowered = jitted.lower(*abstract)
            else:
                step, abstract, shardings = build_decode_cell(cfg, spec, plan)
                jitted = jax.jit(step, in_shardings=shardings,
                                 out_shardings=(None, shardings[1]))
                lowered = jitted.lower(*abstract)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        record["lower_s"] = round(t_lower, 2)
        record["compile_s"] = round(t_compile, 2)

        mem = compiled.memory_analysis()
        if mem is not None:
            for attr in ("generated_code_size_in_bytes",
                         "argument_size_in_bytes", "output_size_in_bytes",
                         "temp_size_in_bytes", "alias_size_in_bytes"):
                v = getattr(mem, attr, None)
                if v is not None:
                    record.setdefault("memory", {})[attr] = int(v)

        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        # raw XLA numbers (loop bodies counted ONCE — kept for reference)
        record["xla_cost_flops_bodyonce"] = float(cost.get("flops", 0.0))
        record["xla_cost_bytes_bodyonce"] = float(cost.get("bytes accessed", 0.0))

        hlo = compiled.as_text()
        # loop-aware static analysis (per-device): dot FLOPs, HBM traffic,
        # collective bytes — see launch/hlo_analysis.py
        ana = analyze_hlo(hlo)
        record["flops_per_device"] = ana["flops"]
        record["bytes_per_device"] = ana["traffic_bytes"]
        record["collectives"] = ana["collectives"]
        record["hlo_bytes"] = len(hlo)
        record["model_flops"] = model_flops(cfg, spec)
        record["param_count"] = cfg.param_count_estimate()
        record["status"] = "ok"
        hlo_path = RESULTS_DIR / f"{arch}__{shape}__{mesh_name}{tag}.hlo.z"
        RESULTS_DIR.mkdir(parents=True, exist_ok=True)
        hlo_path.write_bytes(zlib.compress(hlo.encode(), 6))
    except Exception as e:  # record failures — they are bugs to fix
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(record, indent=1))
    status = record["status"]
    extra = ("" if status == "ok"
             else f"  {record.get('error', '')[:120]}")
    print(f"[dryrun] {arch:22s} {shape:12s} {mesh_name:8s} {status}{extra}",
          flush=True)
    return record


def reanalyze() -> None:
    """Recompute analysis fields from the saved .hlo.z artifacts (no
    recompilation) — used when the static analyzer improves."""
    n = 0
    for jpath in sorted(RESULTS_DIR.glob("*.json")):
        hpath = jpath.with_suffix("").with_suffix("")  # strip .json
        hpath = jpath.parent / (jpath.stem + ".hlo.z")
        if not hpath.exists():
            continue
        record = json.loads(jpath.read_text())
        hlo = zlib.decompress(hpath.read_bytes()).decode()
        ana = analyze_hlo(hlo)
        record["flops_per_device"] = ana["flops"]
        record["bytes_per_device"] = ana["traffic_bytes"]
        record["collectives"] = ana["collectives"]
        jpath.write_text(json.dumps(record, indent=1))
        n += 1
    print(f"[dryrun] reanalyzed {n} cells from saved HLO")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"], default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--reanalyze", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    if args.reanalyze:
        reanalyze()
        return

    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]

    n_ok = n_fail = n_skip = 0
    for arch in archs:
        cfg = get_config(arch)
        shapes = [args.shape] if args.shape else list(SHAPES)
        for shape in shapes:
            if not applicable(arch, cfg.family, shape):
                n_skip += 1
                continue
            for mp in meshes:
                rec = run_cell(arch, shape, mp, force=args.force)
                if rec["status"] == "ok":
                    n_ok += 1
                else:
                    n_fail += 1
    print(f"[dryrun] done: {n_ok} ok, {n_fail} failed, {n_skip} skipped "
          f"(inapplicable cells)")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
