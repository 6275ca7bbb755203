"""Single-cell dry-run walkthrough: lower + compile one (arch × shape)
on the production 256-chip mesh and print the roofline terms.

This is the interactive version of `python -m repro.launch.dryrun`;
see EXPERIMENTS.md §Dry-run for the full 40-cell table.

Run:  PYTHONPATH=src python examples/dryrun_demo.py --arch llama3.2-1b
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--multipod", action="store_true")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.dryrun import run_cell
    from repro.launch.mesh import PRODUCTION_DEVICE_KIND, chip_peaks

    enable_compile_cache()
    rec = run_cell(args.arch, args.shape, args.multipod, force=True,
                   tag="-demo")
    if rec["status"] != "ok":
        print(rec.get("error"))
        return
    chips = rec["chips"]
    peaks = chip_peaks(PRODUCTION_DEVICE_KIND)
    comp = rec["flops_per_device"] / peaks.flops_bf16
    mem = rec["bytes_per_device"] / peaks.hbm_bytes_per_s
    coll = rec["collectives"]["total_bytes"] / peaks.ici_bytes_per_s
    print(f"\n{args.arch} × {args.shape} on {chips} chips:")
    print(f"  compiled in {rec['compile_s']:.1f}s "
          f"(HLO {rec['hlo_bytes']/1e6:.1f} MB)")
    if "memory" in rec:
        m = rec["memory"]
        print(f"  per-device memory: args {m.get('argument_size_in_bytes',0)/1e9:.2f} GB, "
              f"temps {m.get('temp_size_in_bytes',0)/1e9:.2f} GB")
    print(f"  roofline terms: compute {comp*1e3:.1f} ms | memory {mem*1e3:.1f} ms "
          f"| collective {coll*1e3:.1f} ms")
    dom = max((comp, 'compute'), (mem, 'memory'), (coll, 'collective'))[1]
    print(f"  dominant: {dom}")


if __name__ == "__main__":
    main()
