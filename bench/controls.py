"""Readings that the correctness limits are set against: the precision
control (the reference, one precision step below the configuration's,
in the program's place) and, for training, a planted fault, on several
seeds at the cell's own size.  The benchmark's runs never run this.

    python bench/controls.py --workload <cell> --seeds 1,2,3 [--sound-seconds S]

Prints one JSON line per seed with each compared number's reading.  With
``--sound-seconds``, each seed first gets a whole run of the cell with an
``S``-second window, whose compared numbers are printed as
``program.<name>``: for training, whose numbers come from the steps
before the window, ``S = 0`` reads a dozen seeds in one process.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    from bench.run import Run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sound-seconds", type=float, default=None)
    args = ap.parse_args(argv)
    try:
        cell = harness.Cell(harness.load_spec(), args.workload)
        device = harness.device_info(cell.chips)
    except harness.BenchError as e:
        print(f"[controls] {e}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(harness.CHECKOUT / "src"))
    harness.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        out = {"workload": cell.name, "seed": seed}
        if args.sound_seconds is not None:
            run = Run(cell, seed, args.sound_seconds, False, device)
            checks = cell.runner.run(run)["checks"]
            out.update({f"program.{n}": v for n, v, _ in checks.rows})
        out.update(cell.runner.control(Run(cell, seed, 0.0, False, device)))
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
