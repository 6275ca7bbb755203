"""Record one traced training step with its scope map, on the chip.

    python bench/record_scopes.py --workload p2m_vww.train_b32 --seed <n> \\
        --out bench/data/train_step_scopes.json.gz

Runs the cell once with the profiler on (as ``bench/run.py --trace 1``
does, for ``--seconds``), keeps the operations of the window's middle
`jit_step` program on the first chip in the compact form of
`bench/tracing.py`, with a ``bench.window`` span over it, and adds
the step's instruction → op_name map under ``"scopes"``.  The part
readers' test reads the result (`bench/tests/test_bench_scopes.py`).
"""
from __future__ import annotations

import argparse
import gzip
import json
import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness, scopes  # noqa: E402


def one_step(trace: dict) -> dict:
    """The compact trace cut to the middle `jit_step` program of its
    first chip: that program, the operations that start inside it, and
    a ``bench.window`` span over it."""
    for plane, d in trace["devices"].items():
        steps = sorted((m for m in d["modules"] if "jit_step" in m[0]),
                       key=lambda m: m[1])
        if steps:
            m = steps[len(steps) // 2]
            ops = [e for e in d["ops"] if m[1] <= e[1] < m[1] + m[2]]
            return {"devices": {plane: {"ops": ops, "modules": [m]}},
                    "host": [["bench.window", m[1], m[2]]]}
    raise ValueError("the trace has no jit_step program")


def main(argv=None) -> int:
    from bench.run import Run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args(argv)
    try:
        cell = harness.Cell(harness.load_spec(), args.workload)
        device = harness.device_info(cell.chips)
    except harness.BenchError as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(harness.CHECKOUT / "src"))
    harness.enable_compile_cache()
    run = Run(cell, args.seed, args.seconds, True, device)
    data = cell.runner.run(run)["data"]
    rec = one_step(data["trace"])
    rec["scopes"], _ = scopes.train_step_scopes(data["cfg"], data["batch"])
    args.out.parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(args.out, "wt") as f:
        json.dump(rec, f)
    from bench.tracing import Reduction

    ctx = {"reduction": Reduction(rec), "scopes": rec["scopes"]}
    print(json.dumps({"out": str(args.out), "device": device,
                      "parts_ms": scopes.step_parts(ctx)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
