"""Run one cell of the chip benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, metrics and limits are read
from ``BENCHMARK.json`` and the files it names (see `bench/harness.py`).
The run makes its weights and inputs from ``--seed``, warms up the
cell's shapes (set-up), measures for ``--seconds``, checks what the timed
path produced against the plain reference, and prints one JSON line
last on stdout.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of the window.
Without a TPU, with fewer chips than the cell needs, or when set-up
finds that the program would not see the inputs the reference reads, it
exits 1 and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import sys
import time
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


class Run:
    """One run of one cell: what runners read and report into."""

    def __init__(self, cell, seed: int, seconds: float, trace: bool,
                 device: dict | None):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.device = trace, device
        self.compiles = harness.CompileCounter()
        self.spans: list | None = None  # host spans, while tracing
        self.setup_s = None
        self.compiles_setup = self.compiles_window = 0
        self.memory_peak = 0

    def start_window(self):
        self.setup_s = harness.process_age_s()
        self.compiles_setup = self.compiles.count

    def end_window(self):
        self.compiles_window = self.compiles.count - self.compiles_setup

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span on the wall clock the profiler stamps its events
        with; recorded only while a trace is captured."""
        if self.spans is None:
            yield
            return
        t = time.time_ns()
        try:
            yield
        finally:
            self.spans.append([name, t, time.time_ns() - t])

    def read_memory(self):
        if self.device is not None:
            self.memory_peak = harness.memory_peak_bytes(self.cell.chips)


def per_layer(cell, data: dict, device: dict | None) -> dict:
    ctx = dict(data, device_kind=(device or {}).get("kind", "TPU v5 lite"),
               cell=cell.name)
    out = {}
    for m in cell.metrics("per_layer"):
        value = cell.reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool,
             device: dict | None) -> tuple[dict, harness.Checks]:
    """Set up, measure and check one cell; returns (result, checks)."""
    from bench.tracing import Reduction

    run = Run(cell, seed, seconds, trace, device)
    out = cell.runner.run(run)
    device = dict(device or {"platform": "none", "kind": "none",
                             "count": 0})
    device["memory_peak_bytes"] = run.memory_peak
    data = out["data"]
    if trace:
        tr = data.get("trace")
        red = Reduction(tr) if tr and tr["devices"] else None
        data["reduction"] = red
        metrics = per_layer(cell, data, run.device)
        if red is not None:
            device["busy_s"] = red.busy_s()
            device["window_s"] = red.window_s
            print(f"[trace] {red.alignment()}", file=sys.stderr, flush=True)
    else:
        metrics = {}
        for m in cell.metrics("end_to_end"):
            value = run.setup_s if m["name"] == "setup_s" \
                else out["e2e"][m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    print(f"[setup] setup_s {run.setup_s!r} compiles_in_setup "
          f"{run.compiles_setup} cold {run.compiles_setup > 0} "
          f"compiles_in_window {run.compiles_window}", file=sys.stderr,
          flush=True)
    checks = out["checks"]
    result = {"correct": checks.correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if trace and data.get("reduction") is not None:
        red = data["reduction"]
        result["breakdown"] = {"device_ops": red.top_ops(10),
                               "idle_gaps": red.idle_gaps(10)}
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")
    try:
        cell = harness.Cell(harness.load_spec(), args.workload)
        device = harness.device_info(cell.chips)
    except harness.BenchError as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(harness.CHECKOUT / "src"))
    harness.enable_compile_cache()
    try:
        result, checks = run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), device)
    except harness.BenchError as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 1
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
