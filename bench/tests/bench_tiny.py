"""Shared by the benchmark's CPU tests: cells shrunk to a size a CPU
test holds, run in-process with the chip check bypassed."""
from __future__ import annotations

import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import harness  # noqa: E402

# Frames shrink to 80² at width 0.5.  Training keeps 160² at width 0.5
# and batch 8: below that, BN over a handful of samples makes three steps
# chaotic, and summation order alone moves the loss.
TINY_CFG = {"frames": {"image_size": 80, "width": 0.5, "head_channels": 64},
            "train": {"image_size": 160, "width": 0.5, "head_channels": 64}}
TINY_TRAFFIC = {"rate_per_s": 20, "pool_frames": 8, "calib_frames": 8,
                "batch": 8, "warm_launches": 1}


def _frames_metric(name, unit, better, source, layer):
    return {"name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "frame_p95_ms",
            "workloads": ["p2m_vww.frames"]}


# p2m_vww.frames is held out of BENCHMARK.json until a sweep on the chip
# fixes its rate; its files stay in bench/, and the tests run it from
# these entries, shaped as BENCHMARK.json would hold them.
HELD = {
    "workloads": [{"name": "p2m_vww.frames", "config": "p2m_vww",
                   "traffic": "frames.p2m_vww", "chips": 1,
                   "why": "held: single frames, open loop"}],
    "end_to_end": [{"name": "frame_p95_ms", "unit": "ms", "better": "lower",
                    "bound": 0.25, "source": "host_clock",
                    "workloads": ["p2m_vww.frames"]}],
    "per_layer": [_frames_metric(*m) for m in (
        ("gen_lag_p95_ms.frames", "ms", "lower", "host_clock",
         "load generator"),
        ("queue_p95_ms.frames", "ms", "lower", "host_clock", "scheduler"),
        ("batch_fill.frames", "%", "higher", "program_counter", "scheduler"),
        ("launch_ms.frames", "ms", "lower", "program_span", "engine"),
        ("step_device_ms.frames", "ms", "lower", "device_trace",
         "model step"),
        ("mfu.frames", "%", "higher", "device_trace", "model step"),
        ("p2m_conv_roofline.frames", "%", "higher", "device_trace",
         "kernels"),
        ("idle_share.frames", "%", "lower", "device_trace", "device"))],
}


def with_held(spec: dict) -> dict:
    return {**spec, **{k: spec[k] + HELD[k] for k in HELD}}


SPEC = with_held(harness.load_spec(REPO))
CELLS = [w["name"] for w in SPEC["workloads"]]


def tiny_cell(name: str, bench: Path = harness.BENCH, spec=None):
    cell = harness.Cell(spec or SPEC, name, bench=bench)
    cell.cfg.update(TINY_CFG[cell.traffic["kind"]])
    cell.traffic.update({k: v for k, v in TINY_TRAFFIC.items()
                         if k in cell.traffic})
    return cell


def run_tiny(cell, seed: int = 2**31 + 7, seconds: float = 1.0,
             trace: bool = False):
    from bench.run import run_cell

    return run_cell(cell, seed, seconds, trace, None)
