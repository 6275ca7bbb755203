"""A later change adds a configuration, a traffic mix and a per-layer
metric as new files and entries only: the harness finds them by name."""
from __future__ import annotations

import hashlib
import json
import shutil

from bench_tiny import REPO, harness, run_tiny, tiny_cell, with_held


def _digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_config_mix_and_metric_are_found_without_edits(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = _digest(tmp_path)
    b = tmp_path / "bench"

    cfg = json.loads((b / "configs" / "p2m_vww.json").read_text())
    cfg["name"] = "p2m_vww_other"
    (b / "configs" / "p2m_vww_other.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "frames.p2m_vww.json").read_text())
    mix["rate_per_s"] = 7
    (b / "traffic" / "frames_slow.json").write_text(json.dumps(mix))
    (b / "limits" / "p2m_vww_other.frames_slow.json").write_text(
        (b / "limits" / "p2m_vww.frames.json").read_text())
    (b / "metrics" / "answered.frames_slow.py").write_text(
        "def read(ctx):\n    return float(ctx['frames'])\n")

    spec = with_held(json.loads((REPO / "BENCHMARK.json").read_text()))
    spec["configs"].append({"name": "p2m_vww_other", "source": "x",
                            "file": "bench/configs/p2m_vww_other.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "p2m_vww_other.frames_slow",
                              "config": "p2m_vww_other",
                              "traffic": "frames_slow", "chips": 1,
                              "why": "x"})
    for m in spec["end_to_end"]:
        if m["name"] == "frame_p95_ms":
            m["workloads"].append("p2m_vww_other.frames_slow")
    spec["per_layer"].append({"name": "answered.frames_slow", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "scheduler", "moves": "frame_p95_ms",
                              "workloads": ["p2m_vww_other.frames_slow"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    after = _digest(tmp_path)
    assert all(after[k] == v for k, v in before.items())  # nothing edited

    found = harness.Cell(spec, "p2m_vww_other.frames_slow", bench=b)
    assert found.cfg["name"] == "p2m_vww_other"
    assert found.traffic["rate_per_s"] == 7
    cell = tiny_cell("p2m_vww_other.frames_slow", bench=b, spec=spec)
    cell.traffic["rate_per_s"] = 7
    result, checks = run_tiny(cell, trace=True)
    assert result["metrics"]["answered.frames_slow"]["value"] == 7.0
    assert result["correct"]
