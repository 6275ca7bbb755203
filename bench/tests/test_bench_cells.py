"""Every cell of BENCHMARK.json runs end to end at a tiny size on the CPU
(the chip check bypassed in-process) and prints a well-formed result;
the real entry refuses to run without a TPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench_tiny import CELLS, REPO, harness, run_tiny, tiny_cell


def _well_formed(line: str, cell) -> dict:
    res = json.loads(line)
    assert list(res)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in res
    assert isinstance(res["correct"], bool)
    for name, m in res["metrics"].items():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], (int, float))
    for name, c in res["checks"].items():
        assert set(c) == {"value", "limit"}
    return res


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_tiny_on_cpu(name, capsys):
    cell = tiny_cell(name)
    result, checks = run_tiny(cell)
    harness.emit(result, checks)
    out = capsys.readouterr()
    res = _well_formed(out.out.strip().splitlines()[-1], cell)
    assert res["correct"], res["checks"]
    want = {m["name"] for m in cell.metrics("end_to_end")}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["attempted"] > 0
    # the compared numbers close stderr, each beside its limit
    tail = out.err.strip().splitlines()[-len(checks.rows):]
    assert all(line.startswith("[check] ") for line in tail)


def test_traced_run_on_cpu_reports_only_what_it_reads(capsys):
    cell = tiny_cell("p2m_vww.frames")
    result, checks = run_tiny(cell, trace=True)
    allowed = {m["name"] for m in cell.metrics("per_layer")}
    assert set(result["metrics"]) <= allowed
    # host-side readers find their numbers; trace readers find no TPU
    assert "launch_ms.frames" in result["metrics"]
    assert "idle_share.frames" not in result["metrics"]


@pytest.mark.parametrize("name", CELLS[:1])
def test_real_entry_fails_without_a_chip(name):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", name, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
