"""Find the knee of a frames cell: the highest offered rate at which
every frame is answered (none evicted, none failed) and the 95th
percentile of the queue time stays under two mean launch times, so that
no backlog builds.

    python bench/sweep.py --workload <frames cell> --seed <n> --seconds <s> \\
        --rates 100,200,300

One process, one set-up; each rate gets a fresh engine on the same
compiled program and the cell's open-loop schedule at that rate.  Prints
one JSON line per rate, then the knee (the highest rate below the
first that fails) and 0.8 × the knee.  A cell's fixed rate is then
written as a number into its traffic file.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402


def sweep_rate(run, drv, params, state, pool, rate: float) -> dict:
    engine, door = drv.build(run, params, state)
    n = int(round(rate * run.seconds))
    due = harness.fixed_gaps(n, run.seconds, run.seed)
    picks = np.random.default_rng([run.seed, 0xF0]).integers(0, len(pool), n)
    t0, t_end, due_abs, submit, start, answer = drv.serve_window(
        run, door, pool, due, picks)
    ok = ~np.isnan(answer)
    lat = (np.where(ok, answer, t_end) - due_abs) * 1e3
    s = engine.stats
    launch_ms = s["wall_us"] / max(1, s["launches"]) / 1e3
    queue_p95_ms = float(np.percentile((start - submit)[ok], 95) * 1e3) \
        if ok.any() else float("inf")
    return {"rate_per_s": rate, "offered": n, "answered": int(ok.sum()),
            "evicted": s["evictions"], "failures": s["failures"],
            "launches": s["launches"],
            "launch_ms": launch_ms, "queue_p95_ms": queue_p95_ms,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "gen_lag_p95_ms": float(np.percentile(submit - due_abs, 95)
                                    * 1e3),
            "drain_s": float(t_end - t0 - run.seconds),
            "launches_seen": drv.describe_launches(start, answer),
            "sustained": bool(ok.all()) and queue_p95_ms < 2 * launch_ms}


def main(argv=None) -> int:
    from bench.run import Run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    try:
        cell = harness.Cell(harness.load_spec(), args.workload)
        device = harness.device_info(cell.chips)
    except harness.BenchError as e:
        print(f"[sweep] {e}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(harness.CHECKOUT / "src"))
    harness.enable_compile_cache()
    drv = cell.runner
    run = Run(cell, args.seed, args.seconds, False, device)
    pool = drv.make_pool(cell.cfg, cell.traffic["pool_frames"], args.seed)
    params, state = drv.served_model(run, pool)
    drv.warm(run, params, state, pool)
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        rows.append(sweep_rate(run, drv, params, state, pool, rate))
        print(json.dumps(rows[-1]), flush=True)
    knee = None  # the highest rate below the first that fails
    for r in sorted(rows, key=lambda r: r["rate_per_s"]):
        if not r["sustained"]:
            break
        knee = r["rate_per_s"]
    print(json.dumps({"workload": cell.name, "knee_per_s": knee,
                      "rate_0_8_knee": None if knee is None else 0.8 * knee,
                      "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
