"""Single frames, open loop: ``FrontDoor`` → ``VisionEngine``.

Traffic parameters (``bench/traffic/<mix>.json``, ``"kind": "frames"``):
``rate_per_s`` (fixed offered load), ``pool_frames`` (distinct frames
rendered from the seed, half positive on average), ``warm_launches``.
Arrivals are open-loop Poisson with the same gaps for every seed
(`harness.fixed_gaps`); each picks a pool frame by reference.

A frame's latency runs from its due time on that schedule to its
probabilities on the host.  A frame that is evicted, rejected or failed
never answers: it counts as failed and, in the tail, with the time until
the run gave up on it.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from bench import harness, program, synth
from bench.tracing import capture


def make_pool(cfg: dict, n: int, seed: int) -> np.ndarray:
    return synth.vww_batch(cfg["image_size"], n, seed, 0)["images"]


def served_model(run, pool):
    """Weights from the seed, BN statistics from the pool's first
    frames; returns (params, state)."""
    import jax.numpy as jnp

    ref, cfg = run.cell.reference, run.cell.cfg
    params, state = program.weights(run, ref)
    calib = jnp.asarray(pool[:run.cell.traffic.get("calib_frames", 16)])
    return params, ref.calibrate(params, state, calib, cfg)


def build(run, params, state):
    from repro.launch.serve import FrontDoor
    from repro.serving import VisionEngine

    s = run.cell.cfg["serve"]
    engine = VisionEngine(params, state, program.mnv2_config(run.cell.cfg),
                          max_batch=s["max_batch"], max_queue=s["max_queue"],
                          evict=s["evict"],
                          deploy_quant_bits=s["deploy_quant_bits"])
    return engine, FrontDoor(vision=engine)


def warm(run, params, state, pool):
    """Compile and settle the one shape the window uses: a full
    microbatch (free slots ride as zero frames)."""
    from repro.serving import VisionRequest

    engine, door = build(run, params, state)
    for i in range(run.cell.traffic["warm_launches"] * engine.n_slots):
        door.submit(VisionRequest(uid=i, image=pool[i % len(pool)]))
    while door.busy():
        door.step()


def serve_window(run, door, pool, due, picks):
    """Offer the schedule, drain, and return per-frame host times."""
    from repro.serving import VisionRequest

    n = len(due)
    submit = np.full(n, np.nan)
    start = np.full(n, np.nan)
    answer = np.full(n, np.nan)
    nxt = 0
    t0 = time.perf_counter() + 0.01
    due_abs = t0 + due
    with run.span("bench.window"):
        while nxt < n or door.busy():
            now = time.perf_counter()
            if nxt < n and due_abs[nxt] <= now:
                with run.span("bench.submit"):
                    while nxt < n and due_abs[nxt] <= now:
                        door.submit(VisionRequest(uid=nxt,
                                                  image=pool[picks[nxt]]))
                        submit[nxt] = time.perf_counter()
                        nxt += 1
            if door.busy():
                ts = time.perf_counter()
                with run.span("bench.step"):
                    done = door.step()
                te = time.perf_counter()
                for _, req in done:
                    start[req.uid], answer[req.uid] = ts, te
            elif nxt < n:
                with run.span("bench.idle"):
                    time.sleep(max(0.0, due_abs[nxt] - time.perf_counter()))
    t_end = time.perf_counter()
    return t0, t_end, due_abs, submit, start, answer


def run(run):
    cell, seed = run.cell, run.seed
    tr = cell.traffic
    pool = make_pool(cell.cfg, tr["pool_frames"], seed)
    params, state = served_model(run, pool)
    warm(run, params, state, pool)
    engine, door = build(run, params, state)
    n = int(round(tr["rate_per_s"] * run.seconds))
    due = harness.fixed_gaps(n, run.seconds, seed)
    picks = np.random.default_rng([seed, 0xF0]).integers(0, len(pool), n)

    run.start_window()
    with capture(run) as trace, harness.HostWaits() as waits:
        t0, t_end, due_abs, submit, start, answer = serve_window(
            run, door, pool, due, picks)
    run.end_window()

    ok = ~np.isnan(answer)
    lat = np.where(ok, answer, t_end) - due_abs
    s = engine.stats
    p50, p95, p99 = (harness.percentile(lat, q) * 1e3 for q in (50, 95, 99))
    print(f"[frames] frame ms p50 {p50!r} p95 {p95!r} p99 {p99!r}; "
          f"{describe_launches(start, answer)}; evicted {s['evictions']}; "
          f"host {waits.describe()}", file=sys.stderr, flush=True)
    data = {
        "trace": trace(),
        "latency_s": lat,
        "gen_lag_s": submit - due_abs,
        "queue_s": (start - submit)[ok],
        "stats": dict(s),
        "frames": int(ok.sum()),
        "slots": engine.n_slots,
        "cfg": cell.cfg,
    }
    e2e = {
        "frame_p95_ms": p95,
        "frames_per_s": float(((answer <= t0 + run.seconds) & ok).sum())
        / run.seconds,
    }
    run.read_memory()

    # what the window served, against the reference over the pool
    served = {}
    for req in engine.completed:
        served[req.uid] = req.probs
    del engine, door
    ref = reference_probs(cell, params, state, pool)
    checks = harness.Checks(cell.limits)
    checks.add("logp_gap", logp_gap(served, picks, ref))
    checks.add("unanswered_faults", float(
        s["failures"] + s["launch_faults"]))
    return {"e2e": e2e, "attempted": n, "failed": int(n - ok.sum()),
            "checks": checks, "data": data}


def describe_launches(start, answer) -> str:
    """Launch count, wall-time quartiles and mean fill, from the host
    times at which each answered frame's step started and ended."""
    ok = ~np.isnan(answer)
    steps, fill = np.unique(np.stack([start[ok], answer[ok]]), axis=1,
                            return_counts=True)
    if not fill.size:
        return "no launch answered"
    wall = (steps[1] - steps[0]) * 1e3
    q = np.percentile(wall, [25, 50, 75, 95])
    long = wall > 10 * q[1]
    worst = int(wall.argmax())
    return (f"launches {fill.size} wall ms p25 {q[0]:.2f} p50 {q[1]:.2f} "
            f"p75 {q[2]:.2f} p95 {q[3]:.2f} max {wall[worst]:.2f} (launch "
            f"{worst}, {steps[0, worst] - steps[0, 0]:.3f} s after the "
            f"first); over 10x the median {int(long.sum())}, "
            f"{wall[long].sum():.2f} ms; mean fill {fill.mean():.2f}")


def reference_probs(cell, params, state, pool, operands="float32",
                    block: int = 8):
    """The reference's probabilities for every pool frame, in blocks."""
    import jax.numpy as jnp

    return np.concatenate([np.asarray(cell.reference.probs(
        params, state, jnp.asarray(pool[i:i + block]), cell.cfg, operands))
        for i in range(0, len(pool), block)])


def logp_gap(served: dict, picks, ref) -> float:
    """Widest gap, over every served frame and class, between the
    served log-probability and the reference's for that pool frame."""
    if not served:
        return float("inf")
    uids = np.fromiter(served, int)
    got = np.stack([served[u] for u in uids])
    want = ref[picks[uids]]
    with np.errstate(divide="ignore", invalid="ignore"):
        gap = np.abs(np.log(got) - np.log(want))
    return float(np.nanmax(np.where(np.isfinite(gap), gap, np.inf)))


def control(run) -> dict:
    """The precision control at the cell's size: the reference with its
    contraction operands one step below the configuration's (float8
    e4m3 for bfloat16) put in the program's place over the whole pool,
    compared as the window's answers are.  ``stated.logp_gap`` is the
    same with bfloat16 operands, the precision the configuration states:
    what rounding alone reads."""
    cell = run.cell
    pool = make_pool(cell.cfg, cell.traffic["pool_frames"], run.seed)
    params, state = served_model(run, pool)
    ref = reference_probs(cell, params, state, pool)
    picks = np.arange(len(pool))
    out = {}
    for label, operands in (("logp_gap", "float8_e4m3fn"),
                            ("stated.logp_gap", "bfloat16")):
        got = reference_probs(cell, params, state, pool, operands)
        out[label] = logp_gap(dict(enumerate(got)), picks, ref)
    return out
