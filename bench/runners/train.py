"""Training steps: the jitted ``make_vww_train_step`` as production
compiles it (default matmul precision, SGD with momentum).

Traffic parameters (``"kind": "train"``): ``batch``, ``distinct_batches``
rendered from the seed on the host and cycled, ``images`` (``"uint8"``:
8-bit codes, as decoded camera images are), ``checked_steps`` (the first
steps, run in set-up through the window's own feed and call, that the
reference follows) and ``in_flight`` (steps dispatched ahead of the
host).  Each step's batch is placed on the device inside the window as
a job's input pipeline would place it, as 8-bit images, and scaled to
float32 there by a program of its own; the step gets the float32 batch.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from bench import harness, program, synth
from bench.tracing import capture


SCALE = np.float32(1 / 255)


def scale_images(codes):
    """8-bit codes to float32 images in [0, 1], on the device."""
    import jax.numpy as jnp

    return codes.astype(jnp.float32) * SCALE


def host_images(batch: dict) -> dict:
    """The batch with float32 images, as the step sees it: 8-bit codes
    scaled by the same formula as `scale_images`."""
    return dict(batch, images=batch["images"].astype(np.float32) * SCALE)


def render(cfg: dict, tr: dict, seed: int) -> list[dict]:
    """The distinct batches as the feed places them: 8-bit images, int32
    labels."""
    if tr["images"] != "uint8":
        raise harness.BenchError(f"traffic images {tr['images']!r}: only "
                                 f"'uint8' is fed")
    batches = [synth.vww_batch(cfg["image_size"], tr["batch"], seed, i)
               for i in range(tr["distinct_batches"])]
    return [dict(b, images=np.round(b["images"] * 255).astype(np.uint8))
            for b in batches]


def make_batches(cfg: dict, tr: dict, seed: int) -> list[dict]:
    """The distinct batches as the step and the reference see them:
    float32 images, int32 labels."""
    return [host_images(b) for b in render(cfg, tr, seed)]


def check_placed(placed, host) -> None:
    """Set-up's agreement check: the float32 images the step got from the
    feed equal, bit for bit, the host's copy that the reference reads."""
    got = np.asarray(placed)
    if got.dtype != host.dtype or got.shape != host.shape:
        raise harness.BenchError(f"the feed's images are {got.dtype}"
                                 f"{got.shape}, the host's {host.dtype}"
                                 f"{host.shape}")
    off = int(np.count_nonzero(got.view(np.uint32) != host.view(np.uint32)))
    if off:
        raise harness.BenchError(f"the feed's first batch differs from the "
                                 f"host's copy in {off} of {got.size} values")


def build(run):
    """(params, bn, compiled step, first state) — the one object that
    set-up drives through the checked steps and hands to the window."""
    import jax

    from repro.optim import constant, sgd
    from repro.train.vision import make_vww_train_step, vww_train_state

    t = run.cell.cfg["train"]
    params, bn = program.weights(run, run.cell.reference)
    opt = sgd(constant(t["lr"]), momentum=t["momentum"])
    state = vww_train_state(params, bn, opt.init(params))
    step = jax.jit(make_vww_train_step(program.mnv2_config(run.cell.cfg), opt))
    return params, bn, step, state


class Feed:
    """Cycles the host batches, placing each on the device when asked and
    scaling its 8-bit images there by `scale_images`, jitted on its own."""

    def __init__(self, batches, span):
        import jax

        self.batches, self.span = batches, span
        self.scale = jax.jit(scale_images)
        self.i, self.wait_s, self.calls = 0, 0.0, 0

    def __call__(self):
        import jax

        t = time.perf_counter()
        with self.span("bench.feed"):
            b = jax.device_put(self.batches[self.i % len(self.batches)])
            b = dict(b, images=self.scale(b["images"]))
        self.wait_s += time.perf_counter() - t
        self.calls += 1
        self.i += 1
        return b


def run(run):
    import jax

    cell, tr = run.cell, run.cell.traffic
    placed = render(cell.cfg, tr, run.seed)
    batches = [host_images(b) for b in placed]
    params, bn, step, state = build(run)
    feed = Feed(placed, run.span)
    losses, kept = [], {}
    for i in range(tr["checked_steps"]):
        b = feed()
        if i == 0:
            check_placed(b["images"], batches[0]["images"])
        state, m = step(state, b)
        losses.append(float(m["loss"]))
        if i == 0:
            kept["mu1"] = state["opt"]["mu"]
    kept["params"] = state["params"]
    feed.wait_s, feed.calls = 0.0, 0

    run.start_window()
    pending, steps, dispatch_s, wait_s = [], 0, 0.0, 0.0
    with capture(run) as trace:
        with run.span("bench.window"):
            t0 = time.perf_counter()
            while time.perf_counter() < t0 + run.seconds:
                b = feed()
                t = time.perf_counter()
                with run.span("bench.step"):
                    state, m = step(state, b)
                dispatch_s += time.perf_counter() - t
                steps += 1
                pending.append(m["loss"])
                if len(pending) > tr["in_flight"]:
                    t = time.perf_counter()
                    with run.span("bench.wait"):
                        pending.pop(0).block_until_ready()
                    wait_s += time.perf_counter() - t
            with run.span("bench.wait"):
                jax.block_until_ready(state)
            t_end = time.perf_counter()
    run.end_window()
    run.read_memory()
    window_losses = [float(x) for x in pending]
    data = {"trace": trace(), "steps": steps, "wall_s": t_end - t0,
            "batch": tr["batch"], "input_wait_s": feed.wait_s,
            "feeds": feed.calls, "dispatch_s": dispatch_s, "cfg": cell.cfg}
    print(f"[window] {steps} steps in {t_end - t0:.3f} s; host ms a step: "
          f"feed {feed.wait_s / max(steps, 1) * 1e3:.3f}, step call "
          f"{dispatch_s / max(steps, 1) * 1e3:.3f}, wait on step n-"
          f"{tr['in_flight']} {wait_s / max(steps, 1) * 1e3:.3f}",
          file=sys.stderr, flush=True)
    e2e = {"train_images_per_s": steps * tr["batch"] / (t_end - t0)}
    del state, step, pending

    checks = harness.Checks(cell.limits)
    got = {"losses": losses, "mu1": kept["mu1"], "params": kept["params"]}
    for name, value in compare(cell, params, bn, batches, got).items():
        if name in cell.limits:
            checks.add(name, value)
    finite = all(np.isfinite(window_losses))
    checks.add("nonfinite_window_losses", 0.0 if finite else 1.0)
    return {"e2e": e2e, "attempted": steps, "failed": 0 if finite else steps,
            "checks": checks, "data": data}


def reference_steps(cell, params, bn, batches, n: int, operands="float32",
                    half: bool = False):
    """The reference's first ``n`` steps from the same weights on the
    same batches: (losses, mu after step 1, params after step n)."""
    import jax
    import jax.numpy as jnp

    ref = cell.reference
    mu = jax.tree.map(jnp.zeros_like, params)
    p, s, losses, mu1 = params, bn, [], None
    for i in range(n):
        b = jax.tree.map(jnp.asarray, batches[i % len(batches)])
        p, s, mu, loss = ref.train_step(p, s, mu, b, cell.cfg, operands, half)
        losses.append(float(loss))
        if i == 0:
            mu1 = mu
    return {"losses": losses, "mu1": mu1, "params": p}


def leaf_norms(tree) -> dict:
    import jax

    return {jax.tree_util.keystr(k): float(np.linalg.norm(np.asarray(v)))
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


def leaf_gaps(got: dict, want: dict, keep=None) -> list[tuple[float, str]]:
    """(|‖got‖ − ‖want‖| / max(‖want‖, median ‖want‖), leaf) for every
    leaf, the widest first."""
    med = float(np.median(list(want.values())))
    return sorted(((abs(got[k] - w) / max(w, med), k) for k, w in want.items()
                   if keep is None or k in keep), reverse=True)


def compare(cell, params, bn, batches, got: dict,
            want: dict | None = None) -> dict:
    """Readings of the checked steps against the reference: each step's
    loss gap; the first gradient's leaf norm gaps (the optimizer's
    momentum after one step is that gradient) and those of the
    parameters' change over the checked steps, each as the median leaf's
    gap (compared) and the widest leaf's (printed beside it: a few small
    leaves after the in-pixel layer's ADC swing from seed to seed).
    Leaves whose reference gradient is under a thousandth of the median
    leaf's are left out of the change: rounding alone moves them."""
    import jax

    n = len(got["losses"])
    if want is None:
        want = reference_steps(cell, params, bn, batches, n)
    g_want = leaf_norms(want["mu1"])
    med = float(np.median(list(g_want.values())))
    keep = {k for k, v in g_want.items() if v >= 1e-3 * med}
    delta = lambda p: jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                                   p, params)
    grad = leaf_gaps(leaf_norms(got["mu1"]), g_want)
    dlt = leaf_gaps(leaf_norms(delta(got["params"])),
                    leaf_norms(delta(want["params"])), keep)
    top = lambda gaps: ", ".join(f"{k} {v:.4g}" for v, k in gaps[:3])
    print(f"[train] losses {got['losses']} reference {want['losses']}; "
          f"widest gradient leaves {top(grad)}; widest change leaves "
          f"{top(dlt)}", file=sys.stderr, flush=True)
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(got["losses"],
                                                   want["losses"])),
        "grad_gap_median": float(np.median([v for v, _ in grad])),
        "delta_gap_median": float(np.median([v for v, _ in dlt])),
        "grad_gap_widest": grad[0][0],
        "delta_gap_widest": dlt[0][0],
    }


def control(run) -> dict:
    """Readings at the cell's size of what a limit must catch: the
    reference with float8 e4m3 contraction operands in the program's
    place (the precision control), the same with bfloat16 operands (the
    precision the configuration states: what rounding alone reads), and
    the reference with half of each batch left out, its mean taken over
    the rest (a planted fault)."""
    cell, tr = run.cell, run.cell.traffic
    batches = make_batches(cell.cfg, tr, run.seed)[:tr["checked_steps"]]
    params, bn = program.weights(run, cell.reference)
    n = tr["checked_steps"]
    want = reference_steps(cell, params, bn, batches, n)
    out = {}
    for label, kw in (("control", {"operands": "float8_e4m3fn"}),
                      ("stated", {"operands": "bfloat16"}),
                      ("half_batch", {"half": True})):
        got = reference_steps(cell, params, bn, batches, n, **kw)
        for k, v in compare(cell, params, bn, batches, got, want).items():
            out[f"{label}.{k}"] = v
    return out
