"""Chip smoke: the paper's own workload, end to end, on one TPU chip.

Runs `configs/p2m_vww.CONFIG` — 560² frames, full-width P²M-MobileNetV2,
k = s = 5, c_o = 8, 8-bit ADC — through the library's own entry points,
with random weights made from a fixed seed, in one process:

* device — the first JAX device must be a TPU (JAX falls back to the CPU
  when the TPU backend fails to start, so the device is checked, never
  inferred);
* serve — a `VisionEngine` behind a `FrontDoor` answers 3 microbatches of
  synthetic VWW frames; the served probabilities must equal the same
  deploy forward built here from the same params, and lie within
  ``SERVE_DPROB_TOL`` of the plain-XLA (patches) reference at highest
  precision with the same labels; the stem must agree with that
  reference within 1 ADC LSB, and the compiled serving program must hold
  a Pallas kernel;
* stream — a `StreamEngine` whose stem resolves to the fused delta-gated
  kernel serves hold-2 synthetic videos; boxes and scores must equal the
  where-select reference on the same kernel family, and half the stem
  computations must be skipped in-kernel;
* train — 3 steps of the VWW train step with the Pallas forward and
  backward; losses finite, step 1 within 1e-3 of the XLA-twin step;
* faults — any contained launch fault, degradation, failed, evicted or
  undrained request fails the run.

``--four-chips`` runs only the multi-chip paths instead: the
data-parallel train step over a 4-device mesh against the one-chip step,
and a `VisionEngine` sharded over 4 devices against one device.

Run from the repository root on a machine with the chip:

    python chip_smoke.py
    python chip_smoke.py --four-chips

Lines tagged ``[smoke timing]`` are timings of this run, not benchmarks.
The last line of stdout is one JSON object naming the device; any failed
check exits non-zero before it is printed.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import time

SEED = 0
TRAIN_BATCH = 32
TRAIN_STEPS = 3
TRAIN_LR = 0.03  # the paper's 560² learning rate (SGD, momentum 0.9)
SERVE_MICROBATCHES = 3
STREAM_FRAMES = 6
PARITY_TOL = 1e-3
# Served probabilities vs the highest-precision patches reference: the
# served backbone runs at default matmul precision (one bf16 MXU pass),
# which put them 0.0059 apart on a TPU v5e.
SERVE_DPROB_TOL = 2e-2


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@contextlib.contextmanager
def timing(label: str):
    t0 = time.perf_counter()
    yield
    print(f"[smoke timing] {label}: {time.perf_counter() - t0:.3f} s",
          flush=True)


def device_info(n_chips: int) -> dict:
    import jax

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX's first device is {devs[0].platform!r}")
    check(len(devs) == n_chips, f"expected {n_chips} chips, JAX sees "
                                f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def check_engine(name: str, engine) -> None:
    """Containment must never have fired: no launch fault, no degraded
    path, nothing failed, evicted, rejected or left behind."""
    h = engine.health()
    bad = {k: h[k] for k in ("halted", "degraded") if h[k] is not None}
    bad.update({k: v for k, v in h.items()
                if k not in ("halted", "degraded") and v})
    check(not bad, f"{name}: engine health {bad}")


def check_door(door) -> None:
    check(not door.down, f"front door took engines down: {door.down}")
    for name, engine in door.engines.items():
        check_engine(name, engine)


def max_tree_diff(a, b) -> tuple[float, str]:
    """Largest elementwise |a - b| over two like trees, and its leaf."""
    import jax
    import numpy as np

    return max((float(np.abs(np.asarray(x) - np.asarray(y)).max()),
                jax.tree_util.keystr(path))
               for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                                       jax.tree.leaves(b)))


# ------------------------------------------------------------------ phases


def phase_serve(params, bn) -> None:
    import jax
    import numpy as np

    from repro.configs.p2m_vww import CONFIG, SERVE_MAX_BATCH, SERVE_QUANT_BITS
    from repro.core.bn_fold import deploy_params
    from repro.core.p2m_conv import apply_p2m_conv_deploy
    from repro.core.quant import QuantSpec, quantize_deploy
    from repro.data import SyntheticVWW
    from repro.launch.serve import FrontDoor
    from repro.models.mobilenetv2 import apply_mnv2
    from repro.serving import VisionEngine, VisionRequest

    n = SERVE_MICROBATCHES * SERVE_MAX_BATCH
    images = SyntheticVWW(image_size=CONFIG.image_size, batch=n,
                          seed=SEED).batch_at(0)["images"]
    engine = VisionEngine(params, bn, CONFIG, max_batch=SERVE_MAX_BATCH,
                          deploy_quant_bits=SERVE_QUANT_BITS)
    door = FrontDoor(vision=engine)
    with timing(f"serve {n} frames, {SERVE_MICROBATCHES} launches "
                "(first compiles)"):
        done = door.run([VisionRequest(uid=i, image=images[i])
                         for i in range(n)], on_undrained="raise")
    check_door(door)
    check(len(done) == n, f"serve: {len(done)}/{n} frames answered")
    served = sorted((r for _, r in done), key=lambda r: r.uid)
    probs = np.stack([r.probs for r in served])
    labels = np.array([r.label for r in served])
    print(f"[smoke timing] serve launch wall, mean over "
          f"{engine.stats['launches']}: "
          f"{engine.latency_summary()['mean_launch_us'] / 1e3:.3f} ms")

    compiled = engine._fwd.lower(params, bn, engine._deploy,
                                 images[:SERVE_MAX_BATCH]).compile()
    check("tpu_custom_call" in compiled.as_text(),
          "serve: the compiled serving program holds no Pallas kernel")

    dep = quantize_deploy(deploy_params(params["stem"], bn["stem"],
                                        CONFIG.p2m),
                          QuantSpec(SERVE_QUANT_BITS, SERVE_QUANT_BITS))

    def forward(impl=None):
        return jax.jit(lambda p, b, d, x: jax.nn.softmax(apply_mnv2(
            p, b, x, CONFIG, train=False, p2m_deploy=d, p2m_impl=impl)[0],
            axis=-1))

    def batched(fn):
        return np.concatenate([
            np.asarray(fn(params, bn, dep, images[i:i + SERVE_MAX_BATCH]))
            for i in range(0, n, SERVE_MAX_BATCH)])

    rebuilt = batched(forward())
    stem = jax.jit(lambda d, x: apply_p2m_conv_deploy(d, x, CONFIG.p2m))
    with jax.default_matmul_precision("highest"):
        ref_stem = jax.jit(lambda d, x: apply_p2m_conv_deploy(
            d, x, CONFIG.p2m, impl="patches"))
        ref_probs = batched(forward("patches"))
        stem_ref = np.asarray(ref_stem(dep, images[:SERVE_MAX_BATCH]))
    stem_got = np.asarray(stem(dep, images[:SERVE_MAX_BATCH]))
    lsb = CONFIG.p2m.adc.v_lsb
    stem_err = float(np.abs(stem_got - stem_ref).max())
    n_off = int((stem_got != stem_ref).sum())
    d_rebuilt = float(np.abs(probs - rebuilt).max())
    dp = float(np.abs(probs - ref_probs).max())
    print(f"serve: stem max |diff| {stem_err / lsb:.4f} LSB "
          f"({n_off}/{stem_got.size} activations off), max |dprob| "
          f"{d_rebuilt:.3g} vs the rebuilt forward, {dp:.6f} vs the "
          f"reference, labels {labels.tolist()}", flush=True)
    check(np.isfinite(probs).all(), "serve: non-finite probabilities")
    check(d_rebuilt <= 1e-6, f"serve: served probabilities off the deploy "
                              f"forward rebuilt here by {d_rebuilt}")
    check(stem_err <= lsb * (1 + 1e-4),
          f"serve: stem off the reference by {stem_err / lsb:.3f} LSB")
    check(dp <= SERVE_DPROB_TOL,
          f"serve: probabilities off the reference by {dp}")
    check(np.array_equal(labels, ref_probs.argmax(-1)),
          f"serve: labels {labels.tolist()} != reference "
          f"{ref_probs.argmax(-1).tolist()}")


def phase_stream(params, bn) -> None:
    import jax
    import numpy as np

    from repro.configs.p2m_vww import CONFIG, STREAM_MAX_SLOTS
    from repro.launch.serve import FrontDoor
    from repro.models.mobilenetv2 import head_out_channels
    from repro.video import (
        DetectConfig,
        StreamEngine,
        StreamRequest,
        SyntheticVideo,
        init_detect_head,
    )

    dcfg = DetectConfig()
    det = init_detect_head(jax.random.PRNGKey(SEED + 1),
                           head_out_channels(CONFIG), dcfg)

    def streams():
        return [StreamRequest(uid=i, frames=SyntheticVideo(
            image_size=CONFIG.image_size, n_frames=STREAM_FRAMES, hold=2,
            seed=SEED + i).frames()) for i in range(STREAM_MAX_SLOTS)]

    gated = StreamEngine(params, bn, CONFIG, det, det_cfg=dcfg)
    check(gated.stem_path == "gated",
          f"stream: stem path resolved to {gated.stem_path!r}, not 'gated'")
    door = FrontDoor(stream=gated)
    with timing(f"stream {STREAM_MAX_SLOTS} streams x {STREAM_FRAMES} "
                "frames, gated (first tick compiles)"):
        done = door.run(streams(), on_undrained="raise")
    check_door(door)
    where = StreamEngine(params, bn, CONFIG, det, det_cfg=dcfg,
                         stem_path="where", stem_impl="pallas")
    with timing("stream reference, where-select (first tick compiles)"):
        ref = where.run(streams(), on_undrained="raise")
    check_engine("stream-where", where)
    got = sorted((r for _, r in done), key=lambda r: r.uid)
    ref = sorted(ref, key=lambda r: r.uid)
    check([r.uid for r in got] == list(range(STREAM_MAX_SLOTS))
          and [r.uid for r in ref] == list(range(STREAM_MAX_SLOTS)),
          "stream: not every stream completed")
    for g, w in zip(got, ref):
        check(g.frames_done == w.frames_done == STREAM_FRAMES,
              f"stream {g.uid}: {g.frames_done} frames served")
        for t, ((bg, sg), (bw, sw)) in enumerate(zip(g.frame_outputs,
                                                     w.frame_outputs)):
            check(np.array_equal(bg, bw) and np.array_equal(sg, sw),
                  f"stream {g.uid} frame {t}: gated detections differ from "
                  f"the where-select reference (max |dbox| "
                  f"{np.abs(bg - bw).max():.3g}, max |dscore| "
                  f"{np.abs(sg - sw).max():.3g})")
    summary = gated.stream_summary()
    print(f"stream: stem skip ratio {summary['stem_flops_skipped_ratio']}, "
          f"measured readout reduction "
          f"{summary['measured_reduction_vs_dense']}x", flush=True)
    check(summary["stem_flops_skipped_ratio"] == 0.5,
          f"stream: stem skip ratio {summary['stem_flops_skipped_ratio']} "
          "!= 0.5 on hold-2 streams")


def _train_setup(params, bn):
    from repro.configs.p2m_vww import CONFIG
    from repro.data import SyntheticVWW
    from repro.optim import constant, sgd
    from repro.train.vision import make_vww_train_step, vww_train_state

    opt = sgd(constant(TRAIN_LR), momentum=0.9)
    state0 = vww_train_state(params, bn, opt.init(params))
    data = SyntheticVWW(image_size=CONFIG.image_size, batch=TRAIN_BATCH,
                        seed=SEED)
    return opt, state0, data, make_vww_train_step(CONFIG, opt)


def _twin_step(opt):
    """The plain-XLA reference step: the same loss and update with the
    P²M conv on its XLA twin (``p2m_impl="fused"``), autodiff backward."""
    import jax

    from repro.configs.p2m_vww import CONFIG
    from repro.models.mobilenetv2 import apply_mnv2
    from repro.train.vision import softmax_ce

    def step(state, batch):
        def loss_fn(p):
            logits, _ = apply_mnv2(p, state["bn"], batch["images"], CONFIG,
                                   train=True, p2m_impl="fused")
            return softmax_ce(logits, batch["labels"])

        loss, grads = jax.value_and_grad(loss_fn)(state["params"])
        params, _ = opt.update(grads, state["opt"], state["params"],
                               state["step"])
        return params, loss

    return step


def phase_train(params, bn) -> None:
    import jax
    import numpy as np

    opt, state0, data, step = _train_setup(params, bn)
    batch0 = data.batch_at(0)
    with jax.default_matmul_precision("highest"):
        with timing(f"train step compile, batch {TRAIN_BATCH}"):
            compiled = jax.jit(step).lower(state0, batch0).compile()
        with timing("train twin step compile"):
            twin = jax.jit(_twin_step(opt)).lower(state0, batch0).compile()
    n_kernels = compiled.as_text().count("tpu_custom_call")
    check(n_kernels >= 2, f"train: compiled step holds {n_kernels} Pallas "
                          "kernels; want the forward conv and the dW kernel")
    state, losses = state0, []
    for i in range(TRAIN_STEPS):
        batch = data.batch_at(i)
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch)
        losses.append(float(metrics["loss"]))  # blocks on the step
        print(f"[smoke timing] train step {i + 1}: "
              f"{(time.perf_counter() - t0) * 1e3:.3f} ms", flush=True)
        if i == 0:
            state1 = state
    ref_params, ref_loss = twin(state0, batch0)
    dloss = abs(losses[0] - float(ref_loss))
    dparams, leaf = max_tree_diff(state1["params"], ref_params)
    print(f"train: losses {losses}, step-1 |dloss| {dloss:.3g}, "
          f"max |dparam| {dparams:.3g} (at {leaf}) vs the XLA twin",
          flush=True)
    check(all(np.isfinite(losses)), f"train: non-finite loss {losses}")
    check(dloss <= PARITY_TOL and dparams <= PARITY_TOL,
          f"train: step 1 off the XLA twin (|dloss| {dloss}, "
          f"max |dparam| {dparams})")


def phase_four_chips(params, bn) -> None:
    """Training compares at highest matmul precision, as the one-chip
    train check: the stem's weight gradient sums 32·112² patch rows whose
    terms nearly cancel after BN, so at the default single bf16 pass a
    change of summation order alone (per-shard partial sums, then an
    all-reduce) moved the stem weights by 0.0132 on a TPU v5e.  Serving
    compares at highest precision and at the engine's own."""
    import jax

    from repro.launch.mesh import make_debug_mesh

    mesh = make_debug_mesh(4)
    with jax.default_matmul_precision("highest"):
        _four_chip_train(params, bn, mesh)
    _four_chip_serve(params, bn, mesh)


def _four_chip_train(params, bn, mesh) -> None:
    import jax

    from repro.parallel import use_plan, vision_plan_for
    from repro.train.vision import vww_train_shardings

    plan = vision_plan_for(mesh)
    opt, state0, data, step = _train_setup(params, bn)
    batch0 = data.batch_at(0)
    with timing("train step, 1 chip (compiles)"):
        ref1, mref = jax.jit(step)(state0, batch0)
        jax.block_until_ready(ref1)
    with use_plan(plan), mesh:
        st_sh, b_sh = vww_train_shardings(state0, batch0, plan)
        dp_step = jax.jit(step, in_shardings=(st_sh, b_sh),
                          out_shardings=(st_sh, None))
        with timing("train step, 4-chip data parallel (compiles)"):
            st1, msh = dp_step(jax.device_put(state0, st_sh),
                               jax.device_put(batch0, b_sh))
            jax.block_until_ready(st1)
    dloss = abs(float(msh["loss"]) - float(mref["loss"]))
    dparams, pleaf = max_tree_diff(st1["params"], ref1["params"])
    dbn, bleaf = max_tree_diff(st1["bn"], ref1["bn"])
    print(f"four-chip train: |dloss| {dloss:.3g}, max |dparam| "
          f"{dparams:.3g} (at {pleaf}), max |dbn| {dbn:.3g} (at {bleaf}) "
          "vs 1 chip", flush=True)
    check(max(dloss, dparams, dbn) <= PARITY_TOL,
          "four-chip train: data-parallel step off the one-chip step")


def _four_chip_serve(params, bn, mesh) -> None:
    """Sharded vs single-device serving, twice.  At highest matmul
    precision within ``PARITY_TOL``: a sharding or wiring fault shows
    there.  At the engine's own precision within ``SERVE_DPROB_TOL``: the
    backbone then runs one bf16 pass and the per-device batch (2 vs 8)
    changes how XLA computes it, which put the two 0.00378 apart on four
    TPU v5e chips."""
    import jax
    import numpy as np

    from repro.configs.p2m_vww import CONFIG, SERVE_MAX_BATCH
    from repro.data import SyntheticVWW
    from repro.serving import VisionEngine, VisionRequest

    n = 2 * SERVE_MAX_BATCH
    images = SyntheticVWW(image_size=CONFIG.image_size, batch=n,
                          seed=SEED).batch_at(0)["images"]

    def serve(m, label):
        engine = VisionEngine(params, bn, CONFIG, mesh=m)
        with timing(f"serve {n} frames, {label} (first launch compiles)"):
            done = engine.run([VisionRequest(uid=i, image=images[i])
                               for i in range(n)], on_undrained="raise")
        check_engine(f"serve {label}", engine)
        check(len(done) == n, f"serve {label}: {len(done)}/{n} answered")
        done = sorted(done, key=lambda r: r.uid)
        return np.stack([r.probs for r in done]), [r.label for r in done]

    for precision, tol in (("highest", PARITY_TOL),
                           ("default", SERVE_DPROB_TOL)):
        ctx = (jax.default_matmul_precision(precision)
               if precision == "highest" else contextlib.nullcontext())
        with ctx:
            (p1, l1) = serve(None, f"1 chip, {precision} precision")
            (p4, l4) = serve(mesh, f"4 chips, {precision} precision")
        dp = float(np.abs(p1 - p4).max())
        print(f"four-chip serve, {precision} precision: max |dprob| "
              f"{dp:.3g}, labels {l4}", flush=True)
        check(l1 == l4 and dp <= tol,
              f"four-chip serve: sharded probabilities off the "
              f"single-device ones at {precision} precision (max |dprob| "
              f"{dp}, labels {l4} vs {l1})")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data-parallel train and sharded "
                         "serving parity checks, on 4 chips")
    args = ap.parse_args()
    n_chips = 4 if args.four_chips else 1
    try:
        device = device_info(n_chips)
    except SmokeFailure as e:
        print(f"[smoke] FAIL device: {e}", file=sys.stderr)
        return 1

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    import jax

    from repro.configs.p2m_vww import CONFIG
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.mobilenetv2 import init_mnv2

    print(f"[smoke] {device}, compile cache {enable_compile_cache()}",
          flush=True)
    params, bn = init_mnv2(jax.random.PRNGKey(SEED), CONFIG)
    phases = ([phase_four_chips] if args.four_chips
              else [phase_serve, phase_stream, phase_train])
    for phase in phases:
        name = phase.__name__.removeprefix("phase_")
        try:
            with timing(f"phase {name}"):
                phase(params, bn)
        except SmokeFailure as e:
            print(f"[smoke] FAIL {name}: {e}", file=sys.stderr)
            return 1
        print(f"[smoke] {name}: ok", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
