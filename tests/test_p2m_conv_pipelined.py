"""Pipelined double-buffered conv + fused delta-gated stem
(DESIGN.md §3.5-§3.6).

Three property families through the hypothesis shim:

* the explicit DMA-ring pipelined kernel is **bitwise** identical to the
  automatic grid pipeline (same per-tile dot shapes in the same order)
  and matches the XLA premix twin to fp32 tolerance — forward and, via
  the custom-VJP `pipeline_depth` override, both gradients — over random
  geometries including the ``s == k`` zero-copy fast path;
* the delta-gated stem kernel is **bitwise** identical to
  ``dense Pallas + jnp.where`` under random per-slot rerun masks (the
  reference path the engine keeps);
* a recycled slot on the gated engine path leaks nothing from its
  previous occupant (the StreamEngine isolation invariant, re-pinned on
  the fused path).

Plus the tuner satellites: the conv cache key distinguishes pipeline
depth menus and backend, and the disabled-off-TPU default fallback logs
exactly once per (kind, backend).

The kernels run in interpret mode off-TPU and compiled (Mosaic) on a TPU
(the chip lane of `scripts/ci.sh`); compiled, a geometry Mosaic cannot
lower (`conv.mosaic_conv_error`) must raise ``ValueError`` instead.
"""
import json
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.adc import ADCConfig
from repro.core.pixel_model import default_pixel_model
from repro.kernels.p2m_conv import (
    aligned_block_h,
    p2m_conv,
    p2m_conv_gated_jnp,
    p2m_conv_jnp,
    p2m_conv_pallas,
    p2m_conv_pallas_gated,
)
from repro.kernels.p2m_conv import tune
from repro.kernels.p2m_conv.conv import default_conv_blocks, mosaic_conv_error
from repro.kernels.p2m_conv.ops import _coeff_tuple

MODEL = default_pixel_model()
ADC = ADCConfig()
COEFFS = _coeff_tuple(MODEL)
MODES = ("raw", "relu", "quant")
N_OUT = 5  # off the lane quantum on purpose
INTERPRET = jax.default_backend() != "tpu"


def _geometry(h, w_dim, k):
    return max(h, k), max(w_dim, k)


def _data(h, w_dim, c, k, seed, b=2):
    rng = np.random.default_rng(seed)
    imgs = jnp.asarray(rng.random((b, h, w_dim, c)), jnp.float32)
    w = jnp.asarray(rng.uniform(-1, 1, (k * k * c, N_OUT)), jnp.float32)
    sh = jnp.asarray(rng.uniform(-0.2, 0.2, (N_OUT,)), jnp.float32)
    return imgs, w, sh


def _out_spatial(h, k, s):
    return (h - k) // s + 1


def _compiled_rejects(k, s, c, depth, call) -> bool:
    """Compiled, a geometry Mosaic cannot lower must raise ``ValueError``
    before lowering; True when it did (nothing left to compare)."""
    if INTERPRET or mosaic_conv_error(k, s, c, depth) is None:
        return False
    with pytest.raises(ValueError):
        call()
    return True


# --------------------------------------------------- pipelined kernel parity


@settings(max_examples=10, deadline=None)
@given(st.integers(4, 14), st.integers(4, 14), st.integers(1, 3),
       st.integers(2, 5), st.integers(0, 4), st.integers(0, 1),
       st.integers(0, 2))
def test_pipelined_forward_parity_random_geometry(h, w_dim, c, k, s_raw,
                                                  d_i, mode_i):
    """Explicit DMA ring == automatic grid pipeline bitwise, == XLA premix
    to fp32 tolerance.  ``s_raw == 0`` draws the s == k zero-copy fast
    path; otherwise the general strided path."""
    h, w_dim = _geometry(h, w_dim, k)
    s = k if s_raw == 0 else min(max(s_raw, 1), k)
    depth = (2, 3)[d_i]
    mode = MODES[mode_i]
    imgs, w, sh = _data(h, w_dim, c, k, seed=h * 31 + w_dim * 7 + k + s)
    if _compiled_rejects(k, s, c, depth, lambda: p2m_conv_pallas(
            imgs, w, sh, kernel=k, stride=s, coeffs=COEFFS, mode=mode,
            pipeline_depth=depth, interpret=INTERPRET)):
        return

    grid = p2m_conv_pallas(imgs, w, sh, kernel=k, stride=s, coeffs=COEFFS,
                           mode=mode, pipeline_depth=0, interpret=INTERPRET)
    pipe = p2m_conv_pallas(imgs, w, sh, kernel=k, stride=s, coeffs=COEFFS,
                           mode=mode, pipeline_depth=depth,
                           interpret=INTERPRET)
    np.testing.assert_array_equal(np.asarray(grid), np.asarray(pipe))

    xla = p2m_conv_jnp(imgs, w, sh, MODEL, ADC, mode, k, s)
    np.testing.assert_allclose(np.asarray(pipe), np.asarray(xla),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=6, deadline=None)
@given(st.integers(4, 12), st.integers(1, 2), st.integers(2, 4),
       st.integers(0, 2), st.integers(0, 1))
def test_pipelined_grad_parity_random_geometry(h, c, k, s_raw, d_i):
    """The custom-VJP conv with the pipelined forward produces bitwise
    the same gradients as with the grid forward (grads flow through the
    saved raw accumulation, which the ring reproduces bit-for-bit), and
    matches autodiff of the XLA premix twin to tolerance."""
    h, _ = _geometry(h, h, k)
    s = k if s_raw == 0 else min(max(s_raw, 1), k)
    depth = (2, 3)[d_i]
    imgs, w, sh = _data(h, h, c, k, seed=h * 13 + c + k * s)

    def loss(depth_):
        def f(im, ww, ss):
            out = p2m_conv(im, ww, ss, MODEL, ADC, "relu", k, s, INTERPRET,
                           "pallas", depth_)
            return (out ** 2).sum()
        return jax.grad(f, argnums=(0, 1, 2))

    if _compiled_rejects(k, s, c, depth, lambda: loss(depth)(imgs, w, sh)):
        return
    g_grid = loss(0)(imgs, w, sh)
    g_pipe = loss(depth)(imgs, w, sh)
    for a, b in zip(g_grid, g_pipe):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def loss_xla(im, ww, ss):
        return (p2m_conv_jnp(im, ww, ss, MODEL, ADC, "relu", k, s) ** 2).sum()

    g_xla = jax.grad(loss_xla, argnums=(0, 1, 2))(imgs, w, sh)
    for a, b in zip(g_pipe, g_xla):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_pipeline_depth_one_rejected():
    """Depth 1 would stall on its own DMA every step; the kernel refuses
    it (and negatives) rather than silently degrading."""
    imgs, w, sh = _data(10, 10, 3, 5, seed=0)
    for bad in (1, -2):
        with pytest.raises(ValueError):
            p2m_conv_pallas(imgs, w, sh, kernel=5, stride=5, coeffs=COEFFS,
                            pipeline_depth=bad, interpret=INTERPRET)


def test_pipeline_depth_deeper_than_k_clamps():
    """depth > k just fills the ring once — still bitwise the grid path."""
    imgs, w, sh = _data(15, 15, 3, 5, seed=4)
    if _compiled_rejects(5, 5, 3, 8, lambda: p2m_conv_pallas(
            imgs, w, sh, kernel=5, stride=5, coeffs=COEFFS, pipeline_depth=8,
            interpret=INTERPRET)):
        return
    grid = p2m_conv_pallas(imgs, w, sh, kernel=5, stride=5, coeffs=COEFFS,
                           pipeline_depth=0, interpret=INTERPRET)
    deep = p2m_conv_pallas(imgs, w, sh, kernel=5, stride=5, coeffs=COEFFS,
                           pipeline_depth=8, interpret=INTERPRET)
    np.testing.assert_array_equal(np.asarray(grid), np.asarray(deep))


# ----------------------------------------------------- gated stem parity


LSB = 1.0 / 255.0  # the kernels' default ADC step (v_lsb)


def _check_gated_vs_where(h, c, k, s, mode, mask_seed):
    """Gated kernel vs ``where(rerun, dense, cached)``: bitwise with both
    kernels on the gated kernel's slot-aligned row tile; with the dense
    kernel on its own default tile (as the engine's where-select
    reference runs it), within the DESIGN.md §3.6 cross-tile tolerance;
    and the XLA gated twin to fp32 tolerance."""
    b = 4
    imgs, w, sh = _data(h, h, c, k, seed=h * 11 + c * 5 + k, b=b)
    ho = _out_spatial(h, k, s)
    wo = _out_spatial(h, k, s)
    rng = np.random.default_rng(mask_seed)
    cached = jnp.asarray(rng.normal(0, 1, (b, ho, wo, N_OUT)), jnp.float32)
    rerun = jnp.asarray(rng.integers(0, 2, (b,)), bool)
    if mask_seed % 3 == 1:
        rerun = jnp.zeros((b,), bool)  # all-skip: pure cache copy
    elif mask_seed % 3 == 2:
        rerun = jnp.ones((b,), bool)  # all-rerun: dense kernel equivalent

    def dense(**blocks):
        out = p2m_conv_pallas(imgs, w, sh, kernel=k, stride=s,
                              coeffs=COEFFS, mode=mode, interpret=INTERPRET,
                              **blocks)
        return np.asarray(jnp.where(rerun[:, None, None, None], out, cached))

    def gated():
        return p2m_conv_pallas_gated(imgs, w, sh, cached, rerun, kernel=k,
                                     stride=s, coeffs=COEFFS, mode=mode,
                                     interpret=INTERPRET)

    if _compiled_rejects(k, s, c, 0, gated):
        return
    got = np.asarray(gated())
    bh = aligned_block_h(ho, default_conv_blocks(b, ho, wo, N_OUT, 0)[0])
    np.testing.assert_array_equal(got, dense(block_h=bh))

    cross = dense()
    if mode == "quant":  # a rounding flip moves one ADC count
        assert np.abs(got - cross).max() <= LSB * (1 + 1e-6)
    else:
        np.testing.assert_allclose(got, cross, rtol=1e-5, atol=1e-5)

    xla = p2m_conv_gated_jnp(imgs, w, sh, cached, rerun, kernel=k, stride=s,
                             coeffs=COEFFS, mode=mode)
    np.testing.assert_allclose(got, np.asarray(xla), rtol=1e-5, atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(st.integers(4, 14), st.integers(1, 3), st.integers(2, 5),
       st.integers(0, 3), st.integers(0, 2), st.integers(0, 99))
def test_gated_stem_bitwise_vs_where_random_masks(h, c, k, s_raw, mode_i,
                                                  mask_seed):
    """The fused delta-gated kernel == dense Pallas + jnp.where under
    random per-slot rerun masks (including all-skip and all-rerun draws):
    bitwise on one row tile, within tolerance across tiles."""
    h, _ = _geometry(h, h, k)
    s = k if s_raw == 0 else min(max(s_raw, 1), k)
    _check_gated_vs_where(h, c, k, s, MODES[mode_i], mask_seed)


@pytest.mark.parametrize("mode", MODES)
def test_gated_stem_cross_tile_example(mode):
    """The recorded counterexample to a bitwise cross-tile claim: at
    H = 5, C = 3, k = s = 5 the output is one row per image, so the gated
    kernel's slot-aligned tile is a 1-row dot while the dense default
    tile stacks the 4 slots into a 4-row dot.  The CPU dot rounds the two
    differently (raw: 17 of 20 outputs off by up to 1.8e-6), so only the
    equal-tile comparison is bitwise."""
    _check_gated_vs_where(5, 3, 5, 5, mode, mask_seed=2)


def test_aligned_block_h_divides_ho():
    """The slot-alignment clamp: largest divisor of Ho ≤ the requested
    block, so a row tile never straddles two slots and the per-tile mask
    is exact."""
    assert aligned_block_h(4, 3) == 2
    assert aligned_block_h(7, 7) == 7
    assert aligned_block_h(7, 6) == 1
    assert aligned_block_h(12, 8) == 6
    assert aligned_block_h(1, 64) == 1
    for ho in range(1, 30):
        for bh in range(1, 70):
            got = aligned_block_h(ho, bh)
            assert ho % got == 0 and got <= max(1, min(bh, ho))


# ------------------------------------------------ gated engine invariants


def _stream_fixtures():
    from repro.models.mobilenetv2 import MNV2Config, init_mnv2
    from repro.video import DetectConfig, init_detect_head

    cfg = MNV2Config(variant="p2m", image_size=20, width=0.25,
                     head_channels=16)
    dcfg = DetectConfig(head_channels=8, max_dets=4)
    params, bn = init_mnv2(jax.random.PRNGKey(0), cfg)
    det = init_detect_head(jax.random.PRNGKey(1), 16, dcfg)
    return cfg, dcfg, params, bn, det


def _gated_engine(max_streams=1, **kw):
    from repro.video import DeltaGateConfig, StreamEngine

    cfg, dcfg, params, bn, det = _stream_fixtures()
    return StreamEngine(params, bn, cfg, det, det_cfg=dcfg,
                        gate=DeltaGateConfig(threshold=0.0),
                        max_streams=max_streams, **kw)


def test_gated_engine_bitwise_matches_where_reference():
    """The acceptance pin: the fused gated-stem engine path is
    bit-identical to the where-select reference (same kernel family
    forced via stem_impl='pallas') on hold-redundant streams, while
    actually skipping stem FLOPs in-kernel."""
    from repro.video import StreamRequest, SyntheticVideo

    cfg, *_ = _stream_fixtures()

    def streams():
        return [StreamRequest(
            uid=i, frames=SyntheticVideo(image_size=cfg.image_size,
                                         n_frames=6, hold=2,
                                         seed=i).frames())
            for i in range(3)]

    gated = _gated_engine(max_streams=2, stem_path="gated")
    where = _gated_engine(max_streams=2, stem_path="where",
                          stem_impl="pallas")
    done_g = gated.run(streams())
    done_w = where.run(streams())
    assert [r.uid for r in done_g] == [r.uid for r in done_w]
    for g, w in zip(done_g, done_w):
        for (bg, sg), (bw, sw) in zip(g.frame_outputs, w.frame_outputs):
            np.testing.assert_array_equal(bg, bw)
            np.testing.assert_array_equal(sg, sw)
    sg = gated.stream_summary()
    assert sg["stem_path"] == "gated"
    # hold=2, noise=0 → half the frames are bit-identical repeats, and
    # every one of them short-circuited in-kernel
    assert sg["stem_flops_skipped_ratio"] == pytest.approx(0.5)
    assert where.stream_summary()["stem_flops_skipped_ratio"] == 0.0


def test_gated_engine_recycled_slot_cache_isolation():
    """Isolation invariant on the fused path: two identical streams back
    to back through ONE gated slot produce identical results — a leaked
    cached-stem row or gate reference from the previous occupant would
    skew the recycled stream's first frames."""
    from repro.video import StreamRequest, SyntheticVideo

    cfg, *_ = _stream_fixtures()
    eng = _gated_engine(max_streams=1, stem_path="gated")
    vid = SyntheticVideo(image_size=cfg.image_size, n_frames=5, hold=2,
                         seed=3)
    a = StreamRequest(uid=0, frames=vid.frames())
    b = StreamRequest(uid=1, frames=vid.frames())
    done = eng.run([a, b])
    assert [r.uid for r in done] == [0, 1]
    ra, rb = done
    assert ra.skip_count == rb.skip_count
    assert rb.frame_outputs and ra.frames_done == rb.frames_done
    for (ba, sa), (bb, sb) in zip(ra.frame_outputs, rb.frame_outputs):
        np.testing.assert_array_equal(ba, bb)
        np.testing.assert_array_equal(sa, sb)


def test_gated_engine_rejects_mesh():
    from repro.video.engine import _stream_forward_for

    cfg, dcfg, *_ = _stream_fixtures()
    with pytest.raises(ValueError, match="mesh"):
        _stream_forward_for.__wrapped__(cfg, dcfg, "mesh-sentinel", 2,
                                        None, "gated")


# ------------------------------------------------------- tuner satellites


def test_conv_cache_key_distinguishes_depth_menu_and_backend():
    """A winner tuned over one depth menu (or on one backend) must never
    be served for another: both ride in the cache key."""
    tune.cache_clear()
    args = (1, 12, 12, 3, 8, 3, 3, COEFFS, "relu")
    tune.get_conv_blocks(*args, enable=True, interpret=True, iters=1,
                         depths=(0,))
    tune.get_conv_blocks(*args, enable=True, interpret=True, iters=1,
                         depths=(0, 2))
    keys = [k for k in tune._CACHE if k[0] == "conv"]
    assert len(keys) == 2  # distinct depth menus → distinct entries
    backend = jax.default_backend()
    for key in keys:
        assert backend in key  # backend is part of the signature
    assert {key[-1] for key in keys} == {(0,), (0, 2)}
    # the (0,)-menu winner can never carry a pipelined depth
    (only_grid,) = [tune._CACHE[k]["best"] for k in keys if k[-1] == (0,)]
    assert only_grid[2] == 0
    tune.cache_clear()


def test_autotune_disabled_logs_defaults_once(caplog):
    """Disabled-off-TPU fallback is no longer silent: exactly one
    structured log per (kind, backend) names the backend and the
    defaults served."""
    tune.cache_clear()
    tune._DISABLED_LOGGED.clear()
    with caplog.at_level(logging.INFO, logger=tune.logger.name):
        assert tune.get_conv_blocks(1, 12, 12, 3, 8, 3, 3, COEFFS, "relu",
                                    enable=False) == (None, None, 0)
        tune.get_conv_blocks(2, 16, 16, 3, 8, 5, 5, COEFFS, "quant",
                             enable=False)  # second call: no second log
    msgs = [r.message for r in caplog.records
            if "p2m_autotune_disabled_defaults" in r.message]
    assert len(msgs) == 1
    payload = json.loads(msgs[0])
    assert payload["kind"] == "conv"
    assert payload["backend"] == jax.default_backend()
    assert payload["default"] == [None, None, 0]
    tune._DISABLED_LOGGED.clear()
