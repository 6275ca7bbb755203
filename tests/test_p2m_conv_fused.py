"""Fused implicit-im2col conv kernel vs the patch-materializing path and
the elementwise oracle: parity matrix over modes × strides × odd shapes,
gradient parity of the Pallas dX/dW backward kernels (incl. quant STE),
autotuner legality/caching, and the core-layer impl equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.adc import ADCConfig
from repro.core.p2m_conv import (
    P2MConvConfig,
    apply_p2m_conv_deploy,
    apply_p2m_conv_train,
    extract_patches,
    init_p2m_conv,
    init_p2m_state,
)
from repro.core.pixel_model import default_pixel_model
from repro.kernels.p2m_conv import (
    im2col_matrix,
    p2m_backward_jnp,
    p2m_bwd_dx_pallas,
    p2m_bwd_dw_pallas,
    p2m_conv,
    p2m_conv_jnp,
    p2m_conv_pallas,
    p2m_matmul_jnp,
    p2m_matmul_ref,
    premix_weights,
)
from repro.kernels.p2m_conv import tune
from repro.kernels.p2m_conv.backward import epilogue_mask
from repro.kernels.p2m_conv.conv import ceil_to, image_view
from repro.kernels.p2m_conv.ops import _coeff_tuple
from repro.obs.metrics import default_registry

MODEL = default_pixel_model()
ADC = ADCConfig()
COEFFS = _coeff_tuple(MODEL)

# (B, H, W, C, k, s): paper geometry, non-divisible H/W (remainder crop),
# overlapping stride < kernel, stride > kernel gaps, single-channel,
# single-pixel-row outputs, shapes off the 8/128 tile quanta.
GEOMETRIES = [
    (2, 20, 20, 3, 5, 5),    # paper fast path, divisible
    (1, 23, 19, 3, 5, 5),    # fast path with remainder crop
    (2, 14, 11, 2, 3, 2),    # overlapping stride < kernel
    (2, 13, 13, 3, 5, 3),    # overlapping, odd dims
    (1, 9, 9, 1, 4, 4),      # single channel
    (1, 8, 17, 3, 2, 2),     # wide/narrow
    (2, 10, 10, 3, 3, 6),    # stride > kernel (gaps)
    (1, 5, 5, 3, 5, 5),      # single output pixel
]
FAST_GEOMETRIES = [g for g in GEOMETRIES if g[4] == g[5]]


def _conv_data(b, h, w_dim, c, k, n=8, seed=0):
    rng = np.random.default_rng(seed)
    imgs = jnp.asarray(rng.random((b, h, w_dim, c)), jnp.float32)
    w = jnp.asarray(rng.uniform(-1, 1, (k * k * c, n)), jnp.float32)
    s = jnp.asarray(rng.uniform(-0.2, 0.2, (n,)), jnp.float32)
    return imgs, w, s


def _patch_reference(imgs, w, s, k, stride, mode):
    """extract_patches + p2m_matmul_jnp — the materializing baseline."""
    b = imgs.shape[0]
    patches = extract_patches(imgs, k, stride)
    xf = patches.reshape(b * patches.shape[1], -1)
    out = p2m_matmul_jnp(xf, w, s, MODEL, ADC, mode)
    ho = (imgs.shape[1] - k) // stride + 1
    wo = (imgs.shape[2] - k) // stride + 1
    return out.reshape(b, ho, wo, w.shape[1])


@pytest.mark.parametrize("b,h,w_dim,c,k,s", GEOMETRIES)
@pytest.mark.parametrize("mode", ["raw", "relu", "quant"])
def test_fused_conv_matches_patch_path(b, h, w_dim, c, k, s, mode):
    imgs, w, sh = _conv_data(b, h, w_dim, c, k)
    ref = _patch_reference(imgs, w, sh, k, s, mode)
    out = p2m_conv_pallas(imgs, w, sh, kernel=k, stride=s, coeffs=COEFFS,
                          mode=mode, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    outj = p2m_conv_jnp(imgs, w, sh, MODEL, ADC, mode, k, s)
    np.testing.assert_allclose(np.asarray(outj), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,h,w_dim,c,k,s", GEOMETRIES[:4])
def test_fused_conv_matches_elementwise_oracle(b, h, w_dim, c, k, s):
    """Fused kernel ≡ the faithful per-element g() oracle (ref.py)."""
    imgs, w, sh = _conv_data(b, h, w_dim, c, k, seed=3)
    xf = im2col_matrix(imgs, k, s)
    ref = p2m_matmul_ref(xf, w, MODEL, sh, ADC)
    out = p2m_conv_pallas(imgs, w, sh, kernel=k, stride=s, coeffs=COEFFS,
                          mode="relu", interpret=True)
    np.testing.assert_allclose(np.asarray(out).reshape(ref.shape),
                               np.asarray(ref), rtol=1e-4, atol=1e-4)


def test_im2col_matrix_matches_extract_patches():
    for b, h, w_dim, c, k, s in GEOMETRIES:
        imgs, _, _ = _conv_data(b, h, w_dim, c, k, seed=1)
        a = im2col_matrix(imgs, k, s)
        bnum = imgs.shape[0]
        p = extract_patches(imgs, k, s).reshape(a.shape)
        np.testing.assert_allclose(np.asarray(a), np.asarray(p), atol=0)


def test_fused_conv_tiny_blocks_padded_edges():
    """Force 1-row blocks so every tile edge is a padded edge."""
    imgs, w, sh = _conv_data(2, 13, 11, 3, 5, seed=5)
    ref = _patch_reference(imgs, w, sh, 5, 3, "relu")
    out = p2m_conv_pallas(imgs, w, sh, kernel=5, stride=3, coeffs=COEFFS,
                          mode="relu", block_h=1, block_n=128,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_premix_weights_collapses_basis():
    """Σ_j X^j @ W̃_j ≡ Σ_ij a_ij X^j (sign(W)|W|^i) — the premix identity."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.random((32, 12)), jnp.float32)
    w = jnp.asarray(rng.uniform(-1, 1, (12, 4)), jnp.float32)
    wmix = premix_weights(w, COEFFS)
    acc = sum((x ** j) @ wmix[j - 1] for j in range(1, wmix.shape[0] + 1))
    ref = p2m_matmul_jnp(x, w, jnp.zeros((4,)), MODEL, ADC, "raw")
    np.testing.assert_allclose(np.asarray(acc), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Gradient parity: Pallas dX/dW kernels vs jax.vjp of the jnp path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", [(48, 75, 8), (130, 33, 5), (8, 1, 1)])
@pytest.mark.parametrize("mode", ["raw", "relu"])
def test_pallas_bwd_kernels_match_jax_vjp(m, k, n, mode):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.random((m, k)), jnp.float32)
    w = jnp.asarray(rng.uniform(-1, 1, (k, n)), jnp.float32)
    s = jnp.asarray(rng.uniform(-0.2, 0.2, (n,)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)

    _, vjp = jax.vjp(
        lambda xx, ww, ss: p2m_matmul_jnp(xx, ww, ss, MODEL, ADC, mode),
        x, w, s)
    rgx, rgw, rgs = vjp(g)

    raw = p2m_matmul_jnp(x, w, jnp.zeros_like(s), MODEL, ADC, "raw")
    g_eff = g * epilogue_mask(raw, s, mode=mode, full_scale=ADC.full_scale)
    gx = p2m_bwd_dx_pallas(g_eff, w, x, coeffs=COEFFS, interpret=True)
    gw = p2m_bwd_dw_pallas(g_eff, w, x, coeffs=COEFFS, interpret=True)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rgx),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rgw),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g_eff.sum(0)), np.asarray(rgs),
                               rtol=1e-4, atol=1e-5)

    jgx, jgw = p2m_backward_jnp(g_eff, w, x, COEFFS)
    np.testing.assert_allclose(np.asarray(jgx), np.asarray(rgx),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(jgw), np.asarray(rgw),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("b,h,w_dim,c,k,s",
                         [(2, 20, 20, 3, 5, 5), (2, 13, 11, 3, 5, 3)])
@pytest.mark.parametrize("mode", ["raw", "relu"])
def test_fused_conv_gradients_match_jnp(b, h, w_dim, c, k, s, mode):
    """custom-VJP fused conv (Pallas fwd + Pallas bwd) ≡ autodiff of the
    XLA fused path, including the col2im scatter for overlapping stride."""
    imgs, w, sh = _conv_data(b, h, w_dim, c, k, seed=4)

    def loss_pallas(im, ww, ss):
        return (p2m_conv(im, ww, ss, MODEL, ADC, mode, k, s, True,
                         "pallas") ** 2).sum()

    def loss_jnp(im, ww, ss):
        return (p2m_conv_jnp(im, ww, ss, MODEL, ADC, mode, k, s) ** 2).sum()

    route = default_registry().counter(
        "p2m_conv.bwd_dw_" + ("view" if s == k else "patches"))
    n0 = route.value
    g1 = jax.grad(loss_pallas, argnums=(0, 1, 2))(imgs, w, sh)
    assert route.value == n0 + 1
    g2 = jax.grad(loss_jnp, argnums=(0, 1, 2))(imgs, w, sh)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("b,h,w_dim,c,k,s", FAST_GEOMETRIES)
@pytest.mark.parametrize("fwd_block_h", [1, 3], ids=["fwd_divides",
                                                     "fwd_pads"])
@pytest.mark.parametrize("block_h", [None, 1], ids=["one_block", "per_row"])
def test_dw_from_image_view_matches_patch_dw(b, h, w_dim, c, k, s,
                                             fwd_block_h, block_h):
    """The dW kernel on the forward's (mh_pad, k, Wo, k·C) image view ≡
    the closed form on the im2col matrix of the same images: with B·Ho a
    multiple of the forward's block_h (an unpadded view) and not (zero
    rows the dW grid must not reach), in one row block or one per row."""
    imgs, w, _ = _conv_data(b, h, w_dim, c, k, seed=11)
    mh = b * ((h - k) // s + 1)
    wo = (w_dim - k) // s + 1
    rng = np.random.default_rng(12)
    g = jnp.asarray(rng.standard_normal((mh * wo, w.shape[1])), jnp.float32)
    _, ref = p2m_backward_jnp(g, w, im2col_matrix(imgs, k, s), COEFFS)
    view = image_view(imgs, k, ceil_to(mh, fwd_block_h))
    assert (view.shape[0] > mh) == (fwd_block_h == 3)
    gw = p2m_bwd_dw_pallas(g, w, view, coeffs=COEFFS, block_h=block_h,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("want_raw", [False, True])
def test_fused_conv_forward_bitwise_with_view(want_raw):
    """Keeping the image view for the backward leaves the forward's
    outputs bitwise as they are: p2m_conv_pallas with and without
    want_view, and p2m_conv's primal under a Pallas VJP, which keeps it."""
    imgs, w, sh = _conv_data(2, 23, 19, 3, 5, seed=13)
    kw = dict(kernel=5, stride=5, coeffs=COEFFS, mode="relu", block_h=3,
              want_raw=want_raw, interpret=True)
    plain = p2m_conv_pallas(imgs, w, sh, **kw)
    *outs, view = p2m_conv_pallas(imgs, w, sh, want_view=True, **kw)
    plain = plain if want_raw else (plain,)
    for a, b_ in zip(plain, outs, strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
    mh = 2 * 4
    np.testing.assert_array_equal(np.asarray(view),
                                  np.asarray(image_view(imgs, 5,
                                                        ceil_to(mh, 3))))
    primal, _ = jax.vjp(lambda ww: p2m_conv(imgs, ww, sh, MODEL, ADC, "relu",
                                            5, 5, True, "pallas"), w)
    np.testing.assert_array_equal(np.asarray(primal), np.asarray(plain[0]))


def test_bwd_dw_route_counters():
    """One trace of a p2m_conv backward counts its dW route once: the
    image view at stride == kernel with the Pallas backward, the patch
    matrix at stride != kernel."""
    view = default_registry().counter("p2m_conv.bwd_dw_view")
    patches = default_registry().counter("p2m_conv.bwd_dw_patches")
    for k, s, counter in [(5, 5, view), (5, 3, patches)]:
        imgs, w, sh = _conv_data(1, 13, 13, 3, k, seed=14)
        v0, p0 = view.value, patches.value
        jax.make_jaxpr(jax.grad(lambda ww: p2m_conv(
            imgs, ww, sh, MODEL, ADC, "relu", k, s, True, "pallas").sum()))(w)
        assert counter.value - (v0 if counter is view else p0) == 1
        assert view.value + patches.value == v0 + p0 + 1


def test_fused_conv_quant_ste_gradient():
    """quant forward is stepped; its gradient is the relu path's (STE)."""
    imgs, w, sh = _conv_data(1, 13, 13, 3, 5, seed=6)
    gq = jax.grad(lambda im: p2m_conv(im, w, sh, MODEL, ADC, "quant", 5, 3,
                                      True, "pallas").sum())(imgs)
    gr = jax.grad(lambda im: p2m_conv_jnp(im, w, sh, MODEL, ADC, "relu",
                                          5, 3).sum())(imgs)
    np.testing.assert_allclose(np.asarray(gq), np.asarray(gr),
                               rtol=1e-4, atol=1e-5)


def test_train_form_grad_impl_equivalence():
    """d loss/d theta agrees between the fused custom-VJP path and the
    patch-materializing autodiff path through the full train form."""
    cfg = P2MConvConfig()
    params = init_p2m_conv(jax.random.PRNGKey(0), cfg)
    state = init_p2m_state(cfg)
    imgs = jax.random.uniform(jax.random.PRNGKey(1), (2, 20, 20, 3))

    def loss(theta, impl):
        p = dict(params, theta=theta)
        out, _ = apply_p2m_conv_train(p, state, imgs, cfg, impl=impl)
        return (out ** 2).sum()

    g_pallas = jax.grad(lambda t: loss(t, "pallas"))(params["theta"])
    g_fused = jax.grad(lambda t: loss(t, "fused"))(params["theta"])
    g_patch = jax.grad(lambda t: loss(t, "patches"))(params["theta"])
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_patch),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g_pallas), np.asarray(g_patch),
                               rtol=1e-4, atol=1e-5)


def test_deploy_impl_equivalence():
    cfg = P2MConvConfig()
    rng = np.random.default_rng(8)
    dep = {
        "w": jnp.asarray(rng.uniform(-1, 1, (75, cfg.out_channels)),
                         jnp.float32),
        "shift": jnp.asarray(rng.uniform(-0.1, 0.1, (cfg.out_channels,)),
                             jnp.float32),
    }
    imgs = jnp.asarray(rng.random((2, 20, 20, 3)), jnp.float32)
    outs = [apply_p2m_conv_deploy(dep, imgs, cfg, quantize=True, impl=impl)
            for impl in ("pallas", "fused", "patches")]
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(outs[2]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(outs[1]), np.asarray(outs[2]),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Autotuner
# ---------------------------------------------------------------------------


def test_autotune_candidates_respect_vmem_budget():
    for cand in tune.matmul_candidates(4096, 75, 8):
        assert tune.matmul_vmem_bytes(*cand) <= tune.VMEM_BUDGET_BYTES
    for bh, bn, depth in tune.conv_candidates(8, 112, 112, 8, 15):
        assert depth in tune.CONV_PIPELINE_DEPTHS
        assert tune.conv_vmem_bytes(bh, 112, 15, bn,
                                    depth=depth) <= tune.VMEM_BUDGET_BYTES
    assert tune.matmul_candidates(4096, 75, 8)  # never empty at paper geom
    assert tune.conv_candidates(8, 112, 112, 8, 15)


def test_autotune_times_once_and_caches():
    tune.cache_clear()
    calls = []
    orig = tune._time_once

    def counting_timer(fn, *args, **kw):
        calls.append(1)
        return orig(fn, *args, iters=1, warmup=0)

    tune._time_once = counting_timer
    try:
        blocks = tune.get_matmul_blocks(16, 12, 4, COEFFS, "relu",
                                        enable=True, interpret=True, iters=1)
        n_first = len(calls)
        assert n_first >= 1
        again = tune.get_matmul_blocks(16, 12, 4, COEFFS, "relu",
                                       enable=True, interpret=True, iters=1)
        assert again == blocks
        assert len(calls) == n_first  # cached: no re-timing
    finally:
        tune._time_once = orig
        tune.cache_clear()


def test_autotune_disabled_returns_defaults_instantly():
    tune.cache_clear()
    assert tune.get_matmul_blocks(10**6, 75, 8, COEFFS, "relu",
                                  enable=False) == (256, 128, 128)
    assert tune.get_conv_blocks(8, 224, 224, 3, 8, 5, 5, COEFFS, "relu",
                                enable=False) == (None, None, 0)


def test_autotuned_conv_blocks_stay_correct():
    """Whatever block shape the tuner picks must not change the numerics."""
    tune.cache_clear()
    imgs, w, sh = _conv_data(1, 15, 15, 3, 5, seed=9)
    ref = _patch_reference(imgs, w, sh, 5, 5, "relu")
    bh, bn, depth = tune.get_conv_blocks(1, 15, 15, 3, 8, 5, 5, COEFFS,
                                         "relu", enable=True, interpret=True,
                                         iters=1)
    out = p2m_conv_pallas(imgs, w, sh, kernel=5, stride=5, coeffs=COEFFS,
                          mode="relu", block_h=bh, block_n=bn,
                          pipeline_depth=depth, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    tune.cache_clear()


def test_autotune_never_times_under_a_trace(monkeypatch):
    """Tuning forced on: a jitted p2m_conv (forward and grad) serves the
    static default, counted as ``autotune.traced_default``, and records no
    timed decision; the same call made eagerly on concrete arrays tunes."""
    from repro.obs.metrics import default_registry

    monkeypatch.setenv("REPRO_P2M_AUTOTUNE", "1")
    tune.cache_clear()
    traced = default_registry().counter("autotune.traced_default")
    t0 = traced.value
    imgs, w, sh = _conv_data(1, 10, 10, 3, 5, seed=3)

    def f(im):
        return p2m_conv(im, w, sh, MODEL, ADC, "relu", 5, 5, True)

    try:
        jax.block_until_ready(jax.jit(f)(imgs))
        jax.block_until_ready(jax.jit(jax.grad(lambda im: f(im).sum()))(imgs))
        assert tune.decision_records() == []
        assert traced.value - t0 >= 3  # conv fwd, conv fwd under grad, bwd
        f(imgs)  # eager, concrete arrays: the tuner times candidates
        (rec,) = tune.decision_records()
        assert rec["kind"] == "conv" and rec["n_viable"] >= 1
    finally:
        tune.cache_clear()


def test_autotune_refuses_to_time_tracers():
    """`autotune` called while JAX traces raises instead of timing
    tracing."""
    tune.cache_clear()
    key = ("traced_probe", 1)

    def tunes_under_trace(x):
        tune.autotune(key, [(1,), (2,)], lambda c: x * c[0], iters=1)
        return x

    try:
        with pytest.raises(RuntimeError, match="under a trace"):
            jax.jit(tunes_under_trace)(jnp.ones(2))
        assert key not in tune._CACHE
    finally:
        tune.cache_clear()


def test_mosaic_rejects_unlowerable_conv_geometries():
    """Compiled (interpret=False), the geometries Mosaic cannot lower
    raise a clear ValueError before lowering — stride != kernel, and the
    DMA ring where k·C is off the 128-lane quantum — and the tuner takes
    the ring off its menu there."""
    from repro.kernels.p2m_conv.conv import mosaic_conv_error

    assert mosaic_conv_error(5, 5, 3) is None
    assert mosaic_conv_error(5, 5, 3, 0) is None
    assert "128" in mosaic_conv_error(5, 5, 3, 2)
    assert mosaic_conv_error(4, 4, 32, 2) is None  # k·C = 128 tiles
    assert "stride" in mosaic_conv_error(5, 2, 3)

    imgs, w, sh = _conv_data(1, 20, 20, 3, 5)
    with pytest.raises(ValueError, match="stride 2 != kernel 5"):
        p2m_conv_pallas(imgs, w, sh, kernel=5, stride=2, coeffs=COEFFS,
                        interpret=False)
    with pytest.raises(ValueError, match="multiple of 128"):
        p2m_conv_pallas(imgs, w, sh, kernel=5, stride=5, coeffs=COEFFS,
                        pipeline_depth=2, interpret=False)
    with pytest.raises(ValueError, match="stride 2 != kernel 5"):
        tune.get_conv_blocks(1, 20, 20, 3, 8, 5, 2, COEFFS, "quant",
                             enable=False, interpret=False)
    tune.cache_clear()

    def blocks_under_trace(x):
        assert tune.get_conv_blocks(1, 20, 20, 3, 8, 5, 5, COEFFS, "quant",
                                    enable=True, interpret=False) == (
            None, None, 0)
        return x

    try:
        jax.jit(blocks_under_trace)(jnp.ones(2))
        assert tune.cache_info() == {}  # traced: nothing timed or cached
    finally:
        tune.cache_clear()


def test_kernel_ops_imports_first():
    """`repro.kernels.p2m_conv.ops` imports as a process's first repro
    import (the core <-> kernels cycle resolves through module refs)."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    src = str(Path(__file__).resolve().parents[1] / "src")
    env = {**os.environ, "PYTHONPATH": src, "JAX_PLATFORMS": "cpu"}
    for mod in ("repro.kernels.p2m_conv.ops", "repro.core.bn_fold",
                "repro.kernels.p2m_conv"):
        out = subprocess.run([sys.executable, "-c", f"import {mod}"],
                             env=env, capture_output=True, text=True,
                             timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
