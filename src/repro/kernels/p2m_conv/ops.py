"""Jit'd wrappers for the P²M inner product and the fused P²M conv.

Tiers, all computing the same math (see `ref.py` for the oracle):

* :func:`p2m_matmul_jnp` — basis-decomposed XLA version (dw·dx matmuls)
  on pre-extracted im2col patches, fully differentiable through autodiff.
  The reference fallback.
* :func:`p2m_matmul` — Pallas kernel forward (VMEM-fused power expansion
  + epilogue) on patches, with a custom VJP whose backward runs the
  closed-form premixed kernels in `backward.py` (Pallas on TPU, XLA
  closed form elsewhere) instead of re-differentiating the forward.
* :func:`p2m_conv` — the fused implicit-im2col convolution (`conv.py`):
  NHWC images in, no HBM patch tensor, same custom-VJP treatment.  The
  hot path for both training and deployment.  At ``stride == kernel``
  with the Pallas backward, the forward keeps the image view its kernel
  read, and the dW kernel reads that view: the weight gradient builds no
  patch matrix either.  Only the input gradient (dX, which training does
  not take) and the other routes read the im2col patch matrix.
* mode="quant" uses an STE backward (gradient of the soft-clipped path).

Forward Pallas calls route their block sizes through the autotuner
(`tune.py`; off-TPU it returns the static defaults instantly), and the
backward kernels reuse the forward winner for the same (M, K, N)
signature — the tile dims are driven by the same operands.  A call on
Tracers (inside jit or grad) only looks winners up: the tuner times
eager calls alone.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.adc import ADCConfig
from repro.core.pixel_model import PixelModel
from repro.kernels.p2m_conv import tune
from repro.kernels.p2m_conv.backward import (
    epilogue_mask,
    p2m_backward,
    p2m_backward_jnp,
)
from repro.kernels.p2m_conv.conv import (
    _epilogue_values,
    conv_out_spatial,
    im2col_matrix,
    p2m_conv_jnp as _conv_jnp_impl,
    p2m_conv_pallas,
)
from repro.kernels.p2m_conv.kernel import p2m_matmul_pallas
from repro.obs.metrics import default_registry

_DEFAULT_ADC = ADCConfig()


def _coeff_tuple(model: PixelModel) -> tuple:
    return tuple(tuple(float(v) for v in row) for row in model.coeffs)


def _resolve_interpret(interpret: bool | None) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _use_pallas_bwd(bwd_impl: str | None, interpret: bool) -> bool:
    """Backward dispatch: Pallas kernels on TPU, closed-form XLA off-TPU
    (timing interpret-mode kernels in the train loop would be absurd);
    ``bwd_impl`` in {"pallas", "jnp"} forces either — tests force "pallas"
    with interpret=True to cover the kernels everywhere."""
    if bwd_impl is not None:
        return bwd_impl == "pallas"
    return not interpret


def p2m_matmul_jnp(x, w, shift, model: PixelModel, adc: ADCConfig | None = None,
                   mode: str = "relu"):
    """Basis-decomposed P²M product in plain jnp (differentiable).

    x: (M, K) in [0,1]; w: (K, N) signed; shift: (N,) volts.
    mode: "raw" (accumulation + shift), "relu" (shifted ReLU with full-scale
    saturation), "quant" (integer-exact counter emulation, STE-friendly
    only through :func:`p2m_matmul`).
    """
    adc = adc or _DEFAULT_ADC
    coeffs = model.coeffs
    dw, dx = coeffs.shape
    x32 = x.astype(jnp.float32)
    sgn = jnp.sign(w).astype(jnp.float32)
    aw = jnp.abs(w).astype(jnp.float32)

    acc = jnp.zeros((x.shape[0], w.shape[1]), jnp.float32)
    wp = aw
    for i in range(1, dw + 1):
        wsig = sgn * wp
        xp = x32
        for j in range(1, dx + 1):
            a_ij = float(coeffs[i - 1, j - 1])
            if a_ij != 0.0:
                acc = acc + a_ij * (xp @ wsig)
            if j < dx:
                xp = xp * x32
        if i < dw:
            wp = wp * aw
    return _epilogue_jnp(acc, shift, adc, mode)


def _epilogue_jnp(acc, shift, adc: ADCConfig, mode: str):
    # Single source of truth for the epilogue semantics (conv.py) — the
    # Pallas kernels run the same function inside VMEM.
    return _epilogue_values(acc, jnp.asarray(shift, jnp.float32),
                            mode=mode, v_lsb=adc.v_lsb,
                            max_count=adc.max_count)


# ---------------------------------------------------------------------------
# Patch-level Pallas op with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def p2m_matmul(x, w, shift, model: PixelModel, adc: ADCConfig | None = None,
               mode: str = "relu", interpret: bool | None = None,
               bwd_impl: str | None = None):
    """Pallas-kernel P²M product; differentiable via custom VJP.

    ``interpret=None`` auto-selects interpret mode off-TPU (the kernel body
    then runs as reference Python, validating the TPU lowering path).
    ``bwd_impl`` forces the backward implementation ("pallas" | "jnp");
    None auto-selects like the forward.
    """
    return _matmul_fwd_only(x, w, shift, model, adc, mode, interpret)


def _matmul_fwd_only(x, w, shift, model, adc, mode, interpret,
                     want_raw: bool = False):
    adc = adc or _DEFAULT_ADC
    interpret = _resolve_interpret(interpret)
    coeffs = _coeff_tuple(model)
    bm, bn, bk = tune.get_matmul_blocks(x.shape[0], x.shape[1], w.shape[1],
                                        coeffs, mode, interpret=interpret)
    return p2m_matmul_pallas(
        x,
        w,
        shift,
        coeffs=coeffs,
        mode=mode,
        v_lsb=adc.v_lsb,
        max_count=adc.max_count,
        block_m=bm,
        block_n=bn,
        block_k=bk,
        want_raw=want_raw,
        interpret=interpret,
    )


def _p2m_fwd(x, w, shift, model, adc, mode, interpret, bwd_impl):
    with jax.named_scope("p2m_matmul_fwd"):
        out, raw = _matmul_fwd_only(x, w, shift, model, adc, mode, interpret,
                                    want_raw=True)
    return out, (x, w, shift, raw)


def _p2m_bwd(model, adc, mode, interpret, bwd_impl, res, g):
    x, w, shift, raw = res
    adc = adc or _DEFAULT_ADC
    interpret = _resolve_interpret(interpret)
    coeffs = _coeff_tuple(model)
    # Reuse the forward-tuned blocks (cache hit — the fwd ran first).
    blocks = tune.get_matmul_blocks(x.shape[0], x.shape[1], w.shape[1],
                                    coeffs, mode, interpret=interpret)
    with jax.named_scope("p2m_matmul_bwd"):
        mask = epilogue_mask(raw, shift, mode=mode, full_scale=adc.full_scale)
        g_eff = g.astype(jnp.float32) * mask
        gx, gw = p2m_backward(g_eff, w, x, coeffs,
                              use_pallas=_use_pallas_bwd(bwd_impl, interpret),
                              interpret=interpret, blocks=blocks)
        gs = g_eff.sum(axis=0)
        return (gx.astype(x.dtype), gw.astype(w.dtype),
                gs.astype(jnp.asarray(shift).dtype))


p2m_matmul.defvjp(_p2m_fwd, _p2m_bwd)


# ---------------------------------------------------------------------------
# Fused implicit-im2col conv op with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def p2m_conv(images, w, shift, model: PixelModel,
             adc: ADCConfig | None = None, mode: str = "relu",
             kernel: int = 5, stride: int = 5,
             interpret: bool | None = None, bwd_impl: str | None = None,
             pipeline_depth: int | None = None):
    """Fused P²M convolution: (B, H, W, C) images → (B, Ho, Wo, N).

    Forward is the implicit-im2col Pallas kernel (`conv.py`) — no HBM
    patch tensor in either the ``stride == kernel`` fast path (zero-copy
    image view) or the general strided path (per-kernel-row VMEM bands).

    Backward runs the premixed closed-form kernels (`backward.py`); at
    ``stride == kernel`` the Pallas dW kernel reads the forward's image
    view.  The col2im scatter back to image space is a pure reshape at
    ``stride == kernel`` and an XLA scatter-add otherwise.

    ``pipeline_depth`` overrides the autotuner's depth axis (DESIGN.md
    §3.5): ``None`` defers to the tuned winner, 0 forces the automatic
    grid pipeline, ≥2 forces the explicit double-buffered DMA ring —
    tests and benches pin both to prove parity.
    """
    return _conv_fwd_only(images, w, shift, model, adc, mode, kernel,
                          stride, interpret, pipeline_depth=pipeline_depth)


def _conv_fwd_only(images, w, shift, model, adc, mode, kernel, stride,
                   interpret, want_raw: bool = False,
                   pipeline_depth: int | None = None,
                   want_view: bool = False):
    adc = adc or _DEFAULT_ADC
    interpret = _resolve_interpret(interpret)
    coeffs = _coeff_tuple(model)
    b, h, w_dim, c = images.shape
    bh, bn, depth = tune.get_conv_blocks(b, h, w_dim, c, w.shape[1], kernel,
                                         stride, coeffs, mode,
                                         interpret=interpret)
    if pipeline_depth is not None:
        depth = pipeline_depth
    return p2m_conv_pallas(
        images,
        w,
        shift,
        kernel=kernel,
        stride=stride,
        coeffs=coeffs,
        mode=mode,
        v_lsb=adc.v_lsb,
        max_count=adc.max_count,
        block_h=bh,
        block_n=bn,
        pipeline_depth=depth,
        want_raw=want_raw,
        want_view=want_view,
        interpret=interpret,
    )


def p2m_conv_jnp(images, w, shift, model: PixelModel,
                 adc: ADCConfig | None = None, mode: str = "relu",
                 kernel: int = 5, stride: int = 5):
    """Fused conv in XLA ops (differentiable; patch-free) — the off-TPU
    twin of :func:`p2m_conv` and its autodiff reference."""
    adc = adc or _DEFAULT_ADC
    return _conv_jnp_impl(images, w, shift, kernel=kernel, stride=stride,
                          coeffs=_coeff_tuple(model), mode=mode,
                          v_lsb=adc.v_lsb, max_count=adc.max_count)


def _conv_fwd(images, w, shift, model, adc, mode, kernel, stride, interpret,
              bwd_impl, pipeline_depth):
    # The Pallas dW kernel reads the forward's image view: keep it.
    want_view = stride == kernel and _use_pallas_bwd(
        bwd_impl, _resolve_interpret(interpret))
    with jax.named_scope("p2m_conv_fwd"):
        out, raw, *view = _conv_fwd_only(
            images, w, shift, model, adc, mode, kernel, stride, interpret,
            want_raw=True, pipeline_depth=pipeline_depth,
            want_view=want_view)
    return out, (images, w, shift, raw, view[0] if view else None)


def _conv_bwd(model, adc, mode, kernel, stride, interpret, bwd_impl,
              pipeline_depth, res, g):
    images, w, shift, raw, view = res
    adc = adc or _DEFAULT_ADC
    interpret = _resolve_interpret(interpret)
    coeffs = _coeff_tuple(model)
    n = w.shape[1]
    m = raw.shape[0] * raw.shape[1] * raw.shape[2]
    route = "patches" if view is None else "view"
    default_registry().counter(f"p2m_conv.bwd_dw_{route}").inc()

    with jax.named_scope("p2m_conv_bwd"):
        raw2d = raw.reshape(m, n)
        mask = epilogue_mask(raw2d, shift, mode=mode,
                             full_scale=adc.full_scale)
        g_eff = g.reshape(m, n).astype(jnp.float32) * mask

        # X's powers enter both gradients.  dW reads the forward's image
        # view where it kept one (stride == kernel, Pallas backward), and
        # the patch matrix otherwise; dX always reads the patch matrix
        # (zero-copy reshapes at stride == kernel; a gather otherwise).
        # A step that takes no image gradient, as training does, leaves
        # the matrix to dX alone, and XLA drops the whole dX chain.
        with jax.named_scope("im2col"):
            x, im2col_vjp = jax.vjp(
                lambda im: im2col_matrix(im, kernel, stride), images)
        blocks = tune.get_matmul_blocks(x.shape[0], x.shape[1], w.shape[1],
                                        coeffs, mode, interpret=interpret)
        with jax.named_scope("dx_dw"):
            gx, gw = p2m_backward(
                g_eff, w, x, coeffs,
                use_pallas=_use_pallas_bwd(bwd_impl, interpret),
                interpret=interpret, blocks=blocks, dw_view=view)
        with jax.named_scope("col2im"):
            (gimages,) = im2col_vjp(gx.astype(x.dtype))
        gs = g_eff.sum(axis=0)
        return (gimages.astype(images.dtype), gw.astype(w.dtype),
                gs.astype(jnp.asarray(shift).dtype))


p2m_conv.defvjp(_conv_fwd, _conv_bwd)
