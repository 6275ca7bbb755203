"""Fused delta-gated P²M stem kernel (DESIGN.md §3.6).

The streaming-video engine's temporal delta gate (`video/delta.py`)
decides per slot whether this tick's frame needs the stem re-run.  The
original engine path computed the stem for **every** slot and discarded
the skipped results with a host-visible ``jnp.where`` — shape-stable,
but the opposite of the event-driven skipping the gate models
(Neuromorphic-P2M, arXiv:2301.09111): every masked-off slot still paid
the full stem FLOPs.

`p2m_conv_pallas_gated` fuses the select into the conv kernel itself.
The per-slot rerun mask rides as a **scalar-prefetch** operand
(`pltpu.PrefetchScalarGridSpec` — available in SMEM before the tile
body runs), expanded host-side to one int32 per row tile.  Inside the
kernel each (rows, N) tile branches on its mask scalar:

* mask 0 — the tile's slot is gated off: skip the power expansion and
  the MXU dot entirely (``pl.when`` — a real branch, no wasted stem
  FLOPs) and copy the cached tile to the output;
* mask 1 — compute the tile exactly like the dense kernel (same
  accumulate order) and run the epilogue.

One launch, no host round-trip, and bitwise-identical to
``dense-kernel + jnp.where`` by construction (computed rows run the
same tile compute in the same order; skipped rows copy the same cache)
— pinned by test and gated at 1.0 in the bench.

``block_h`` is clamped to a divisor of ``Ho`` (`aligned_block_h`) so a
row tile never straddles two slots: every tile is then all-rerun or
all-skip, the scalar mask is exact, and the FLOPs actually skipped
equal the mask's skip fraction (the ``stem_flops_skipped_ratio`` the
bench records).  The tile's input block is still DMA'd by the pipeline
— the win is stem *FLOPs*; the readout *bits* the gate models are
metered separately by the stream ledger (`core/bandwidth.py`).

`p2m_conv_gated_jnp` is the XLA twin — compute-all + where-select, the
reference path the engine keeps (``stem_path="where"``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.p2m_conv.conv import (
    _accumulate_step,
    _epilogue_values,
    ceil_to,
    conv_out_spatial,
    default_conv_blocks,
    mosaic_conv_error,
    p2m_conv_jnp,
    premix_weights,
)


def aligned_block_h(ho: int, bh: int) -> int:
    """Largest divisor of ``ho`` that is ≤ ``bh`` — the slot-aligned row
    tile: with ``bh | Ho`` a tile's rows all belong to one image, so the
    per-slot mask is uniform across the tile and a skip skips the whole
    tile's FLOPs."""
    bh = max(1, min(bh, ho))
    while ho % bh:
        bh -= 1
    return bh


def _gated_tail(mask, shift_ref, cached_ref, out_ref, acc_ref, *, last,
                mode: str, v_lsb: float, max_count: int):
    """Per-tile select: fresh epilogue where the slot reran, cache copy
    where it was gated off (the copy runs every kernel-row step it's
    cheap and keeps the skip path free of the acc scratch, which holds
    stale values for skipped tiles)."""

    @pl.when(mask & last)
    def _epilogue():
        raw = acc_ref[...]
        shift = shift_ref[...].astype(jnp.float32)
        out = _epilogue_values(raw, shift, mode=mode, v_lsb=v_lsb,
                               max_count=max_count)
        out_ref[...] = out.reshape(out_ref.shape)

    @pl.when(jnp.logical_not(mask) & last)
    def _copy_cache():
        out_ref[...] = cached_ref[...]


def _gated_kernel_fast(mask_ref, a_ref, wmix_ref, shift_ref, cached_ref,
                       out_ref, acc_ref, *, k: int, dx: int, mode: str,
                       v_lsb: float, max_count: int):
    """stride == kernel; a_ref is (bh, 1, Wo, kC); mask_ref is the
    scalar-prefetch per-row-tile rerun vector."""
    mi, ki = pl.program_id(0), pl.program_id(2)
    mask = mask_ref[mi] != 0

    @pl.when(mask)  # a gated-off tile issues no MXU work at all
    def _compute():
        bh, _, wo, kc = a_ref.shape
        x2d = a_ref[...].reshape(bh * wo, kc)
        wmix2d = wmix_ref[...].reshape(wmix_ref.shape[1], wmix_ref.shape[2])
        _accumulate_step(x2d, wmix2d, acc_ref, dx=dx, first=ki == 0)

    _gated_tail(mask, shift_ref, cached_ref, out_ref, acc_ref,
                last=ki == k - 1, mode=mode, v_lsb=v_lsb,
                max_count=max_count)


def _gated_kernel_general(mask_ref, band_ref, wmix_ref, shift_ref,
                          cached_ref, out_ref, acc_ref, *, k: int,
                          stride: int, wo: int, dx: int, mode: str,
                          v_lsb: float, max_count: int):
    """General stride; band_ref is (1, bh, Wband, C) — see conv.py §3.2."""
    mi, ki = pl.program_id(0), pl.program_id(2)
    mask = mask_ref[mi] != 0

    @pl.when(mask)
    def _compute():
        _, bh, wpad, c = band_ref.shape
        band = band_ref[...].reshape(bh, wpad, c)
        parts = []
        for dw in range(k):
            win = band[:, dw : dw + wo * stride, :]
            parts.append(win.reshape(bh, wo, stride, c)[:, :, 0, :])
        x = jnp.stack(parts, axis=2)
        x2d = x.reshape(bh * wo, k * c)
        wmix2d = wmix_ref[...].reshape(wmix_ref.shape[1], wmix_ref.shape[2])
        _accumulate_step(x2d, wmix2d, acc_ref, dx=dx, first=ki == 0)

    _gated_tail(mask, shift_ref, cached_ref, out_ref, acc_ref,
                last=ki == k - 1, mode=mode, v_lsb=v_lsb,
                max_count=max_count)


@functools.partial(
    jax.jit,
    static_argnames=("kernel", "stride", "coeffs", "mode", "v_lsb",
                     "max_count", "block_h", "block_n", "interpret"),
)
def p2m_conv_pallas_gated(
    images,
    w,
    shift,
    cached,
    rerun,
    *,
    kernel: int,
    stride: int,
    coeffs: tuple,
    mode: str = "relu",
    v_lsb: float = 1.0 / 255.0,
    max_count: int = 255,
    block_h: int | None = None,
    block_n: int | None = None,
    interpret: bool = False,
):
    """Delta-gated fused conv: one launch computes the stem only where
    ``rerun`` says to and returns the cached activations elsewhere.

    images: (B, H, W, C); w/shift as `p2m_conv_pallas`; cached:
    (B, Ho, Wo, N) — the slot-resident stem cache; rerun: (B,) bool.
    Inference-only (no VJP): the serving hot path never differentiates
    through the gate.  Compiled, ``stride != kernel`` raises ``ValueError``
    (`conv.mosaic_conv_error`).
    """
    b, h, w_dim, c = images.shape
    k, s = kernel, stride
    if not interpret and (err := mosaic_conv_error(k, s, c)):
        raise ValueError(err)
    ho = conv_out_spatial(h, k, s)
    wo = conv_out_spatial(w_dim, k, s)
    kc = k * c
    n = w.shape[1]
    assert cached.shape == (b, ho, wo, n), (cached.shape, (b, ho, wo, n))
    assert rerun.shape == (b,), rerun.shape
    dx = len(coeffs[0])

    wmix = premix_weights(w, coeffs)
    wmix = wmix.reshape(dx, k, kc, n).transpose(1, 0, 2, 3).reshape(
        k, dx * kc, n)

    bh_default, bn_default = default_conv_blocks(b, ho, wo, n, dx * kc)
    # Slot alignment: bh | Ho ⇒ every row tile belongs to one image and
    # mh = B·Ho needs no row padding.
    bh = aligned_block_h(ho, block_h or bh_default)
    bn = min(block_n or bn_default, ceil_to(n, 128))

    mh = b * ho
    n_pad = ceil_to(n, bn)

    wmix = jnp.pad(wmix, ((0, 0), (0, 0), (0, n_pad - n)))
    sp = jnp.pad(jnp.asarray(shift, jnp.float32), (0, n_pad - n)).reshape(
        1, n_pad)
    # One int32 per row tile (scalar prefetch): tile mi belongs to image
    # mi·bh // Ho, i.e. repeat each slot's flag Ho/bh times.
    tile_mask = jnp.repeat(jnp.asarray(rerun, jnp.int32), ho // bh)
    cached_p = jnp.pad(cached.astype(jnp.float32).reshape(mh, wo, n),
                       ((0, 0), (0, 0), (0, n_pad - n)))

    grid = (mh // bh, n_pad // bn, k)
    common = dict(mode=mode, v_lsb=v_lsb, max_count=max_count)
    if s == k:
        a = images[:, : ho * k, : wo * k, :].reshape(mh, k, wo, kc)
        kernel_fn = functools.partial(_gated_kernel_fast, k=k, dx=dx,
                                      **common)
        x_spec = pl.BlockSpec((bh, 1, wo, kc),
                              lambda mi, ni, ki, m: (mi, ki, 0, 0))
        x_arr = a
    else:
        rows = jnp.stack(
            [images[:, dh : dh + (ho - 1) * s + 1 : s, :, :]
             for dh in range(k)],
            axis=0,
        ).reshape(k, mh, w_dim, c)
        w_band = wo * s + k
        rows = jnp.pad(rows, ((0, 0), (0, 0), (0, w_band - w_dim), (0, 0)))
        kernel_fn = functools.partial(_gated_kernel_general, k=k, stride=s,
                                      wo=wo, dx=dx, **common)
        x_spec = pl.BlockSpec((1, bh, w_band, c),
                              lambda mi, ni, ki, m: (ki, mi, 0, 0))
        x_arr = rows

    out = pl.pallas_call(
        kernel_fn,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                x_spec,
                pl.BlockSpec((1, dx * kc, bn),
                             lambda mi, ni, ki, m: (ki, 0, ni)),
                pl.BlockSpec((1, bn), lambda mi, ni, ki, m: (0, ni)),
                pl.BlockSpec((bh, wo, bn), lambda mi, ni, ki, m: (mi, 0, ni)),
            ],
            out_specs=pl.BlockSpec((bh, wo, bn),
                                   lambda mi, ni, ki, m: (mi, 0, ni)),
            scratch_shapes=[pltpu.VMEM((bh * wo, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((mh, wo, n_pad), jnp.float32),
        interpret=interpret,
    )(tile_mask, x_arr, wmix, sp, cached_p)
    return out[:, :, :n].reshape(b, ho, wo, n)


def p2m_conv_gated_jnp(images, w, shift, cached, rerun, *, kernel: int,
                       stride: int, coeffs, mode: str = "relu",
                       v_lsb: float = 1.0 / 255.0, max_count: int = 255):
    """XLA twin: dense stem + where-select — the reference path.  Shape-
    stable XLA cannot branch on the traced mask, so every slot pays the
    stem FLOPs; only the Pallas kernel genuinely skips them."""
    stem = p2m_conv_jnp(images, w, shift, kernel=kernel, stride=stride,
                        coeffs=coeffs, mode=mode, v_lsb=v_lsb,
                        max_count=max_count)
    return jnp.where(jnp.asarray(rerun, bool)[:, None, None, None],
                     stem, cached.astype(jnp.float32))
