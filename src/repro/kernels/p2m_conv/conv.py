"""Fused implicit-im2col P²M convolution (DESIGN.md §3).

The patch-materializing path (`core.p2m_conv.extract_patches` +
`p2m_matmul`) round-trips a ``(B, P, k·k·C)`` patch tensor through HBM —
a ~``k²/s²`` blow-up of the input for overlapping strides, and an extra
O(input) transpose copy even in the paper's non-overlapping ``s == k``
geometry.  The kernels here take NHWC images directly and gather each
activation tile *in VMEM* via the block index map, so no patch tensor
ever exists in HBM:

* **fast path** (``stride == kernel``): the im2col matrix is a pure
  reshape of the (cropped) image — ``(B·Ho, k, Wo, k·C)`` with the K
  dimension split across the ``k`` kernel rows.  Zero-copy; the grid's
  third dimension walks kernel rows ``dh`` and the block index map picks
  ``A[mi·bh : , dh, :, :]`` straight out of the image.

* **general path** (any ``stride < kernel``): a per-kernel-row band of
  image rows (``k·B·Ho·W·C`` total — ≤ ``k/s``× the input, vs ``k²/s²``×
  for im2col) is streamed through VMEM; the ``k`` sliding windows along W
  are sliced out of the resident band with static strided views.

Both paths share the **basis-premix** tile compute (DESIGN.md §2.3): with
``g(w,x) = Σ_ij a_ij w^i x^j`` the accumulation is

    raw = Σ_j (X^∘j) @ W̃_j,   W̃_j := Σ_i a_ij · sign(W) ⊙ |W|^∘i

``W̃`` is precomputed outside the kernel (it is weight-sized, O(dx·K·N)),
so each grid step issues ONE MXU dot of ``[X, X², …] @ [W̃_1; W̃_2; …]``
instead of dw·dx separate passes.  The CDS/ADC epilogue (BN pre-load
shift, counter ReLU clamp, optional integer-exact quantization) runs on
the final kernel-row step, in VMEM.

`p2m_conv_jnp` is the same decomposition expressed in XLA ops
(differentiable, patch-free) — the CPU/GPU fallback and the autodiff
reference for the Pallas backward kernels in `backward.py`.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def conv_out_spatial(size: int, kernel: int, stride: int) -> int:
    """VALID conv output extent."""
    return (size - kernel) // stride + 1


def ceil_to(x: int, m: int) -> int:
    """Round ``x`` up to a multiple of the tile quantum ``m`` — the one
    copy shared by the forward/backward kernels and the tuner, so padding
    and candidate enumeration can never disagree."""
    return -(-x // m) * m


def premix_weights(w, coeffs) -> jax.Array:
    """Fold the pixel-polynomial w-powers into the weights.

    w: (K, N) signed weights; coeffs: (dw, dx) nested floats.
    Returns W̃ of shape (dx, K, N) with ``W̃[j-1] = Σ_i a_ij sign(w)|w|^i``
    — after this, the P²M product is ``Σ_j X^∘j @ W̃_j`` (DESIGN.md §2.3).
    """
    w = jnp.asarray(w, jnp.float32)
    dw = len(coeffs)
    dx = len(coeffs[0])
    sgn = jnp.sign(w)
    aw = jnp.abs(w)
    pow_i = []  # sign(w)·|w|^i for i = 1..dw
    wp = aw
    for i in range(1, dw + 1):
        pow_i.append(sgn * wp)
        if i < dw:
            wp = wp * aw
    return jnp.stack(
        [
            sum(float(coeffs[i][j]) * pow_i[i] for i in range(dw))
            for j in range(dx)
        ],
        axis=0,
    )


def _power_concat(x, dx: int):
    """[x, x∘x, …, x^∘dx] along the last axis; x is fp32 (bm, kc)."""
    xs = [x]
    xp = x
    for _ in range(dx - 1):
        xp = xp * x
        xs.append(xp)
    return jnp.concatenate(xs, axis=-1) if dx > 1 else x


def _epilogue_values(raw, shift, *, mode: str, v_lsb: float, max_count: int):
    """Shared CDS/ADC epilogue on an fp32 accumulation tile."""
    if mode == "raw":
        return raw + shift
    if mode == "relu":
        return jnp.clip(raw + shift, 0.0, max_count * v_lsb)
    if mode == "quant":
        counts = jnp.round(raw / v_lsb) + jnp.round(shift / v_lsb)
        return jnp.clip(counts, 0.0, float(max_count)) * v_lsb
    raise ValueError(f"unknown mode {mode!r}")


def _tile_dot(xcat, wmix2d):
    """``[x, x², …] @ W̃`` at full fp32 precision, whatever the caller's
    matmul-precision context.  The epilogue rounds this sum to ADC counts,
    and Mosaic's default (one bf16 pass) put 17% of the paper-geometry
    counts up to 3 LSB off the fp32 pixel model on a TPU v5e."""
    return jax.lax.dot_general(
        xcat,
        wmix2d.astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _accumulate_step(x2d, wmix2d, acc_ref, *, dx: int, first: jax.Array):
    """One grid step: acc += [x, x², …] @ W̃-tile (single MXU dot)."""

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xcat = _power_concat(x2d.astype(jnp.float32), dx)
    acc_ref[...] += _tile_dot(xcat, wmix2d)


def _write_outputs(shift_ref, out_ref, raw_ref, acc_ref, *, last, mode,
                   v_lsb, max_count):
    @pl.when(last)
    def _epilogue():
        raw = acc_ref[...]
        shift = shift_ref[...].astype(jnp.float32)  # (1, bn), broadcasts
        out = _epilogue_values(raw, shift, mode=mode, v_lsb=v_lsb,
                               max_count=max_count)
        out_ref[...] = out.reshape(out_ref.shape).astype(out_ref.dtype)
        if raw_ref is not None:
            raw_ref[...] = raw.reshape(raw_ref.shape)


def _conv_kernel_fast(a_ref, wmix_ref, shift_ref, *refs, k: int, dx: int,
                      mode: str, v_lsb: float, max_count: int):
    """stride == kernel: a_ref is (bh, 1, Wo, kC) — a zero-copy image view."""
    out_ref, raw_ref, acc_ref = _split_refs(refs)
    ki = pl.program_id(2)
    bh, _, wo, kc = a_ref.shape
    x2d = a_ref[...].reshape(bh * wo, kc)
    wmix2d = wmix_ref[...].reshape(wmix_ref.shape[1], wmix_ref.shape[2])
    _accumulate_step(x2d, wmix2d, acc_ref, dx=dx, first=ki == 0)
    _write_outputs(shift_ref, out_ref, raw_ref, acc_ref, last=ki == k - 1,
                   mode=mode, v_lsb=v_lsb, max_count=max_count)


def _conv_kernel_general(band_ref, wmix_ref, shift_ref, *refs, k: int,
                         stride: int, wo: int, dx: int, mode: str,
                         v_lsb: float, max_count: int):
    """General strided case: band_ref is (1, bh, Wpad, C) — one kernel-row
    band of image rows; the k sliding windows are sliced out in VMEM."""
    out_ref, raw_ref, acc_ref = _split_refs(refs)
    ki = pl.program_id(2)
    _, bh, wpad, c = band_ref.shape
    band = band_ref[...].reshape(bh, wpad, c)
    # Strided window gather, entirely on the VMEM-resident band: for each
    # in-row kernel offset dw, rows ow·s + dw for ow ∈ [0, Wo).
    parts = []
    for dw in range(k):
        win = band[:, dw : dw + wo * stride, :]
        parts.append(win.reshape(bh, wo, stride, c)[:, :, 0, :])
    x = jnp.stack(parts, axis=2)  # (bh, Wo, k, C) — (dw, c) fastest-varying
    x2d = x.reshape(bh * wo, k * c)
    wmix2d = wmix_ref[...].reshape(wmix_ref.shape[1], wmix_ref.shape[2])
    _accumulate_step(x2d, wmix2d, acc_ref, dx=dx, first=ki == 0)
    _write_outputs(shift_ref, out_ref, raw_ref, acc_ref, last=ki == k - 1,
                   mode=mode, v_lsb=v_lsb, max_count=max_count)


def _split_refs(refs):
    """(out, acc) or (out, raw, acc) depending on want_raw."""
    if len(refs) == 2:
        out_ref, acc_ref = refs
        return out_ref, None, acc_ref
    out_ref, raw_ref, acc_ref = refs
    return out_ref, raw_ref, acc_ref


# ---------------------------------------------------------------------------
# Pipelined (manual double-buffered DMA) kernel bodies — DESIGN.md §3.5
# ---------------------------------------------------------------------------
#
# The grid-path kernels above lean on Pallas's automatic pipeline, which
# double-buffers every operand uniformly.  The pipelined variants below
# take the activation/weight arrays as HBM-resident (`memory_space=ANY`)
# refs and stream the per-kernel-row tiles into an explicit `depth`-slot
# VMEM ring with `pltpu.make_async_copy`: while kernel row ``ki`` is on
# the MXU, rows ``ki+1 … ki+depth-1`` are already in flight HBM→VMEM —
# the Helium-guide prefetch discipline, with depth as a tunable knob
# (autotuner axis, `tune.py`).  The k-loop is unrolled in Python (k ≤ 7
# in every supported geometry), so slot indices are static and the same
# body lowers identically under interpret mode.
#
# Accumulation order is identical to the grid path (zeros, then one
# ``[x, x², …] @ W̃[ki]`` add per kernel row, ki ascending), so outputs
# are bitwise-identical to the non-pipelined kernel — pinned by test and
# gated at 1.0 in the bench.


def _pipelined_body(x_tile_2d, wbuf, shift_ref, out_ref, raw_ref, *, k: int,
                    depth: int, dx: int, mode: str, v_lsb: float,
                    max_count: int, x_dma, w_dma):
    """Shared ring-buffer driver: ``x_tile_2d(slot) -> (rows, kC)`` view of
    the x ring slot; ``x_dma/w_dma(slot, ki)`` build the async copies."""
    nbuf = min(depth, k)
    for ki in range(nbuf):  # warm-up: fill the ring
        x_dma(ki, ki).start()
        w_dma(ki, ki).start()
    acc = None
    for ki in range(k):
        slot = ki % nbuf
        x_dma(slot, ki).wait()
        w_dma(slot, ki).wait()
        xcat = _power_concat(x_tile_2d(slot).astype(jnp.float32), dx)
        term = _tile_dot(xcat, wbuf[slot])
        # Same fp-add order as the grid path's (init-zeros, then +=).
        acc = term if ki == 0 else acc + term
        nxt = ki + nbuf
        if nxt < k:  # refill the slot we just drained
            x_dma(slot, nxt).start()
            w_dma(slot, nxt).start()
    shift = shift_ref[...].astype(jnp.float32)  # (1, bn), broadcasts
    out = _epilogue_values(acc, shift, mode=mode, v_lsb=v_lsb,
                           max_count=max_count)
    out_ref[...] = out.reshape(out_ref.shape).astype(out_ref.dtype)
    if raw_ref is not None:
        raw_ref[...] = acc.reshape(raw_ref.shape)


def _conv_kernel_fast_pipelined(a_hbm, wmix_hbm, shift_ref, *refs, k: int,
                                depth: int, bh: int, bn: int, wo: int,
                                kc: int, dx: int, mode: str, v_lsb: float,
                                max_count: int):
    """stride == kernel, manual pipeline: a_hbm is the whole (mh, k, Wo,
    kC) image view in HBM; tile (mi, ki) streams into the x ring."""
    out_ref, raw_ref = (refs[0], refs[1]) if len(refs) == 6 else (refs[0], None)
    xbuf, wbuf, xsem, wsem = refs[-4:]
    mi, ni = pl.program_id(0), pl.program_id(1)

    def x_dma(slot, ki):
        return pltpu.make_async_copy(
            a_hbm.at[pl.ds(mi * bh, bh), ki], xbuf.at[slot], xsem.at[slot])

    def w_dma(slot, ki):
        return pltpu.make_async_copy(
            wmix_hbm.at[ki, :, pl.ds(ni * bn, bn)], wbuf.at[slot],
            wsem.at[slot])

    _pipelined_body(lambda slot: xbuf[slot].reshape(bh * wo, kc), wbuf,
                    shift_ref, out_ref, raw_ref, k=k, depth=depth, dx=dx,
                    mode=mode, v_lsb=v_lsb, max_count=max_count,
                    x_dma=x_dma, w_dma=w_dma)


def _conv_kernel_general_pipelined(rows_hbm, wmix_hbm, shift_ref, *refs,
                                   k: int, stride: int, depth: int, bh: int,
                                   bn: int, wo: int, dx: int, mode: str,
                                   v_lsb: float, max_count: int):
    """General stride, manual pipeline: rows_hbm is the (k, mh, Wband, C)
    kernel-row band stack in HBM; band (ki, mi) streams into the x ring
    and the k sliding windows are sliced out of the VMEM-resident slot."""
    out_ref, raw_ref = (refs[0], refs[1]) if len(refs) == 6 else (refs[0], None)
    xbuf, wbuf, xsem, wsem = refs[-4:]
    mi, ni = pl.program_id(0), pl.program_id(1)
    c = rows_hbm.shape[-1]

    def x_dma(slot, ki):
        return pltpu.make_async_copy(
            rows_hbm.at[ki, pl.ds(mi * bh, bh)], xbuf.at[slot],
            xsem.at[slot])

    def w_dma(slot, ki):
        return pltpu.make_async_copy(
            wmix_hbm.at[ki, :, pl.ds(ni * bn, bn)], wbuf.at[slot],
            wsem.at[slot])

    def x_tile_2d(slot):
        band = xbuf[slot]  # (bh, Wband, C), resident
        parts = []
        for dw in range(k):
            win = band[:, dw : dw + wo * stride, :]
            parts.append(win.reshape(bh, wo, stride, c)[:, :, 0, :])
        x = jnp.stack(parts, axis=2)  # (bh, Wo, k, C)
        return x.reshape(bh * wo, k * c)

    _pipelined_body(x_tile_2d, wbuf, shift_ref, out_ref, raw_ref, k=k,
                    depth=depth, dx=dx, mode=mode, v_lsb=v_lsb,
                    max_count=max_count, x_dma=x_dma, w_dma=w_dma)





def mosaic_conv_error(kernel: int, stride: int, channels: int,
                      pipeline_depth: int = 0) -> str | None:
    """Why the compiled (Mosaic) TPU lowering rejects this conv, or None.

    Interpret mode runs every path; Mosaic refuses two of them:

    * ``stride != kernel`` — the general path gathers its k sliding
      windows with a ``(bh, Wo, k, C) → (bh·Wo, k·C)`` in-register reshape
      ("infer-vector-layout: unsupported shape cast");
    * ``pipeline_depth >= 2`` with ``k·C`` off the 128-lane quantum — the
      DMA ring slices ``(bh, Wo, k·C)`` tiles out of HBM, and a DMA slice's
      lane dim must be a multiple of 128 ("Slice shape along dimension 3
      must be aligned to tiling (128)"); ``k·C`` is 15 at the paper's
      geometry.
    """
    if stride != kernel:
        return (f"stride {stride} != kernel {kernel}: the general strided "
                "P2M conv does not lower with Mosaic (its window gather is "
                "an unsupported in-register reshape); only stride == kernel "
                "compiles for TPU — use impl='fused' for other strides")
    kc = kernel * channels
    if pipeline_depth >= 2 and kc % 128:
        return (f"pipeline_depth {pipeline_depth} needs kernel*channels "
                f"(= {kc}) to be a multiple of 128: the DMA ring's HBM tile "
                "slices must be lane-aligned; use pipeline_depth=0")
    return None


def default_conv_blocks(b: int, ho: int, wo: int, n: int,
                        kc_dx: int) -> tuple[int, int]:
    """(block_h, block_n) heuristic: bh·Wo ≈ 2048 rows per tile, full-N
    blocks up to 128 — see DESIGN.md §3.3 for the VMEM budget math."""
    bh = max(1, min(b * ho, max(1, 2048 // max(wo, 1))))
    bn = min(128, ceil_to(n, 128))
    return bh, bn


def image_view(images, kernel: int, rows: int):
    """The ``stride == kernel`` im2col matrix as a view of the cropped
    images, ``(B·Ho, k, Wo, k·C)``, zero-padded to ``rows`` rows (§3.1 of
    DESIGN.md).  The forward kernel reads it, and the dW kernel reads the
    forward's copy (`backward.p2m_bwd_dw_pallas`)."""
    b, h, w_dim, c = images.shape
    k = kernel
    ho, wo = conv_out_spatial(h, k, k), conv_out_spatial(w_dim, k, k)
    a = images[:, : ho * k, : wo * k, :].reshape(b * ho, k, wo, k * c)
    return jnp.pad(a, ((0, rows - b * ho), (0, 0), (0, 0), (0, 0)))


@functools.partial(
    jax.jit,
    static_argnames=("kernel", "stride", "coeffs", "mode", "v_lsb",
                     "max_count", "block_h", "block_n", "want_raw",
                     "want_view", "interpret", "pipeline_depth"),
)
def p2m_conv_pallas(
    images,
    w,
    shift,
    *,
    kernel: int,
    stride: int,
    coeffs: tuple,
    mode: str = "relu",
    v_lsb: float = 1.0 / 255.0,
    max_count: int = 255,
    block_h: int | None = None,
    block_n: int | None = None,
    want_raw: bool = False,
    want_view: bool = False,
    interpret: bool = False,
    pipeline_depth: int = 0,
):
    """Fused P²M conv: NHWC images in, (B, Ho, Wo, N) activations out.

    images: (B, H, W, C) in [0, 1]; w: (k·k·C, N) signed flat weights with
    (kh, kw, C) fastest-varying K order (the `extract_patches` layout);
    shift: (N,) BN counter pre-load in volts.

    ``want_raw=True`` additionally returns the pre-epilogue accumulation
    (the training residual for the backward mask — see `backward.py`),
    and ``want_view=True`` (``stride == kernel`` only) the image view the
    kernel read (`image_view`: the dW kernel's operand), after them.

    ``pipeline_depth``: 0 uses the grid-path kernels (Pallas's automatic
    pipeline); ≥ 2 switches to the manual double-buffered kernels, which
    stream the next ``depth-1`` input/weight kernel-row tiles HBM→VMEM
    while the current tile is on the MXU (DESIGN.md §3.5) — an autotuner
    axis (`tune.py`).  Outputs are bitwise-identical either way.

    Compiled (``interpret=False``), a geometry Mosaic cannot lower raises
    ``ValueError`` naming the cause (`mosaic_conv_error`): ``stride !=
    kernel``, and the ring wherever ``k·C`` is off the 128-lane quantum.

    VMEM per step (fp32 words): x-tile ``bh·Wo·dx·kC`` (power concat) +
    W̃-tile ``dx·kC·bn`` + acc/out ``2·bh·Wo·bn``.  At the paper geometry
    (Wo=112, kC=75, dx=3, bh=8, bn=128) that is ≈ 1.3 MB — double-buffered
    comfortably inside the ~16 MB v5e VMEM (DESIGN.md §3.3; the manual
    path charges ``depth ×`` the streamed tiles explicitly).
    """
    if pipeline_depth == 1 or pipeline_depth < 0:
        raise ValueError("pipeline_depth must be 0 (grid path) or >= 2 "
                         f"(double-buffered ring), got {pipeline_depth}")
    b, h, w_dim, c = images.shape
    k, s = kernel, stride
    if not interpret and (err := mosaic_conv_error(k, s, c, pipeline_depth)):
        raise ValueError(err)
    if want_view and s != k:
        raise ValueError(f"want_view needs stride == kernel, got stride "
                         f"{s}, kernel {k}")
    ho = conv_out_spatial(h, k, s)
    wo = conv_out_spatial(w_dim, k, s)
    kc = k * c
    kk = k * k * c
    assert w.shape[0] == kk, (w.shape, kk)
    n = w.shape[1]
    dx = len(coeffs[0])

    # Host-side (XLA) weight prep: O(dx·K·N), weight-sized.
    wmix = premix_weights(w, coeffs)  # (dx, K, N)
    # Per-kernel-row layout: (k, dx·kC, N), rows ordered (j, dw, c) to match
    # the kernel's power-concat column order.
    wmix = wmix.reshape(dx, k, kc, n).transpose(1, 0, 2, 3).reshape(
        k, dx * kc, n)

    bh_default, bn_default = default_conv_blocks(b, ho, wo, n, dx * kc)
    bh = min(block_h or bh_default, b * ho)
    bn = min(block_n or bn_default, ceil_to(n, 128))

    mh = b * ho
    mh_pad = ceil_to(mh, bh)
    n_pad = ceil_to(n, bn)

    wmix = jnp.pad(wmix, ((0, 0), (0, 0), (0, n_pad - n)))
    sp = jnp.pad(jnp.asarray(shift, jnp.float32), (0, n_pad - n)).reshape(
        1, n_pad)

    common = dict(mode=mode, v_lsb=v_lsb, max_count=max_count)
    pipelined = pipeline_depth >= 2
    if s == k:
        # Zero-copy implicit im2col: the grid's k-dimension walks kernel
        # rows of the image view.
        x_arr = image_view(images, k, mh_pad)
        if pipelined:
            kernel_fn = functools.partial(
                _conv_kernel_fast_pipelined, k=k, depth=pipeline_depth,
                bh=bh, bn=bn, wo=wo, kc=kc, dx=dx, **common)
            x_tile_shape = (bh, wo, kc)
        else:
            kernel_fn = functools.partial(_conv_kernel_fast, k=k, dx=dx,
                                          **common)
            x_spec = pl.BlockSpec((bh, 1, wo, kc),
                                  lambda mi, ni, ki: (mi, ki, 0, 0))
    else:
        # Kernel-row band stack: (k, B·Ho, Wpad, C) — ≤ k/s × the input.
        rows = jnp.stack(
            [images[:, dh : dh + (ho - 1) * s + 1 : s, :, :]
             for dh in range(k)],
            axis=0,
        ).reshape(k, mh, w_dim, c)
        w_band = wo * s + k  # every dw window slice stays in-bounds
        x_arr = jnp.pad(rows, ((0, 0), (0, mh_pad - mh),
                               (0, w_band - w_dim), (0, 0)))
        if pipelined:
            kernel_fn = functools.partial(
                _conv_kernel_general_pipelined, k=k, stride=s,
                depth=pipeline_depth, bh=bh, bn=bn, wo=wo, dx=dx, **common)
            x_tile_shape = (bh, w_band, c)
        else:
            kernel_fn = functools.partial(_conv_kernel_general, k=k,
                                          stride=s, wo=wo, dx=dx, **common)
            x_spec = pl.BlockSpec((1, bh, w_band, c),
                                  lambda mi, ni, ki: (ki, mi, 0, 0))

    if pipelined:
        # 2-D grid: the kernel-row loop (and its HBM→VMEM streaming) lives
        # inside the kernel as an explicit depth-slot ring (DESIGN.md §3.5).
        nbuf = min(pipeline_depth, k)
        grid = (mh_pad // bh, n_pad // bn)
        out_shapes = [jax.ShapeDtypeStruct((mh_pad, wo, n_pad), jnp.float32)]
        out_specs = [pl.BlockSpec((bh, wo, bn), lambda mi, ni: (mi, 0, ni))]
        if want_raw:
            out_shapes.append(jax.ShapeDtypeStruct((mh_pad, wo, n_pad),
                                                   jnp.float32))
            out_specs.append(
                pl.BlockSpec((bh, wo, bn), lambda mi, ni: (mi, 0, ni)))
        outs = pl.pallas_call(
            kernel_fn,
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, bn), lambda mi, ni: (0, ni)),
            ],
            out_specs=out_specs,
            out_shape=out_shapes,
            scratch_shapes=[
                pltpu.VMEM((nbuf,) + x_tile_shape, jnp.float32),
                pltpu.VMEM((nbuf, dx * kc, bn), jnp.float32),
                pltpu.SemaphoreType.DMA((nbuf,)),
                pltpu.SemaphoreType.DMA((nbuf,)),
            ],
            interpret=interpret,
        )(x_arr, wmix, sp)
    else:
        grid = (mh_pad // bh, n_pad // bn, k)
        out_shapes = [jax.ShapeDtypeStruct((mh_pad, wo, n_pad), jnp.float32)]
        out_specs = [pl.BlockSpec((bh, wo, bn),
                                  lambda mi, ni, ki: (mi, 0, ni))]
        if want_raw:
            out_shapes.append(jax.ShapeDtypeStruct((mh_pad, wo, n_pad),
                                                   jnp.float32))
            out_specs.append(
                pl.BlockSpec((bh, wo, bn), lambda mi, ni, ki: (mi, 0, ni)))
        outs = pl.pallas_call(
            kernel_fn,
            grid=grid,
            in_specs=[
                x_spec,
                pl.BlockSpec((1, dx * kc, bn), lambda mi, ni, ki: (ki, 0, ni)),
                pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
            ],
            out_specs=out_specs,
            out_shape=out_shapes,
            scratch_shapes=[pltpu.VMEM((bh * wo, bn), jnp.float32)],
            interpret=interpret,
        )(x_arr, wmix, sp)

    def _unpad(o):
        return o[:mh, :, :n].reshape(b, ho, wo, n)

    res = [_unpad(o) for o in outs]
    if want_view:
        res.append(x_arr)
    return tuple(res) if len(res) > 1 else res[0]


def im2col_slices(images, kernel: int, stride: int):
    """Per-kernel-row im2col slices, without materializing the patch tensor.

    Yields k arrays of shape (M, k·C) — each a (strided-)sliced view the
    compiler can fuse; at ``stride == kernel`` they are pure reshapes.
    """
    b, h, w_dim, c = images.shape
    k, s = kernel, stride
    ho = conv_out_spatial(h, k, s)
    wo = conv_out_spatial(w_dim, k, s)
    m = b * ho * wo
    if s == k:
        a = image_view(images, k, b * ho)
        for dh in range(k):
            yield a[:, dh].reshape(m, k * c)
        return
    # General stride: same row-band structure as the Pallas kernel — one
    # strided row gather per dh, then contiguous slice + reshape-subsample
    # for the k in-row windows (cheaper than k strided gathers).
    w_band = wo * s + k
    for dh in range(k):
        rows = images[:, dh : dh + (ho - 1) * s + 1 : s, :, :]  # (B,Ho,W,C)
        rows = jnp.pad(rows, ((0, 0), (0, 0), (0, w_band - w_dim), (0, 0)))
        cols = [rows[:, :, dw : dw + wo * s, :]
                .reshape(b, ho, wo, s, c)[:, :, :, 0, :]
                for dw in range(k)]
        x = jnp.stack(cols, axis=3)  # (B, Ho, Wo, k, C)
        yield x.reshape(m, k * c)


def im2col_matrix(images, kernel: int, stride: int):
    """Materialized (M, k·k·C) im2col matrix, (kh, kw, C) fastest-varying.

    Built from `im2col_slices`, so at ``stride == kernel`` the only data
    movement is the final concat.  Used by the backward pass (which needs
    X for the power factors) and as a fallback patch extractor; the fused
    forward never calls this.
    """
    return jnp.concatenate(list(im2col_slices(images, kernel, stride)),
                           axis=1)


def p2m_conv_raw_jnp(images, w, *, kernel: int, stride: int, coeffs):
    """Pre-epilogue fused conv accumulation in XLA (differentiable).

    Same basis-premix decomposition as the Pallas kernel — one
    ``(M, dx·kC) @ (dx·kC, N)`` contraction per kernel row, never a
    ``(M, k²C)`` patch tensor.
    """
    k, c = kernel, images.shape[-1]
    kc = k * c
    n = w.shape[1]
    dx = len(coeffs[0])
    wmix = premix_weights(w, coeffs)  # (dx, K, N)
    wmix = wmix.reshape(dx, k, kc, n).transpose(1, 0, 2, 3).reshape(
        k, dx * kc, n)
    raw = None
    for dh, x in enumerate(im2col_slices(images, kernel, stride)):
        xcat = _power_concat(x.astype(jnp.float32), dx)
        term = xcat @ wmix[dh]
        raw = term if raw is None else raw + term
    return raw  # (M, N)


def p2m_conv_jnp(images, w, shift, *, kernel: int, stride: int, coeffs,
                 mode: str = "relu", v_lsb: float = 1.0 / 255.0,
                 max_count: int = 255):
    """XLA fused conv: same contract as `p2m_conv_pallas`, differentiable."""
    b, h, w_dim, _ = images.shape
    ho = conv_out_spatial(h, kernel, stride)
    wo = conv_out_spatial(w_dim, kernel, stride)
    raw = p2m_conv_raw_jnp(images, w, kernel=kernel, stride=stride,
                           coeffs=coeffs)
    shift = jnp.asarray(shift, jnp.float32)
    out = _epilogue_values(raw, shift, mode=mode, v_lsb=v_lsb,
                           max_count=max_count)
    return out.reshape(b, ho, wo, w.shape[1])
