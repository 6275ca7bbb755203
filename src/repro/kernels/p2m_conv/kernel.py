"""Pallas TPU kernel for the P²M non-ideal convolution (basis-decomposed).

TPU-native formulation (DESIGN.md §2): with the pixel non-ideality fit as
``g(w,x) = Σ_{i,j≥1} a_ij w^i x^j``, the P²M im2col product

    out[m,n] = Σ_k sign(W[k,n]) · g(|W[k,n]|, X[m,k])

factorizes into ``Σ_ij a_ij · (X^∘j) @ (sign(W) ⊙ |W|^∘i)`` — dw·dx MXU
matmuls over elementwise powers.  The kernel tiles (M, N, K) into VMEM
blocks, computes the power expansion *in VMEM* (the powered operands are
never materialized in HBM), accumulates in an fp32 VMEM scratch across the
K grid dimension, and applies the CDS/ADC epilogue (BN shift pre-load,
ReLU clamp at the counter, optional integer-exact quantization) on the
final K step.

Zero padding is exact: every basis term carries a ``w^i x^j`` factor with
i, j ≥ 1, so padded rows/cols contribute exactly 0 to the accumulation.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.p2m_conv.conv import _epilogue_values, ceil_to


def _p2m_kernel(
    x_ref,        # (bm, bk) activation patch tile
    w_ref,        # (bk, bn) signed weight tile
    shift_ref,    # (1, bn) BN shift term (volts)
    *refs,        # out (bm, bn) [, raw (bm, bn)], then acc scratch
    coeffs: Sequence[Sequence[float]],
    nk: int,
    mode: str,
    v_lsb: float,
    max_count: int,
):
    if len(refs) == 3:
        out_ref, raw_ref, acc_ref = refs
    else:
        (out_ref, acc_ref), raw_ref = refs, None
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # powers and sign in fp32: a v5e's VPU has no bf16 compare (Mosaic:
    # "Target does not support this comparison")
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    sgn = jnp.sign(w)
    aw = jnp.abs(w)

    dw = len(coeffs)
    dx = len(coeffs[0])
    acc = acc_ref[...]
    # Incremental powers: wp_i = |w|^i (sign applied once per dot), xp_j = x^j.
    wp = aw
    for i in range(1, dw + 1):
        wsig = sgn * wp  # sign(w)·|w|^i
        xp = x
        for j in range(1, dx + 1):
            a_ij = coeffs[i - 1][j - 1]
            if a_ij != 0.0:
                acc = acc + a_ij * jax.lax.dot_general(
                    xp,
                    wsig,
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            if j < dx:
                xp = xp * x
        if i < dw:
            wp = wp * aw
    acc_ref[...] = acc

    @pl.when(k_idx == nk - 1)
    def _epilogue():
        raw = acc_ref[...]
        shift = shift_ref[...].astype(jnp.float32)  # (1, bn), broadcasts
        out = _epilogue_values(raw, shift, mode=mode, v_lsb=v_lsb,
                               max_count=max_count)
        out_ref[...] = out.astype(out_ref.dtype)
        if raw_ref is not None:
            raw_ref[...] = raw


@functools.partial(
    jax.jit,
    static_argnames=(
        "coeffs",
        "mode",
        "v_lsb",
        "max_count",
        "block_m",
        "block_n",
        "block_k",
        "want_raw",
        "interpret",
    ),
)
def p2m_matmul_pallas(
    x,
    w,
    shift,
    *,
    coeffs: tuple,
    mode: str = "relu",
    v_lsb: float = 1.0 / 255.0,
    max_count: int = 255,
    block_m: int = 256,
    block_n: int = 128,
    block_k: int = 128,
    want_raw: bool = False,
    interpret: bool = False,
):
    """Tiled Pallas forward. x: (M, K), w: (K, N), shift: (N,) → (M, N) f32.

    ``want_raw=True`` additionally returns the pre-epilogue accumulation
    (saved as the training residual for the backward mask, `backward.py`).

    VMEM budget per step (fp32 equivalents): x tile bm·bk + w tile bk·bn +
    acc bm·bn + out bm·bn ≈ (256·128 + 128·128 + 2·256·128)·4 B ≈ 0.6 MB —
    comfortably inside the ~16 MB v5e VMEM, leaving room for the pipeline's
    double buffering.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    bm = min(block_m, ceil_to(m, 8))
    bn = min(block_n, ceil_to(n, 128))
    bk = min(block_k, ceil_to(k, 128))
    mp, np_, kp = ceil_to(m, bm), ceil_to(n, bn), ceil_to(k, bk)

    xp = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    wp = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    sp = jnp.pad(jnp.asarray(shift, x.dtype), (0, np_ - n)).reshape(1, np_)

    nk = kp // bk
    grid = (mp // bm, np_ // bn, nk)

    kernel = functools.partial(
        _p2m_kernel,
        coeffs=coeffs,
        nk=nk,
        mode=mode,
        v_lsb=v_lsb,
        max_count=max_count,
    )
    out_specs = [pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni))]
    out_shapes = [jax.ShapeDtypeStruct((mp, np_), jnp.float32)]
    if want_raw:
        out_specs.append(pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)))
        out_shapes.append(jax.ShapeDtypeStruct((mp, np_), jnp.float32))
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xp, wp, sp)
    if want_raw:
        return outs[0][:m, :n], outs[1][:m, :n]
    return outs[0][:m, :n]
