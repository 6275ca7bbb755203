"""Pallas backward kernels for the P²M basis sum (DESIGN.md §4).

The VJP of the premixed accumulation ``raw = Σ_j (X^∘j) @ W̃_j`` is itself
a short sum of matmuls against powered operands, so it reuses the same
(M, N, K) tiling machinery as the forward:

    dX = Σ_j j·X^∘(j-1) ⊙ (G @ W̃_jᵀ)          (one MXU dot per tile step)
    dW = Σ_{i,j} a_ij · i·|W|^∘(i-1) ⊙ T_j,   T_j = (X^∘j)ᵀ @ G

Both kernels accumulate the *matmul* part across the contracted grid
dimension in a VMEM scratch laid out as ``dx`` stacked blocks, and apply
the powered-operand elementwise factors once, in the epilogue — the
powered operands are never materialized in HBM.

The epilogue mask (ReLU/saturation clamp, STE for quant) is elementwise
and cheap, so it is applied to ``g`` by the caller (`ops.py`) in XLA
where it fuses for free; these kernels differentiate the raw basis sum.

`p2m_backward_jnp` is the identical closed form in XLA ops — the CPU/GPU
fallback registered in the `custom_vjp` off-TPU.  Either way, training no
longer pays the old fallback of re-tracing `jax.vjp` through the full
dw·dx forward expansion.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.p2m_conv.conv import _power_concat, ceil_to, premix_weights


# ---------------------------------------------------------------------------
# dX kernel: dX = Σ_j j·X^(j-1) ∘ (G @ W̃_jᵀ), tiled (M, K) with N contracted.
# ---------------------------------------------------------------------------


def _dx_kernel(g_ref, wt_ref, x_ref, out_ref, acc_ref, *, dx: int, nn: int):
    ni = pl.program_id(2)

    @pl.when(ni == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = g_ref[...].astype(jnp.float32)                      # (bm, bn)
    wt = wt_ref[...].reshape(wt_ref.shape[0], -1)           # (bn, dx·bk)
    acc_ref[...] += jax.lax.dot_general(
        g, wt.astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ni == nn - 1)
    def _epilogue():
        x = x_ref[...].astype(jnp.float32)                  # (bm, bk)
        bk = x.shape[1]
        acc = acc_ref[...]
        total = jnp.zeros_like(x)
        xpow = jnp.ones_like(x)                              # x^(j-1)
        for j in range(1, dx + 1):
            total = total + float(j) * xpow * acc[:, (j - 1) * bk : j * bk]
            if j < dx:
                xpow = xpow * x
        out_ref[...] = total


@functools.partial(
    jax.jit,
    static_argnames=("coeffs", "block_m", "block_n", "block_k", "interpret"),
)
def p2m_bwd_dx_pallas(g, w, x, *, coeffs: tuple, block_m: int = 256,
                      block_n: int = 128, block_k: int = 128,
                      interpret: bool = False):
    """dX of the raw basis sum. g: (M, N) cotangent (epilogue mask already
    applied), w: (K, N), x: (M, K) → (M, K) float32."""
    m, n = g.shape
    k = w.shape[0]
    dx = len(coeffs[0])
    bm = min(block_m, ceil_to(m, 8))
    bn = min(block_n, ceil_to(n, 128))
    bk = min(block_k, ceil_to(k, 128))
    mp, np_, kp = ceil_to(m, bm), ceil_to(n, bn), ceil_to(k, bk)

    # (N, dx, K): blocks reshape to the (bn, dx·bk) premixed-transpose tile.
    wt = premix_weights(w, coeffs).transpose(2, 0, 1)
    wt = jnp.pad(wt, ((0, np_ - n), (0, 0), (0, kp - k)))
    gp = jnp.pad(g.astype(jnp.float32), ((0, mp - m), (0, np_ - n)))
    xp = jnp.pad(x.astype(jnp.float32), ((0, mp - m), (0, kp - k)))

    nn = np_ // bn
    grid = (mp // bm, kp // bk, nn)
    out = pl.pallas_call(
        functools.partial(_dx_kernel, dx=dx, nn=nn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda mi, ki, ni: (mi, ni)),
            pl.BlockSpec((bn, dx, bk), lambda mi, ki, ni: (ni, 0, ki)),
            pl.BlockSpec((bm, bk), lambda mi, ki, ni: (mi, ki)),
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda mi, ki, ni: (mi, ki)),
        out_shape=jax.ShapeDtypeStruct((mp, kp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, dx * bk), jnp.float32)],
        interpret=interpret,
    )(gp, wt, xp)
    return out[:m, :k]


# ---------------------------------------------------------------------------
# dW kernel: T_j = (X^∘j)ᵀ @ G accumulated over M; epilogue folds a_ij·i·|W|^(i-1).
# ---------------------------------------------------------------------------


def _dw_epilogue(acc, w, coeffs):
    """Σ_i a_ij · i·|W|^∘(i-1) ⊙ T_j: ``acc`` is the ``(dx·bk, bn)`` stack
    of the T_j, ``w`` the matching ``(bk, bn)`` weights."""
    aw = jnp.abs(w.astype(jnp.float32))
    bk = aw.shape[0]
    total = jnp.zeros_like(aw)
    wpow = jnp.ones_like(aw)                                 # |w|^(i-1)
    for i in range(1, len(coeffs) + 1):
        u_i = jnp.zeros_like(aw)
        for j in range(1, len(coeffs[0]) + 1):
            a_ij = float(coeffs[i - 1][j - 1])
            if a_ij != 0.0:
                u_i = u_i + a_ij * acc[(j - 1) * bk : j * bk, :]
        total = total + float(i) * wpow * u_i
        if i < len(coeffs):
            wpow = wpow * aw
    return total


def _powers_t_dot(x, g, dx: int):
    """[X, X∘X, …]ᵀ @ G for one tile: (rows, bk), (rows, bn) →
    (dx·bk, bn)."""
    return jax.lax.dot_general(
        _power_concat(x.astype(jnp.float32), dx), g.astype(jnp.float32),
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _dw_kernel(x_ref, g_ref, w_ref, out_ref, acc_ref, *, coeffs, nm: int):
    mi = pl.program_id(2)

    @pl.when(mi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _powers_t_dot(x_ref[...], g_ref[...], len(coeffs[0]))

    @pl.when(mi == nm - 1)
    def _epilogue():
        out_ref[...] = _dw_epilogue(acc_ref[...], w_ref[...], coeffs)


def _dw_view_kernel(x_ref, g_ref, w_ref, out_ref, acc_ref, *, coeffs,
                    nm: int):
    """dW from the forward's image view: x_ref is the (bh, 1, Wo, kC)
    block of kernel row ``dh``, g_ref the (bh, Wo, N) cotangent rows of
    the same output pixels; acc_ref holds one T_j stack per kernel row."""
    mi, dh = pl.program_id(0), pl.program_id(1)
    k = acc_ref.shape[0]
    bh, _, wo, kc = x_ref.shape

    @pl.when((mi == 0) & (dh == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].reshape(bh * wo, kc)
    g = g_ref[...].reshape(bh * wo, g_ref.shape[-1])
    acc_ref[dh] += _powers_t_dot(x, g, len(coeffs[0]))

    @pl.when((mi == nm - 1) & (dh == k - 1))
    def _epilogue():
        for r in range(k):
            out_ref[r] = _dw_epilogue(acc_ref[r], w_ref[r], coeffs)


def _dw_from_view(g, w, view, *, coeffs, block_h, interpret):
    """dW with X read through the forward's (mh_pad, k, Wo, kC) view.

    The grid walks (row block, kernel row) over the first B·Ho = M / Wo
    rows of the view, ``block_h`` rows a step.  ``block_h`` divides B·Ho
    — by default the largest divisor up to 2048 / Wo rows, the forward
    kernel's tile height — so the grid never reaches the view's padded
    rows and nothing is padded."""
    _, k, wo, kc = view.shape
    m, n = g.shape
    mh = m // wo
    bh = block_h or next(d for d in range(min(mh, max(1, 2048 // wo)), 0, -1)
                         if mh % d == 0)
    if mh % bh:
        raise ValueError(f"block_h {bh} does not divide B·Ho = {mh}")
    nm = mh // bh
    out = pl.pallas_call(
        functools.partial(_dw_view_kernel, coeffs=coeffs, nm=nm),
        grid=(nm, k),
        in_specs=[
            pl.BlockSpec((bh, 1, wo, kc), lambda mi, dh: (mi, dh, 0, 0)),
            pl.BlockSpec((bh, wo, n), lambda mi, dh: (mi, 0, 0)),
            pl.BlockSpec((k, kc, n), lambda mi, dh: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((k, kc, n), lambda mi, dh: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, kc, n), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((k, len(coeffs[0]) * kc, n), jnp.float32)],
        interpret=interpret,
    )(view, g.astype(jnp.float32).reshape(mh, wo, n),
      w.astype(jnp.float32).reshape(k, kc, n))
    return out.reshape(k * kc, n)


@functools.partial(
    jax.jit,
    static_argnames=("coeffs", "block_m", "block_n", "block_k", "block_h",
                     "interpret"),
)
def p2m_bwd_dw_pallas(g, w, x, *, coeffs: tuple, block_m: int = 256,
                      block_n: int = 128, block_k: int = 128,
                      block_h: int | None = None, interpret: bool = False):
    """dW of the raw basis sum. g: (M, N) masked cotangent, w: (K, N) →
    (K, N) float32.

    ``x`` is the patch matrix (M, K), tiled by ``block_m/n/k``, or — at
    ``stride == kernel`` — the forward's image view (mh_pad, k, Wo, k·C)
    (`conv.image_view`), whose first M / Wo rows are the output pixel
    rows; ``block_h`` of them, a divisor of M / Wo, per grid step
    (`_dw_from_view`)."""
    if x.ndim == 4:
        return _dw_from_view(g, w, x, coeffs=coeffs, block_h=block_h,
                             interpret=interpret)
    m, n = g.shape
    k = w.shape[0]
    dx = len(coeffs[0])
    bm = min(block_m, ceil_to(m, 8))
    bn = min(block_n, ceil_to(n, 128))
    bk = min(block_k, ceil_to(k, 128))
    mp, np_, kp = ceil_to(m, bm), ceil_to(n, bn), ceil_to(k, bk)

    gp = jnp.pad(g.astype(jnp.float32), ((0, mp - m), (0, np_ - n)))
    xp = jnp.pad(x.astype(jnp.float32), ((0, mp - m), (0, kp - k)))
    wp = jnp.pad(w.astype(jnp.float32), ((0, kp - k), (0, np_ - n)))

    nm = mp // bm
    grid = (kp // bk, np_ // bn, nm)
    out = pl.pallas_call(
        functools.partial(_dw_kernel, coeffs=coeffs, nm=nm),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda ki, ni, mi: (mi, ki)),
            pl.BlockSpec((bm, bn), lambda ki, ni, mi: (mi, ni)),
            pl.BlockSpec((bk, bn), lambda ki, ni, mi: (ki, ni)),
        ],
        out_specs=pl.BlockSpec((bk, bn), lambda ki, ni, mi: (ki, ni)),
        out_shape=jax.ShapeDtypeStruct((kp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((dx * bk, bn), jnp.float32)],
        interpret=interpret,
    )(xp, gp, wp)
    return out[:k, :n]


# ---------------------------------------------------------------------------
# Closed-form XLA fallback (identical math, for CPU/GPU custom_vjp).
# ---------------------------------------------------------------------------


def p2m_backward_jnp(g, w, x, coeffs):
    """Closed-form (dX, dW) of the raw basis sum in XLA ops.

    Same premixed decomposition as the Pallas kernels: dx matmuls total
    instead of re-differentiating the dw·dx forward expansion.
    """
    g = g.astype(jnp.float32)
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    dw = len(coeffs)
    dx = len(coeffs[0])
    wmix = premix_weights(w, coeffs)                         # (dx, K, N)

    gx = jnp.zeros_like(x)
    xpow = jnp.ones_like(x)
    t_list = []
    xp = x
    for j in range(1, dx + 1):
        gx = gx + float(j) * xpow * (g @ wmix[j - 1].T)
        t_list.append(xp.T @ g)                              # T_j (K, N)
        if j < dx:
            xpow = xpow * x
            xp = xp * x

    aw = jnp.abs(w)
    gw = jnp.zeros_like(w)
    wpow = jnp.ones_like(aw)
    for i in range(1, dw + 1):
        u_i = jnp.zeros_like(w)
        for j in range(1, dx + 1):
            a_ij = float(coeffs[i - 1][j - 1])
            if a_ij != 0.0:
                u_i = u_i + a_ij * t_list[j - 1]
        gw = gw + float(i) * wpow * u_i
        if i < dw:
            wpow = wpow * aw
    return gx, gw


def epilogue_mask(raw, shift, *, mode: str, full_scale: float):
    """d out / d (raw) of the CDS/ADC epilogue, elementwise.

    "raw" passes gradients through; "relu" masks the clamp's saturated
    regions; "quant" uses the straight-through estimator — the gradient of
    the soft-clipped ("relu") path, the convention used throughout.
    """
    if mode == "raw":
        return jnp.ones_like(raw)
    v = raw + jnp.asarray(shift, jnp.float32)
    return ((v > 0.0) & (v < full_scale)).astype(jnp.float32)


def p2m_backward(g, w, x, coeffs, *, use_pallas: bool, interpret: bool = False,
                 blocks: tuple[int, int, int] | None = None, dw_view=None):
    """Dispatch (dX, dW): Pallas kernels on TPU (or forced interpret),
    closed-form XLA otherwise.  ``dw_view``, the forward's image view
    (Pallas only), is the dW kernel's operand in place of ``x``."""
    if use_pallas:
        bm, bn, bk = blocks or (256, 128, 128)
        gx = p2m_bwd_dx_pallas(g, w, x, coeffs=coeffs, block_m=bm,
                               block_n=bn, block_k=bk, interpret=interpret)
        gw = p2m_bwd_dw_pallas(g, w, x if dw_view is None else dw_view,
                               coeffs=coeffs, block_m=bm, block_n=bn,
                               block_k=bk, interpret=interpret)
        return gx, gw
    return p2m_backward_jnp(g, w, x, coeffs)
