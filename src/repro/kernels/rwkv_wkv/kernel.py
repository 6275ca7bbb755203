"""Pallas TPU kernel for the chunked RWKV-6 WKV scan (DESIGN.md §12.2).

Grid: ``(batch·head, n_chunks)`` with the chunk dimension innermost —
TPU grids execute row-major, so for each (b, h) the chunk steps run
sequentially and the matrix-valued (dk × dv) running state lives in a
VMEM scratch across them: loaded from HBM once at chunk 0, updated in
VMEM every step, written back once at the last chunk.  Per chunk the
body is three MXU matmuls (inter-chunk ``q @ S0``, the strictly-masked
intra-chunk ``(C × C) @ V``, and the rank-C state update ``kdᵀ @ V``)
plus a triangular-matmul cumsum — no lax.cumsum / iota-1D, which Mosaic
does not lower.

Padding is exact (see `ref.py`): the head dim is zero-padded to the
128-lane quantum and the sequence to a chunk multiple; padded positions
carry lw = 0 (identity decay) and r = k = v = 0, so they neither move
the state nor contribute output.  fp32 throughout (`preferred_element_type`
on every dot) — the exp(ΔL) range argument needs fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rwkv_wkv.ref import WKV_CHUNK

_LANES = 128  # TPU lane quantum: last dim of every block padded to this


def ceil_to(x: int, q: int) -> int:
    return -(-x // q) * q


def _dot(a, b, contract=((1,), (0,))):
    return jax.lax.dot_general(a, b, dimension_numbers=(contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, y_ref, sout_ref,
                s_scr, *, chunk: int, nc: int):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _load_state():
        s_scr[...] = s0_ref[0]

    rt, kt, vt, lwt = r_ref[0], k_ref[0], v_ref[0], lw_ref[0]  # (C, Dp)
    u = u_ref[0]  # (1, Dp)
    s0 = s_scr[...]  # (Dp, Dp) — running state, persists across chunks

    # Cumulative log decay via a lower-triangular ones matmul (Mosaic has
    # no cumsum primitive; iota must be ≥2D on TPU).
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril_incl = (si <= ti).astype(jnp.float32)
    cum = _dot(tril_incl, lwt)  # L_t (inclusive), (C, Dp)
    cum_prev = cum - lwt
    total = cum[chunk - 1:chunk, :]  # L_C along lanes, (1, Dp)
    # L_C along sublanes for the state decay, (Dp, 1): contraction-over-
    # tokens dot instead of a transpose.
    total_col = _dot(lwt, jnp.ones((chunk, 1), jnp.float32),
                     contract=((0,), (0,)))

    # inter-chunk: y_t += (r_t · exp(L_{t-1})) @ S0
    q = rt * jnp.exp(cum_prev)
    y = _dot(q, s0)
    # intra-chunk: scores[t,s] = Σ_d qd_t kd_s, strictly causal
    kd = kt * jnp.exp(total - cum)
    qd = rt * jnp.exp(cum_prev - total)
    scores = _dot(qd, kd, contract=((1,), (1,)))  # (C, C)
    scores = scores * (si < ti).astype(jnp.float32)
    y = y + _dot(scores, vt)
    # bonus diagonal
    diag = jnp.sum(rt * u * kt, axis=1, keepdims=True)  # (C, 1)
    y_ref[0] = y + diag * vt
    # state update: S_C = exp(L_C) ∘ S0 + kdᵀ @ V
    s_new = jnp.exp(total_col) * s0 + _dot(kd, vt, contract=((0,), (0,)))
    s_scr[...] = s_new

    @pl.when(c_idx == nc - 1)
    def _store_state():
        sout_ref[0] = s_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv_pallas(r, k, v, lw, u, state, *, chunk: int = WKV_CHUNK,
               interpret: bool = False):
    """Pallas chunked WKV forward.  r/k/v/lw (B,S,H,D); u (H,D);
    state (B,H,D,D) → (y (B,S,H,D), final state), all fp32."""
    b, s, h, d = r.shape
    bh = b * h
    f32 = lambda a: a.astype(jnp.float32)
    to_bh = lambda a: f32(a).transpose(0, 2, 1, 3).reshape(bh, s, d)
    sp, dp = ceil_to(s, chunk), ceil_to(d, _LANES)
    pad_seq = lambda a: jnp.pad(a, ((0, 0), (0, sp - s), (0, dp - d)))
    rr, kk, vv, ll = (pad_seq(to_bh(a)) for a in (r, k, v, lw))
    s0 = jnp.pad(f32(state).reshape(bh, d, d),
                 ((0, 0), (0, dp - d), (0, dp - d)))
    # u rides per-(b,h) so the grid's flat index needs no modulo: rows
    # repeat [u_0 … u_{H-1}] per batch, matching the (B,H) flatten order.
    # (B·H, 1, Dp) so the (1, Dp) block spans the array's last two dims —
    # a (1, Dp) block of a 2-D (B·H, Dp) array breaks the 8-sublane rule.
    uu = jnp.pad(jnp.tile(f32(u), (b, 1)), ((0, 0), (0, dp - d)))[:, None, :]

    nc = sp // chunk
    kernel = functools.partial(_wkv_kernel, chunk=chunk, nc=nc)
    y, s_out = pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, dp), lambda i, c: (i, c, 0)),  # r
            pl.BlockSpec((1, chunk, dp), lambda i, c: (i, c, 0)),  # k
            pl.BlockSpec((1, chunk, dp), lambda i, c: (i, c, 0)),  # v
            pl.BlockSpec((1, chunk, dp), lambda i, c: (i, c, 0)),  # lw
            pl.BlockSpec((1, 1, dp), lambda i, c: (i, 0, 0)),      # u
            pl.BlockSpec((1, dp, dp), lambda i, c: (i, 0, 0)),     # S_0
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dp), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, dp, dp), lambda i, c: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sp, dp), jnp.float32),
            jax.ShapeDtypeStruct((bh, dp, dp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dp, dp), jnp.float32)],
        interpret=interpret,
    )(rr, kk, vv, ll, uu, s0)

    y = y[:, :s, :d].reshape(b, h, s, d).transpose(0, 2, 1, 3)
    s_out = s_out[:, :d, :d].reshape(b, h, d, d)
    return y, s_out
