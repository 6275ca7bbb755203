"""Block-size autotuner for the P²M kernels (DESIGN.md §5).

Picks ``(block_m, block_n, block_k)`` for `p2m_matmul_pallas` and
``(block_h, block_n, pipeline_depth)`` for `p2m_conv_pallas` by
enumerating the legal candidates under the VMEM budget and timing each
once on synthetic data.  ``pipeline_depth`` is the manual double-buffer
ring of DESIGN.md §3.5: depth 0 lets the automatic grid pipeline stream
(budget charges the implicit ×2 against half VMEM), depth ≥ 2 allocates
``depth ×`` explicit input+weight slot buffers, so the budget charges
those buffers directly (DESIGN.md §3.3).

Cache semantics: winners are memoized **per signature** — the problem
shape, the coefficient table (its nonzero pattern changes the kernel's
instruction mix), the epilogue mode, the **backend** the timing ran on,
and (for conv) the depth axis swept.  A signature is timed at most
once per process; every later call is a dict lookup, so the tuner adds
one-off JIT-warmup-style latency, never steady-state cost.  The cache can
be exported as JSON (`cache_dump`) so benchmark runs can record winners.

Timing happens only in an **eager** call on concrete arrays (as
`benchmarks/hillclimb.py` makes them).  A call made while JAX traces
(jit, grad, the serving and training programs) serves the cached winner
or the static default and counts the default as
``autotune.traced_default``, and `autotune` itself refuses to run there
— a kernel launched under a trace only stages, so timing it would time
tracing.  Candidates the compiled (Mosaic) lowering rejects for the
shape (`conv.mosaic_conv_error`) are never on the menu, and a candidate
that fails while being timed is recorded in the decision and never
served.

Autotuning is **off by default off-TPU** (timing interpret-mode kernels
would measure the Python interpreter): `get_*_blocks` then returns the
static heuristic defaults instantly, and emits a one-time structured log
per (kind, backend) naming the backend and the defaults served.  Set
``REPRO_P2M_AUTOTUNE=1`` (or pass ``enable=True``) to force tuning —
tests do, with toy shapes, to exercise the machinery.
"""
from __future__ import annotations

import json
import logging
import os
import time
from typing import Callable, Iterable

import jax
import numpy as np

from repro.obs.log import structured
from repro.obs.metrics import default_registry

logger = logging.getLogger(__name__)

# Half of a v5e core's ~16 MB VMEM, leaving the other half for the
# pipeline's double buffering (DESIGN.md §3.3).
VMEM_BUDGET_BYTES = 8 * 2**20

# Pipeline depths swept for the conv kernel: 0 = automatic grid pipeline,
# ≥2 = explicit DMA ring with that many slot buffers (depth 1 would stall
# every step and is rejected by the kernel).
CONV_PIPELINE_DEPTHS: tuple[int, ...] = (0, 2, 3)

_CACHE: dict[tuple, dict] = {}

# One-time "autotune disabled, serving defaults" notices, per (kind, backend).
_DISABLED_LOGGED: set[tuple[str, str]] = set()


def _log_disabled_defaults(kind: str, backend: str, default) -> None:
    """One-shot notice (per kind × backend) that the static defaults are
    being served because autotuning is disabled on this backend — routed
    through the stack's structured-logging helper (`obs.log`, DESIGN.md
    §13.4) so the record shares the one machine-parseable schema.  Every
    disabled-default *serve* also counts into the metrics registry
    (``autotune.disabled_default``), one-shot or not."""
    default_registry().counter("autotune.disabled_default").inc()
    token = (kind, backend)
    if token in _DISABLED_LOGGED:
        return
    _DISABLED_LOGGED.add(token)
    structured(
        logger, "p2m_autotune_disabled_defaults",
        kind=kind,
        backend=backend,
        default=list(default),
        hint="set REPRO_P2M_AUTOTUNE=1 or pass enable=True to tune",
    )


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def enabled(enable: bool | None = None) -> bool:
    if enable is not None:
        return enable
    if os.environ.get("REPRO_P2M_AUTOTUNE", "") == "1":
        return True
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Candidate enumeration under the VMEM budget
# ---------------------------------------------------------------------------


def matmul_vmem_bytes(bm: int, bn: int, bk: int, dx: int = 3) -> int:
    """fp32 working set of one `p2m_matmul_pallas` grid step: x tile +
    w tile + acc scratch + out tile (+ the dx-power temps live in
    registers/VPU, bounded by the x tile)."""
    words = bm * bk * dx + bk * bn + 2 * bm * bn
    return 4 * words


def conv_vmem_bytes(bh: int, wo: int, kc: int, bn: int, dx: int = 3,
                    depth: int = 0) -> int:
    """fp32 working set of one `p2m_conv_pallas` grid step (power concat
    dominates the activation side).

    ``depth == 0``: the automatic grid pipeline — one streamed x tile and
    one streamed wmix tile (the implicit ×2 double buffer is what the
    half-VMEM budget leaves room for, DESIGN.md §3.3).  ``depth >= 2``:
    the explicit DMA ring holds ``depth`` raw input-tile slots plus
    ``depth`` premixed-weight slots in VMEM scratch, and those are charged
    directly; the power-concat temp and acc/out tiles ride on top."""
    if depth >= 2:
        streamed = depth * (bh * wo * kc + dx * kc * bn)
    else:
        streamed = bh * wo * kc + dx * kc * bn
    words = streamed + bh * wo * kc * dx + 2 * bh * wo * bn
    return 4 * words


def matmul_candidates(m: int, k: int, n: int, *, dx: int = 3,
                      budget: int = VMEM_BUDGET_BYTES
                      ) -> list[tuple[int, int, int]]:
    """Legal (bm, bn, bk) grid-block shapes, deduped after clamping to the
    (tile-quantum-padded) problem dims."""
    out = []
    seen = set()
    for bm in (128, 256, 512, 1024):
        for bn in (128, 256):
            for bk in (128, 256, 512):
                cand = (min(bm, _ceil_to(m, 8)), min(bn, _ceil_to(n, 128)),
                        min(bk, _ceil_to(k, 128)))
                if cand in seen:
                    continue
                seen.add(cand)
                if matmul_vmem_bytes(*cand, dx=dx) <= budget:
                    out.append(cand)
    return out


def conv_candidates(b: int, ho: int, wo: int, n: int, kc: int, *, dx: int = 3,
                    depths: tuple[int, ...] = CONV_PIPELINE_DEPTHS,
                    budget: int = VMEM_BUDGET_BYTES
                    ) -> list[tuple[int, int, int]]:
    """Legal (block_h, block_n, pipeline_depth) for the fused conv kernel.
    Depth ≥ 2 candidates charge ``depth ×`` explicit slot buffers against
    the budget, so deep rings are only offered where they fit."""
    out = []
    seen = set()
    for bh in (1, 2, 4, 8, 16, 32, 64):
        for bn in (128, 256):
            for depth in depths:
                cand = (min(bh, b * ho), min(bn, _ceil_to(n, 128)), depth)
                if cand in seen:
                    continue
                seen.add(cand)
                if conv_vmem_bytes(cand[0], wo, kc, cand[1], dx=dx,
                                   depth=depth) <= budget:
                    out.append(cand)
    return out


# ---------------------------------------------------------------------------
# Timing + memoization
# ---------------------------------------------------------------------------


def _under_trace() -> bool:
    """True while JAX traces (jit, grad, vmap): a kernel call there only
    stages, so the clock would time tracing."""
    return not jax.core.trace_ctx.is_top_level()


def _time_once(fn: Callable, *args, iters: int = 3, warmup: int = 1) -> float:
    """Median wall-clock seconds, blocking on outputs."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def _coeff_sig(coeffs) -> tuple:
    return tuple(tuple(float(v) for v in row) for row in coeffs)


def _lookup(kind: str, key: tuple, default, *, enable: bool | None):
    """``(blocks, False)`` when the answer needs no timing — a cached
    winner, or the default under a trace or with tuning disabled —
    ``(None, True)`` when the caller should tune."""
    if key in _CACHE:
        default_registry().counter("autotune.cache_hit").inc()
        return _CACHE[key]["best"], False
    if _under_trace():
        default_registry().counter("autotune.traced_default").inc()
        return default, False
    if not enabled(enable):
        _log_disabled_defaults(kind, jax.default_backend(), default)
        return default, False
    return None, True


def autotune(key: tuple, candidates: Iterable, run: Callable,
             *, iters: int = 3, vmem: Callable | None = None) -> dict:
    """Generic: time `run(candidate)` for each candidate, cache the winner.

    Returns ``{"best": candidate, "timings": {candidate: seconds},
    "decision": record}``.  A candidate that fails (e.g. a block shape the
    backend rejects) is timed as inf, its error is kept in the decision
    record's ``errors``, and it is never served.  Called while JAX traces
    it raises: timing there would time tracing.

    Observability (DESIGN.md §13.2): every call counts
    ``autotune.cache_hit`` / ``autotune.cache_miss`` into the metrics
    registry, and a miss stores a **decision record** — the candidate
    set with its VMEM charges (``vmem`` maps candidate → bytes), the
    chosen blocks, and the winning time — retrievable via
    :func:`decision_records` and logged as one structured
    ``p2m_autotune_decision`` record.
    """
    if key in _CACHE:
        default_registry().counter("autotune.cache_hit").inc()
        return _CACHE[key]
    if _under_trace():
        raise RuntimeError(f"autotune {key} called under a trace; tune only "
                           "in an eager call on concrete arrays")
    default_registry().counter("autotune.cache_miss").inc()
    timings: dict = {}
    errors: dict = {}
    for cand in candidates:
        try:
            timings[cand] = _time_once(run, cand, iters=iters)
        except Exception as exc:  # noqa: BLE001 - per-candidate isolation
            timings[cand] = float("inf")
            errors[repr(cand)] = f"{type(exc).__name__}: {exc}"[:300]
    if not timings or all(np.isinf(list(timings.values()))):
        raise RuntimeError(f"autotune: no viable candidate for {key}: "
                           f"{errors}")
    best = min(timings, key=timings.get)
    decision = {
        "key": repr(key),
        "kind": key[0] if key and isinstance(key[0], str) else "?",
        "candidates": [list(c) for c in timings],
        "vmem_bytes": ([int(vmem(c)) for c in timings]
                       if vmem is not None else None),
        "best": list(best),
        "best_s": timings[best],
        "n_viable": sum(1 for t in timings.values() if np.isfinite(t)),
        "errors": errors,
    }
    result = {"best": best, "timings": timings, "decision": decision}
    _CACHE[key] = result
    structured(logger, "p2m_autotune_decision",
               kind=decision["kind"], best=decision["best"],
               n_candidates=len(timings), n_viable=decision["n_viable"])
    return result


def decision_records() -> list[dict]:
    """Every autotune decision taken this process (cache misses only —
    a hit serves the recorded decision's winner)."""
    return [v["decision"] for v in _CACHE.values() if "decision" in v]


def get_matmul_blocks(m: int, k: int, n: int, coeffs, mode: str,
                      *, enable: bool | None = None, interpret: bool = False,
                      iters: int = 3) -> tuple[int, int, int]:
    """(block_m, block_n, block_k) for `p2m_matmul_pallas` — tuned when
    enabled and called eagerly, the cached winner or the heuristic
    defaults otherwise."""
    default = (256, 128, 128)
    backend = jax.default_backend()
    # `interpret` and `backend` are part of the key: winners timed in
    # interpret mode (or on another backend) must never be served to
    # compiled calls with the same shape signature.
    key = ("matmul", m, k, n, _coeff_sig(coeffs), mode, bool(interpret),
           backend)
    blocks, tune_now = _lookup("matmul", key, default, enable=enable)
    if not tune_now:
        return blocks
    from repro.kernels.p2m_conv.kernel import p2m_matmul_pallas

    rng = np.random.default_rng(0)
    x = jax.numpy.asarray(rng.random((m, k)), jax.numpy.float32)
    w = jax.numpy.asarray(rng.uniform(-1, 1, (k, n)), jax.numpy.float32)
    s = jax.numpy.zeros((n,), jax.numpy.float32)

    def run(cand):
        bm, bn, bk = cand
        return p2m_matmul_pallas(x, w, s, coeffs=_coeff_sig(coeffs),
                                 mode=mode, block_m=bm, block_n=bn,
                                 block_k=bk, interpret=interpret)

    dx = len(coeffs[0])
    cands = matmul_candidates(m, k, n, dx=dx) or [default]
    return autotune(key, cands, run, iters=iters,
                    vmem=lambda c: matmul_vmem_bytes(*c, dx=dx))["best"]


def get_conv_blocks(b: int, h: int, w: int, c: int, n: int, kernel: int,
                    stride: int, coeffs, mode: str, *,
                    enable: bool | None = None, interpret: bool = False,
                    depths: tuple[int, ...] = CONV_PIPELINE_DEPTHS,
                    iters: int = 3
                    ) -> tuple[int | None, int | None, int]:
    """(block_h, block_n, pipeline_depth) for `p2m_conv_pallas` — tuned
    when enabled and called eagerly; otherwise the
    cached winner or ``(None, None, 0)`` (the kernel's own heuristic
    blocks, automatic grid pipeline).

    Compiled (``interpret=False``), depths Mosaic cannot lower for this
    shape leave the menu, and a geometry it cannot lower at all raises
    ``ValueError`` (`conv.mosaic_conv_error`)."""
    from repro.kernels.p2m_conv.conv import (
        conv_out_spatial,
        mosaic_conv_error,
        p2m_conv_pallas,
    )

    default = (None, None, 0)
    if not interpret:
        if err := mosaic_conv_error(kernel, stride, c):
            raise ValueError(err)
        depths = tuple(d for d in depths
                       if mosaic_conv_error(kernel, stride, c, d) is None)
    backend = jax.default_backend()
    # Backend and the swept depth axis are in the key so a winner tuned on
    # one backend (or over a different depth menu) can't leak to another.
    key = ("conv", b, h, w, c, n, kernel, stride, _coeff_sig(coeffs), mode,
           bool(interpret), backend, tuple(depths))
    blocks, tune_now = _lookup("conv", key, default, enable=enable)
    if not tune_now:
        return blocks

    ho = conv_out_spatial(h, kernel, stride)
    wo = conv_out_spatial(w, kernel, stride)
    rng = np.random.default_rng(0)
    imgs = jax.numpy.asarray(rng.random((b, h, w, c)), jax.numpy.float32)
    wts = jax.numpy.asarray(
        rng.uniform(-1, 1, (kernel * kernel * c, n)), jax.numpy.float32)
    s = jax.numpy.zeros((n,), jax.numpy.float32)

    def run(cand):
        bh, bn, depth = cand
        return p2m_conv_pallas(imgs, wts, s, kernel=kernel, stride=stride,
                               coeffs=_coeff_sig(coeffs), mode=mode,
                               block_h=bh, block_n=bn,
                               pipeline_depth=depth, interpret=interpret)

    dx = len(coeffs[0])
    kc = kernel * c
    cands = conv_candidates(b, ho, wo, n, kc, dx=dx,
                            depths=tuple(depths)) or [(8, 128, 0)]
    return autotune(key, cands, run, iters=iters,
                    vmem=lambda cd: conv_vmem_bytes(
                        cd[0], wo, kc, cd[1], dx=dx, depth=cd[2]))["best"]


# ---------------------------------------------------------------------------
# Cache management
# ---------------------------------------------------------------------------


def cache_info() -> dict[str, tuple]:
    """{printable-signature: best-blocks} for every tuned entry."""
    return {repr(k): v["best"] for k, v in _CACHE.items()}


def cache_clear() -> None:
    _CACHE.clear()


def cache_dump(path: str) -> None:
    """Persist winners (not timings) as JSON, e.g. from a benchmark run."""
    payload = [
        {"key": list(map(repr, k)), "best": list(v["best"]),
         "timings_s": {repr(c): t for c, t in v["timings"].items()}}
        for k, v in _CACHE.items()
    ]
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
