"""Helpers the per-layer metric readers (`bench/metrics/*.py`) share:
finding a step program and a kernel in a reduced trace, and the shares
of a peak or a roofline.  Every reader returns None where it finds
nothing to read; a share is never reported as 0 for want of a reading.
"""
from __future__ import annotations

import re

from bench import yardstick
from bench.tracing import base_name


def reduction(ctx):
    return ctx.get("reduction")


def module_calls(ctx, needle: str) -> tuple[int, float] | None:
    """(calls, device seconds) of the window's compiled programs whose
    name contains ``needle``; None if the trace has none."""
    red = reduction(ctx)
    if red is None:
        return None
    n, s = red.module_seconds(lambda name: needle in name)
    return (n, s) if n and s > 0 else None


def kernel_calls(ctx, kernel: str) -> tuple[int, float] | None:
    """(calls, device seconds) of the window's operations named after
    the Pallas kernel's jitted wrapper ``kernel``: ``kernel.1`` where it
    is called directly, ``jvp_jit_kernel__`` or
    ``transpose_jvp_jit_kernel___`` under autodiff."""
    red = reduction(ctx)
    if red is None:
        return None
    named = re.compile(rf"(^|_){re.escape(kernel)}_*$")
    n, s = red.op_seconds(lambda name: bool(named.search(base_name(name))))
    return (n, s) if n and s > 0 else None


def percent(x: float) -> float:
    return 100.0 * x


def peak_share(ctx, flops: float, seconds: float) -> float | None:
    if seconds <= 0:
        return None
    return percent(flops / seconds
                   / yardstick.peaks(ctx["device_kind"]).flops_bf16)


def roofline_share(ctx, flops: float, byts: float,
                   seconds: float) -> float | None:
    """The least time the chip could take over the measured time."""
    if seconds <= 0:
        return None
    best, _ = yardstick.roofline_s(flops, byts,
                                   yardstick.peaks(ctx["device_kind"]))
    return percent(best / seconds)


def idle_share(ctx) -> float | None:
    red = reduction(ctx)
    if red is None or red.window_s <= 0:
        return None
    return percent(1.0 - red.busy_s() / red.window_s)
