"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Production target: TPU v5e pods, 256
chips/pod; the multi-pod mesh adds a leading "pod" axis (2 pods = 512
chips).  Hardware constants for the roofline live here too.
"""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """`jax.make_mesh` with every axis ``Auto``: the logical-axis plans
    place arrays with `with_sharding_constraint`, which only accepts Auto
    axes (`jax.make_mesh` defaults to Explicit ones)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_devices: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = n_devices or len(jax.devices())
    assert n % model == 0
    return _auto_mesh((n // model, model), ("data", "model"))


def make_submeshes(n: int, *, model: int = 1, devices=None):
    """Split the visible devices into ``n`` disjoint ("data", "model")
    submeshes — one per replica of a `serving.pool.ReplicaPool`, so a
    pool of sharded engines gets data-parallelism *within* each replica
    and replica-parallelism across them (DESIGN.md §11).  Contiguous
    device slices: replica boundaries line up with physical locality on
    real topologies."""
    import numpy as np
    from jax.sharding import Mesh

    devs = list(devices if devices is not None else jax.devices())
    assert len(devs) % n == 0, (len(devs), n)
    per = len(devs) // n
    assert per % model == 0, (per, model)
    return [Mesh(np.asarray(devs[i * per:(i + 1) * per])
                 .reshape(per // model, model), ("data", "model"))
            for i in range(n)]


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks (roofline denominators)."""

    flops_bf16: float  # FLOP/s
    ops_int8: float  # OP/s
    hbm_bytes_per_s: float
    ici_bytes_per_s: float  # chip-to-chip interconnect, all links of one chip


# Keyed by `jax.Device.device_kind`.  TPU v5e: Google Cloud documentation,
# "TPU v5e" — 197 TFLOP/s bf16, 393 TOP/s int8, 819 GB/s HBM,
# 1,600 Gbit/s ICI per chip.
CHIP_PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, ops_int8=393e12,
                             hbm_bytes_per_s=819e9,
                             ici_bytes_per_s=1600e9 / 8),
}

# The chip `make_production_mesh` is built from (TPU v5e pods).
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; a kind without published peaks is an
    error, never a default."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(CHIP_PEAKS)}") from None


def mesh_chips(mesh) -> int:
    return int(mesh.devices.size)
