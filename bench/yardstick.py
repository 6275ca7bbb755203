"""The yardstick: published chip peaks, and the operations and bytes
that each step and kernel needs, computed from shapes.

The layer census is a copy of the arithmetic of the repository's
``models/mobilenetv2.layer_census`` and ``core/energy.ConvSpec``
(``tests/test_bench_yardstick.py`` checks that they agree at the paper
geometry), kept here so that a change to the program cannot move it.
"""
from __future__ import annotations

import dataclasses

from bench.references.mnv2 import block_schedule, head_channels, stem_spatial


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float  # FLOP/s
    hbm_bytes_per_s: float


# Keyed by `jax.Device.device_kind`.  TPU v5e: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM per chip.
PEAKS = {"TPU v5 lite": Peaks(flops_bf16=197e12, hbm_bytes_per_s=819e9)}


def peaks(device_kind: str) -> Peaks:
    """Peaks of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None


@dataclasses.dataclass(frozen=True)
class Conv:
    k: int
    c_i: int
    c_o: int
    h_o: int
    w_o: int
    groups: int = 1

    @property
    def macs(self) -> int:
        return self.k * self.k * (self.c_i // self.groups) * self.c_o \
            * self.h_o * self.w_o


def census(cfg: dict) -> list[Conv]:
    """Every convolution of one frame's forward, the in-pixel layer
    included (the chip computes it too), the classifier last."""
    out = []
    hw = stem_spatial(cfg)
    if cfg["variant"] == "p2m":
        p = cfg["p2m"]
        out.append(Conv(p["kernel"], p["in_channels"], p["out_channels"],
                        hw, hw))
        cin = p["out_channels"]
    else:
        cin = int(round(cfg["first_channels"] * cfg["width"]))
        out.append(Conv(3, 3, cin, hw, hw))
    for t, c, n, s in block_schedule(cfg):
        for i in range(n):
            stride = s if i == 0 else 1
            hid = cin * t
            if t != 1:
                out.append(Conv(1, cin, hid, hw, hw))
            ohw = -(-hw // stride)
            out.append(Conv(3, hid, hid, ohw, ohw, groups=hid))
            out.append(Conv(1, hid, c, ohw, ohw))
            hw, cin = ohw, c
    ch = head_channels(cfg)
    out.append(Conv(1, cin, ch, hw, hw))
    out.append(Conv(1, ch, cfg["num_classes"], 1, 1))
    return out


def frame_macs(cfg: dict) -> tuple[int, int]:
    """(MACs of one frame's forward, MACs of its in-pixel layer; 0 for
    the baseline)."""
    c = census(cfg)
    stem = c[0].macs if cfg["variant"] == "p2m" else 0
    return sum(x.macs for x in c), stem


def forward_flops(cfg: dict, frames: int) -> float:
    """Model FLOPs of ``frames`` forwards: 2 per MAC."""
    return 2.0 * frame_macs(cfg)[0] * frames


def train_flops(cfg: dict, images: int) -> float:
    """Model FLOPs of a training step over ``images``: 6 per MAC
    (forward, input gradient, weight gradient), but 4 for the in-pixel
    layer, whose input takes no gradient."""
    total, stem = frame_macs(cfg)
    return (6.0 * (total - stem) + 4.0 * stem) * images


# -------------------------------------------------- the in-pixel kernels


def _pixel_dims(cfg: dict, batch: int) -> tuple[int, int, int, int]:
    """(M rows, K = k·k·C, N channels, d_x powers of x) of the in-pixel
    layer's sum over ``batch`` frames."""
    p = cfg["p2m"]
    hw = stem_spatial(cfg)
    return (batch * hw * hw, p["kernel"] ** 2 * p["in_channels"],
            p["out_channels"], len(cfg["pixel_model_coeffs"][0]))


def pixel_fwd_cost(cfg: dict, batch: int) -> tuple[float, float]:
    """(FLOPs, bytes) the in-pixel forward needs: Σ_j x^j @ W̃_j over d_x
    powers (2·M·d_x·K·N plus the powers), reading the frames once and
    writing the activations once, float32."""
    m, k, n, dx = _pixel_dims(cfg, batch)
    flops = 2.0 * m * dx * k * n + m * k * (dx - 1)
    return flops, 4.0 * (m * k + m * n)


def pixel_dw_cost(cfg: dict, batch: int) -> tuple[float, float]:
    """The weight gradient: T_j = (x^j)ᵀ @ G over d_x powers, reading the
    frames and the output gradient once, writing K·N."""
    m, k, n, dx = _pixel_dims(cfg, batch)
    flops = 2.0 * m * dx * k * n + m * k * (dx - 1)
    return flops, 4.0 * (m * k + m * n + k * n)


def roofline_s(flops: float, byts: float, pk: Peaks) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    tf, tb = flops / pk.flops_bf16, byts / pk.hbm_bytes_per_s
    return (tf, "compute") if tf >= tb else (tb, "bandwidth")
