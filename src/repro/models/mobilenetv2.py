"""MobileNetV2 for VWW (paper §5.1) — baseline and P²M-custom variants.

Baseline: standard MobileNetV2 (first conv 32ch, last bottleneck 320ch)
supporting full-resolution 560×560 input, with the last inverted-residual
block's channels reduced 3× (paper: to avoid overfitting on 2 classes).

P²M-custom: the first conv layer is replaced by the in-pixel P²M layer
(k=5, s=5, c_o=8, 8-bit ADC output — Table 1); the downstream block
schedule is unchanged, so the stack runs at the P²M output resolution
(112² for a 560² frame, vs 280² after the baseline's stride-2 stem) —
which is exactly where the paper's 7.15× MAdds reduction comes from.

Everything is functional: ``init_mnv2`` → params/state trees,
``apply_mnv2`` → logits.  ``layer_census`` returns the ConvSpec list the
EDP/MAdds analytics consume (paper Table 2 / Fig. 8).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.energy import ConvSpec
from repro.core.p2m_conv import (
    P2MConvConfig,
    apply_p2m_conv_deploy,
    apply_p2m_conv_train,
    init_p2m_conv,
    init_p2m_state,
)
from repro.core.pixel_model import PixelModel

# (expansion t, out channels c, repeats n, first-block stride s)
MNV2_BLOCKS = [
    (1, 16, 1, 1),
    (6, 24, 2, 2),
    (6, 32, 3, 2),
    (6, 64, 4, 2),
    (6, 96, 3, 1),
    (6, 160, 3, 2),
    (6, 320, 1, 1),
]


@dataclasses.dataclass(frozen=True)
class MNV2Config:
    variant: str = "baseline"  # "baseline" | "p2m"
    image_size: int = 560
    num_classes: int = 2
    width: float = 1.0
    head_channels: int = 1280
    last_block_div: int = 3  # paper: reduce last block channels 3×
    first_channels: int = 32
    p2m: P2MConvConfig = dataclasses.field(default_factory=P2MConvConfig)

    def block_schedule(self):
        blocks = []
        for idx, (t, c, n, s) in enumerate(MNV2_BLOCKS):
            c = int(round(c * self.width))
            if idx == len(MNV2_BLOCKS) - 1 and self.last_block_div > 1:
                c = max(8, c // self.last_block_div)
            blocks.append((t, c, n, s))
        return blocks


def smoke_config() -> MNV2Config:
    """Tiny reduced config for CPU smoke tests."""
    return MNV2Config(image_size=40, width=0.25, head_channels=64)


def head_out_channels(cfg: MNV2Config) -> int:
    """Channel width of the pre-pool head conv — the backbone's output
    feature dim (what `apply_mnv2_backbone` returns, and the
    ``in_channels`` a detection head on it must take).  The head never
    narrows below its configured width (the standard MNv2 convention:
    the width multiplier only widens it past 1.0)."""
    return int(round(cfg.head_channels * max(1.0, cfg.width)))


# ------------------------------------------------------------------ layers


def _conv_init(key, k, cin, cout, groups=1):
    fan_in = k * k * cin // groups
    return jax.random.normal(key, (k, k, cin // groups, cout), jnp.float32) * (
        2.0 / fan_in
    ) ** 0.5


def _bn_init(c):
    return {
        "gamma": jnp.ones((c,), jnp.float32),
        "beta": jnp.zeros((c,), jnp.float32),
    }


def _bn_state(c):
    return {"mean": jnp.zeros((c,), jnp.float32), "var": jnp.ones((c,), jnp.float32)}


def _conv(x, w, stride=1, groups=1):
    return jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=(stride, stride),
        padding="SAME",
        feature_group_count=groups,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _bn(x, p, s, train: bool, momentum=0.9, eps=1e-5):
    if train:
        mean = x.mean(axis=(0, 1, 2))
        var = x.var(axis=(0, 1, 2))
        new_s = {
            "mean": momentum * s["mean"] + (1 - momentum) * mean,
            "var": momentum * s["var"] + (1 - momentum) * var,
        }
    else:
        mean, var, new_s = s["mean"], s["var"], s
    y = (x - mean) / jnp.sqrt(var + eps) * p["gamma"] + p["beta"]
    return y, new_s


def _relu6(x):
    return jnp.clip(x, 0.0, 6.0)


# ------------------------------------------------------------------ init


def init_mnv2(key: jax.Array, cfg: MNV2Config) -> tuple[dict, dict]:
    """Returns (params, state)."""
    keys = iter(jax.random.split(key, 256))
    params: dict[str, Any] = {}
    state: dict[str, Any] = {}

    if cfg.variant == "p2m":
        params["stem"] = init_p2m_conv(next(keys), cfg.p2m)
        state["stem"] = init_p2m_state(cfg.p2m)
        cin = cfg.p2m.out_channels
    else:
        c0 = int(round(cfg.first_channels * cfg.width))
        params["stem"] = {"w": _conv_init(next(keys), 3, 3, c0), "bn": _bn_init(c0)}
        state["stem"] = {"bn": _bn_state(c0)}
        cin = c0

    bidx = 0
    for t, c, n, s in cfg.block_schedule():
        for i in range(n):
            stride = s if i == 0 else 1
            hidden = cin * t
            blk: dict[str, Any] = {}
            bst: dict[str, Any] = {}
            if t != 1:
                blk["expand"] = {"w": _conv_init(next(keys), 1, cin, hidden), "bn": _bn_init(hidden)}
                bst["expand"] = {"bn": _bn_state(hidden)}
            blk["dw"] = {
                "w": _conv_init(next(keys), 3, hidden, hidden, groups=hidden),
                "bn": _bn_init(hidden),
            }
            bst["dw"] = {"bn": _bn_state(hidden)}
            blk["project"] = {"w": _conv_init(next(keys), 1, hidden, c), "bn": _bn_init(c)}
            bst["project"] = {"bn": _bn_state(c)}
            params[f"block{bidx}"] = blk
            state[f"block{bidx}"] = bst
            bidx += 1
            cin = c

    ch = head_out_channels(cfg)
    params["head"] = {"w": _conv_init(next(keys), 1, cin, ch), "bn": _bn_init(ch)}
    state["head"] = {"bn": _bn_state(ch)}
    params["fc"] = {
        "w": jax.random.normal(next(keys), (ch, cfg.num_classes), jnp.float32) * 0.01,
        "b": jnp.zeros((cfg.num_classes,), jnp.float32),
    }
    return params, state


# ------------------------------------------------------------------ apply


def apply_mnv2_stem(
    params: dict,
    state: dict,
    images: jax.Array,
    cfg: MNV2Config,
    pixel_model: PixelModel | None = None,
    *,
    train: bool = False,
    p2m_deploy: dict | None = None,
    p2m_impl: str | None = None,
) -> tuple[jax.Array, dict]:
    """First layer only: what the sensor executes for the P²M variant.

    (B, H, W, 3) → (B, Ho, Wo, C) stem activations, plus the new stem
    state.  Split out of :func:`apply_mnv2` so the streaming-video
    subsystem (`repro.video`, DESIGN.md §9) can cache these activations
    per stream and skip re-running the in-pixel layer on temporally
    redundant frames — the stem output is exactly the tensor that leaves
    the sensor, so its recompute rate is also the readout bandwidth.

    ``p2m_impl`` selects the conv path (`core.p2m_conv._resolve_impl`);
    the serving engines pass ``"patches"`` here when degrading to the
    reference conv after repeated kernel faults (DESIGN.md §10).
    """
    new_state: dict[str, Any] = {}
    if cfg.variant == "p2m":
        with jax.named_scope("p2m_stem"):
            if p2m_deploy is not None:
                x = apply_p2m_conv_deploy(p2m_deploy, images, cfg.p2m,
                                          pixel_model, impl=p2m_impl)
                new_state["stem"] = state["stem"]
            else:
                x, st = apply_p2m_conv_train(
                    params["stem"], state["stem"], images, cfg.p2m,
                    pixel_model, train=train, impl=p2m_impl
                )
                new_state["stem"] = st
    else:
        with jax.named_scope("stem"):
            x = _conv(images, params["stem"]["w"], stride=2)
            x, bn_st = _bn(x, params["stem"]["bn"], state["stem"]["bn"], train)
            x = _relu6(x)
        new_state["stem"] = {"bn": bn_st}
    return x, new_state


def _inverted_residual(x, blk: dict, bst: dict, t: int, stride: int,
                       residual: bool, train: bool) -> tuple[jax.Array, dict]:
    """One inverted-residual block: 1×1 expand (t ≠ 1), 3×3 depthwise,
    1×1 project, plus the skip when ``residual``."""
    nst: dict[str, Any] = {}
    y = x
    if t != 1:
        y = _conv(y, blk["expand"]["w"])
        y, st_ = _bn(y, blk["expand"]["bn"], bst["expand"]["bn"], train)
        nst["expand"] = {"bn": st_}
        y = _relu6(y)
    y = _conv(y, blk["dw"]["w"], stride=stride, groups=y.shape[-1])
    y, st_ = _bn(y, blk["dw"]["bn"], bst["dw"]["bn"], train)
    nst["dw"] = {"bn": st_}
    y = _relu6(y)
    y = _conv(y, blk["project"]["w"])
    y, st_ = _bn(y, blk["project"]["bn"], bst["project"]["bn"], train)
    nst["project"] = {"bn": st_}
    if residual:
        y = y + x
    return y, nst


def apply_mnv2_backbone(
    params: dict,
    state: dict,
    x: jax.Array,
    cfg: MNV2Config,
    *,
    train: bool = False,
) -> tuple[jax.Array, dict]:
    """Inverted-residual stack + head conv on stem activations.

    (B, Ho, Wo, C_stem) → (B, h, w, head_channels) feature map (pre
    global-pool), plus the new block/head state.  The classification
    head pools this; the video detection head (`video/detect.py`) reads
    it at full spatial resolution.
    """
    new_state: dict[str, Any] = {}
    bidx = 0
    cin = x.shape[-1]
    for t, c, n, s in cfg.block_schedule():
        for i in range(n):
            stride = s if i == 0 else 1
            with jax.named_scope(f"backbone/block{bidx}"):
                x, new_state[f"block{bidx}"] = _inverted_residual(
                    x, params[f"block{bidx}"], state[f"block{bidx}"], t,
                    stride, stride == 1 and cin == c, train)
            bidx += 1
            cin = c

    with jax.named_scope("backbone/head"):
        x = _conv(x, params["head"]["w"])
        x, st_ = _bn(x, params["head"]["bn"], state["head"]["bn"], train)
        x = _relu6(x)
    new_state["head"] = {"bn": st_}
    return x, new_state


def apply_mnv2(
    params: dict,
    state: dict,
    images: jax.Array,
    cfg: MNV2Config,
    pixel_model: PixelModel | None = None,
    *,
    train: bool = False,
    p2m_deploy: dict | None = None,
    p2m_impl: str | None = None,
) -> tuple[jax.Array, dict]:
    """(B, H, W, 3) → (B, num_classes) logits, plus new state."""
    x, stem_state = apply_mnv2_stem(
        params, state, images, cfg, pixel_model, train=train,
        p2m_deploy=p2m_deploy, p2m_impl=p2m_impl,
    )
    x, new_state = apply_mnv2_backbone(params, state, x, cfg, train=train)
    new_state = {**stem_state, **new_state}
    with jax.named_scope("classifier"):
        x = x.mean(axis=(1, 2))
        logits = x @ params["fc"]["w"] + params["fc"]["b"]
    # (no "fc" entry in the state tree: the head is stateless, and the
    # output state must mirror the input structure exactly so one
    # sharding tree serves jit in_shardings and out_shardings alike)
    return logits, new_state


# ------------------------------------------------------------------ census


def layer_census(cfg: MNV2Config, *, include_in_pixel: bool = False) -> list[ConvSpec]:
    """ConvSpec list for MAdds / delay / peak-memory analytics.

    For the P²M variant the in-pixel first layer is excluded by default
    (it runs in the sensor, not the SoC) — ``include_in_pixel=True`` adds
    it back for ablations.
    """
    census: list[ConvSpec] = []
    i = cfg.image_size

    if cfg.variant == "p2m":
        hw = cfg.p2m.out_spatial(i)
        if include_in_pixel:
            census.append(
                ConvSpec(cfg.p2m.kernel, 3, cfg.p2m.out_channels, hw, hw)
            )
        cin = cfg.p2m.out_channels
    else:
        hw = (i + 1) // 2
        c0 = int(round(cfg.first_channels * cfg.width))
        census.append(ConvSpec(3, 3, c0, hw, hw))
        cin = c0

    for t, c, n, s in cfg.block_schedule():
        for idx in range(n):
            stride = s if idx == 0 else 1
            hidden = cin * t
            if t != 1:
                census.append(ConvSpec(1, cin, hidden, hw, hw))
            out_hw = -(-hw // stride)
            census.append(ConvSpec(3, hidden, hidden, out_hw, out_hw, groups=hidden))
            census.append(ConvSpec(1, hidden, c, out_hw, out_hw))
            hw = out_hw
            cin = c

    ch = head_out_channels(cfg)
    census.append(ConvSpec(1, cin, ch, hw, hw))
    census.append(ConvSpec(1, ch, cfg.num_classes, 1, 1))
    return census


def peak_activation_bytes(cfg: MNV2Config, *, fused_blocks: bool) -> int:
    """Peak activation memory, int8 elements (VWW-challenge accounting).

    ``fused_blocks=False``: every conv output is a materialized buffer and
    the peak is the largest single tensor — the t× expansion buffers
    dominate.  This reproduces the paper's *baseline* column exactly
    (7.53 / 1.2 / 0.311 MB = the 96-channel expansion at stage-2 res).

    ``fused_blocks=True``: inverted-residual blocks stream per-channel
    (TFLite-micro style) so expansions are never materialized; the peak is
    the largest (block input + block output) pair.  This reproduces the
    paper's *P²M-custom* column exactly (0.30 / 0.049 / 0.013 MB =
    8ch input + 16ch output at the P²M resolution).  The paper's Table 2
    mixes these two conventions across its columns — defensible (the P²M
    model targets fused MCU kernels; the baseline doesn't fit an MCU under
    either convention) but worth making explicit.  See EXPERIMENTS.md.
    """
    peak = 0
    i = cfg.image_size
    if cfg.variant == "p2m":
        hw = cfg.p2m.out_spatial(i)
        cin = cfg.p2m.out_channels
        peak = max(peak, hw * hw * cin)
    else:
        hw = (i + 1) // 2
        cin = int(round(cfg.first_channels * cfg.width))
        peak = (
            max(peak, i * i * 3, hw * hw * cin)
            if not fused_blocks
            else max(peak, i * i * 3 + hw * hw * cin)
        )

    for t, c, n, s in cfg.block_schedule():
        for idx in range(n):
            stride = s if idx == 0 else 1
            hidden = cin * t
            out_hw = -(-hw // stride)
            if fused_blocks:
                peak = max(peak, hw * hw * cin + out_hw * out_hw * c)
            else:
                peak = max(peak, hw * hw * hidden, out_hw * out_hw * hidden,
                           out_hw * out_hw * c)
            hw = out_hw
            cin = c
    ch = head_out_channels(cfg)
    peak = max(peak, hw * hw * cin + hw * hw * ch if fused_blocks else hw * hw * ch)
    return peak
