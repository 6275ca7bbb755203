"""The P²M in-pixel convolutional layer (paper §3.2, §4.1-4.2).

Functionally a conv + BN + ReLU block, but computed the way the circuit
computes it:

* every multiply is the behavioral pixel function ``g(w, x)`` (not ``w·x``),
* weights live in [−1, 1] (normalized transistor driving strength; the CDS
  double-sample realizes the sign),
* the output passes through the SS-ADC: shifted ReLU with full-scale
  saturation, optionally integer-quantized.

Two parameterizations:

* **train form** — conv(g) → BatchNorm (batch stats) → saturating ReLU.
  This is what the paper trains.
* **deploy form** — BN folded (scale into weights, shift into the ADC
  counter pre-load), optional post-training quantization.  Produced by
  `bn_fold.deploy_params`.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.adc import ADCConfig
from repro.core.pixel_model import PixelModel, default_pixel_model
# A module reference, not a from-import: `ops` imports `repro.core.adc`,
# which runs this package's __init__ and so this module, before `ops` has
# finished when `ops` is imported first.
from repro.kernels.p2m_conv import ops
from repro.parallel.axes import batch_shard_map


@dataclasses.dataclass(frozen=True)
class P2MConvConfig:
    """Paper Table 1 defaults: k=5, s=5 (non-overlapping), p=0, c_o=8, N_b=8."""

    kernel: int = 5
    stride: int = 5
    in_channels: int = 3
    out_channels: int = 8
    n_bits: int = 8
    bn_momentum: float = 0.9
    bn_eps: float = 1e-5

    @property
    def adc(self) -> ADCConfig:
        return ADCConfig(n_bits=self.n_bits, v_lsb=1.0 / (2**self.n_bits - 1))

    def out_spatial(self, i: int) -> int:
        return (i - self.kernel) // self.stride + 1


def extract_patches(images: jax.Array, kernel: int, stride: int) -> jax.Array:
    """(B, H, W, C) → (B, P, k·k·C) patches, (kh, kw, C) fastest-varying.

    Fast path for the paper's non-overlapping case (stride == kernel,
    dims divisible): a pure reshape/transpose, no gather.  General path
    uses ``conv_general_dilated_patches`` and reorders its channel-major
    feature layout to (kh, kw, C).
    """
    b, h, w, c = images.shape
    k, s = kernel, stride
    if s == k and h % k == 0 and w % k == 0:
        ph, pw = h // k, w // k
        x = images.reshape(b, ph, k, pw, k, c)
        x = x.transpose(0, 1, 3, 2, 4, 5)  # (B, ph, pw, k, k, C)
        return x.reshape(b, ph * pw, k * k * c)
    patches = jax.lax.conv_general_dilated_patches(
        images,
        filter_shape=(k, k),
        window_strides=(s, s),
        padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )  # (B, ph, pw, C·k·k) with channel-major (C, kh, kw) feature order
    bb, ph, pw, f = patches.shape
    patches = patches.reshape(bb, ph * pw, c, k * k)
    patches = patches.transpose(0, 1, 3, 2)  # → (kh·kw, C) fastest-varying
    return patches.reshape(bb, ph * pw, k * k * c)


def init_p2m_conv(key: jax.Array, cfg: P2MConvConfig) -> dict[str, Any]:
    """Trainable params + BN state for the train form."""
    k = cfg.kernel
    fan_in = k * k * cfg.in_channels
    wkey, _ = jax.random.split(key)
    theta = jax.random.uniform(
        wkey, (k, k, cfg.in_channels, cfg.out_channels),
        minval=-1.0, maxval=1.0, dtype=jnp.float32,
    ) * (3.0 / fan_in) ** 0.5
    return {
        "theta": theta,
        "bn_gamma": jnp.ones((cfg.out_channels,), jnp.float32),
        "bn_beta": jnp.zeros((cfg.out_channels,), jnp.float32),
    }


def init_p2m_state(cfg: P2MConvConfig) -> dict[str, Any]:
    return {
        "bn_mean": jnp.zeros((cfg.out_channels,), jnp.float32),
        "bn_var": jnp.ones((cfg.out_channels,), jnp.float32),
    }


def _flat_weights(theta: jax.Array, cfg: P2MConvConfig) -> jax.Array:
    """(k,k,C,Co) → (k·k·C, Co), clipped to the transistor range [−1, 1]."""
    k = cfg.kernel
    w = jnp.clip(theta, -1.0, 1.0)
    return w.reshape(k * k * cfg.in_channels, cfg.out_channels)


def _resolve_impl(impl: str | None) -> str:
    """Conv implementation select: "pallas" (fused implicit-im2col kernel,
    the TPU hot path), "fused" (same decomposition in XLA ops — the
    off-TPU default), "patches" (extract_patches + p2m_matmul_jnp, the
    reference fallback)."""
    if impl is None:
        return "pallas" if jax.default_backend() == "tpu" else "fused"
    if impl not in ("pallas", "fused", "patches"):
        raise ValueError(f"unknown p2m conv impl {impl!r}")
    return impl


def _conv_raw(images, w, cfg: P2MConvConfig, model: PixelModel,
              impl: str) -> jax.Array:
    """Pre-epilogue conv accumulation (B, Ho, Wo, Co) via the chosen impl."""
    zero = jnp.zeros((cfg.out_channels,), jnp.float32)
    if impl == "pallas":
        return batch_shard_map(
            lambda im, w_, sh: ops.p2m_conv(im, w_, sh, model, cfg.adc, "raw",
                                            cfg.kernel, cfg.stride),
            images, w, zero)
    if impl == "fused":
        return ops.p2m_conv_jnp(images, w, zero, model, cfg.adc, "raw",
                                cfg.kernel, cfg.stride)
    b = images.shape[0]
    ho = cfg.out_spatial(images.shape[1])
    wo = cfg.out_spatial(images.shape[2])
    patches = extract_patches(images, cfg.kernel, cfg.stride)  # (B,P,K)
    xf = patches.reshape(b * patches.shape[1], -1)
    raw = ops.p2m_matmul_jnp(xf, w, zero, model, cfg.adc, mode="raw")
    return raw.reshape(b, ho, wo, cfg.out_channels)


def apply_p2m_conv_train(
    params: dict,
    state: dict,
    images: jax.Array,
    cfg: P2MConvConfig,
    model: PixelModel | None = None,
    *,
    train: bool = True,
    rng: jax.Array | None = None,
    impl: str | None = None,
):
    """Train-form forward: conv(g) → BN → saturating ReLU.

    ``impl`` selects the conv path (see `_resolve_impl`); the default is
    the fused implicit-im2col kernel on TPU and its XLA twin elsewhere,
    with ``"patches"`` as the materializing reference fallback.  Under a
    sharding plan the kernel runs once per batch shard
    (`parallel.batch_shard_map`): XLA cannot partition a Mosaic kernel.

    Returns ``(out (B, Ho, Wo, Co), new_state)``.
    """
    model = model or default_pixel_model()
    b = images.shape[0]
    ho = cfg.out_spatial(images.shape[1])
    wo = cfg.out_spatial(images.shape[2])
    w = _flat_weights(params["theta"], cfg)

    raw = _conv_raw(images, w, cfg, model, _resolve_impl(impl))
    raw = raw.reshape(b * ho * wo, cfg.out_channels)
    if model.read_noise_std > 0.0 and rng is not None:
        raw = raw + model.read_noise_std * jax.random.normal(rng, raw.shape, raw.dtype)

    if train:
        mean = raw.mean(axis=0)
        var = raw.var(axis=0)
        mom = cfg.bn_momentum
        new_state = {
            "bn_mean": mom * state["bn_mean"] + (1 - mom) * mean,
            "bn_var": mom * state["bn_var"] + (1 - mom) * var,
        }
    else:
        mean, var = state["bn_mean"], state["bn_var"]
        new_state = state
    xhat = (raw - mean) / jnp.sqrt(var + cfg.bn_eps)
    y = params["bn_gamma"] * xhat + params["bn_beta"]
    y = jnp.clip(y, 0.0, cfg.adc.full_scale)  # saturating ReLU (counter clamp)
    return y.reshape(b, ho, wo, cfg.out_channels), new_state


def apply_p2m_conv_deploy(
    deploy: dict,
    images: jax.Array,
    cfg: P2MConvConfig,
    model: PixelModel | None = None,
    *,
    quantize: bool = True,
    use_pallas: bool = True,
    impl: str | None = None,
):
    """Deploy-form forward with folded BN: conv(g) → shifted-ReLU ADC.

    ``deploy`` holds ``w`` (k·k·C, Co) folded+clipped weights and ``shift``
    (Co,) counter pre-load in volts (see `bn_fold`).  The conv runs on the
    fused implicit-im2col path (``impl``, `_resolve_impl`);
    ``use_pallas=False`` is the back-compat spelling of
    ``impl="patches"`` — the patch-materializing reference.
    """
    model = model or default_pixel_model()
    mode = "quant" if quantize else "relu"
    if impl is None and not use_pallas:
        impl = "patches"
    impl = _resolve_impl(impl)
    if impl == "pallas":
        return batch_shard_map(
            lambda im, w, sh: ops.p2m_conv(im, w, sh, model, cfg.adc, mode,
                                           cfg.kernel, cfg.stride),
            images, deploy["w"], deploy["shift"])
    if impl == "fused":
        return ops.p2m_conv_jnp(images, deploy["w"], deploy["shift"], model,
                                cfg.adc, mode, cfg.kernel, cfg.stride)
    b = images.shape[0]
    ho = cfg.out_spatial(images.shape[1])
    wo = cfg.out_spatial(images.shape[2])
    patches = extract_patches(images, cfg.kernel, cfg.stride)
    xf = patches.reshape(b * patches.shape[1], -1)
    out = ops.p2m_matmul_jnp(xf, deploy["w"], deploy["shift"], model,
                             cfg.adc, mode=mode)
    return out.reshape(b, ho, wo, cfg.out_channels)
