"""BN folding into the P²M layer (paper §4.2, Eq. 1).

At inference BN is affine: ``Y = A·X + B`` with
``A = γ/√(σ²+ε)``, ``B = β − γμ/√(σ²+ε)``.

The paper folds **A into the pixel weights** (deployed transistor width
realizes ``A·θ``) and **B into the ADC counter pre-load** (shifted ReLU).

Caveat the paper glosses over: the pixel transfer ``g`` is *nonlinear in
w*, so ``Σ g(A·θ, x) ≠ A·Σ g(θ, x)`` exactly.  We implement the paper's
fold literally, expose :func:`fold_error` to quantify the approximation,
and (beyond-paper) support *deploy-form training* — training directly in
the folded parameterization — which removes the approximation entirely.
For a degree-1-in-w pixel model the fold is exact; tests cover both.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.p2m_conv import P2MConvConfig, _flat_weights
from repro.core.pixel_model import PixelModel
from repro.kernels.p2m_conv import ops  # module reference: see core/p2m_conv.py


def bn_affine(gamma, beta, mean, var, eps: float = 1e-5):
    """Return (A, B) of the inference-time BN affine map."""
    inv = 1.0 / jnp.sqrt(var + eps)
    a = gamma * inv
    b = beta - gamma * mean * inv
    return a, b


def deploy_params(params: dict, state: dict, cfg: P2MConvConfig) -> dict:
    """Fold train-form (θ, BN) into deploy-form (w, shift).

    ``w[k, c] = clip(A[c]·θ[k, c], −1, 1)`` — the transistor widths that get
    manufactured; ``shift[c] = B[c]`` — the counter pre-load in volts.
    """
    a, b = bn_affine(
        params["bn_gamma"], params["bn_beta"],
        state["bn_mean"], state["bn_var"], cfg.bn_eps,
    )
    w = _flat_weights(params["theta"], cfg)
    w_fold = jnp.clip(w * a[None, :], -1.0, 1.0)
    return {"w": w_fold, "shift": b, "bn_scale": a}


def fold_error(
    params: dict,
    state: dict,
    cfg: P2MConvConfig,
    model: PixelModel,
    sample_patches,
) -> float:
    """Max |BN(conv_g(θ)) − conv_g(A·θ) − B| over sample patches.

    Zero when g is linear in w (degree_w == 1) and |A·θ| ≤ 1; small but
    nonzero for the degree-3 fit — the residual the paper's fold incurs.
    """
    a, b = bn_affine(
        params["bn_gamma"], params["bn_beta"],
        state["bn_mean"], state["bn_var"], cfg.bn_eps,
    )
    w = _flat_weights(params["theta"], cfg)
    zero = jnp.zeros((cfg.out_channels,), jnp.float32)
    raw = ops.p2m_matmul_jnp(sample_patches, w, zero, model, cfg.adc, mode="raw")
    exact = a[None, :] * raw + b[None, :]
    w_fold = jnp.clip(w * a[None, :], -1.0, 1.0)
    folded = ops.p2m_matmul_jnp(sample_patches, w_fold, b, model, cfg.adc, mode="raw")
    return float(jnp.max(jnp.abs(exact - folded)))


def init_deploy_form(key, cfg: P2MConvConfig):
    """Beyond-paper: initialize directly in deploy parameterization
    (trainable w ∈ [−1,1] and shift), so no fold approximation exists."""
    import jax

    k = cfg.kernel
    fan_in = k * k * cfg.in_channels
    w = jax.random.uniform(
        key, (fan_in, cfg.out_channels), minval=-1.0, maxval=1.0
    ) * (3.0 / fan_in) ** 0.5
    return {"w": w.astype(np.float32), "shift": jnp.zeros((cfg.out_channels,), jnp.float32)}
