"""Plain float32 reference for MobileNetV2-VWW, with or without the P²M
first layer (arXiv:2203.04737 §3-§5; MobileNetV2: arXiv:1801.04381).

Written from the papers and the configuration file alone, in straight
`jax.numpy`: no kernel, no cache, no batching tricks, and nothing
imported from the system under test.  Parameter and state trees use the
system's layout, so the weights the benchmark makes from a seed can be
handed to both sides.

Every contraction runs in float32 at ``Precision.HIGHEST``.  With
``operands="float8_e4m3fn"`` the operands of each backbone, head and
classifier contraction are first rounded to that type (the precision
control: one step below the bfloat16 operands the configuration states).
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

# (expansion t, out channels c, repeats n, first-block stride s)
BLOCKS = ((1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
          (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1))


def block_schedule(cfg: dict) -> list[tuple[int, int, int, int]]:
    out = []
    for idx, (t, c, n, s) in enumerate(BLOCKS):
        c = int(round(c * cfg["width"]))
        if idx == len(BLOCKS) - 1 and cfg["last_block_div"] > 1:
            c = max(8, c // cfg["last_block_div"])
        out.append((t, c, n, s))
    return out


def head_channels(cfg: dict) -> int:
    return int(round(cfg["head_channels"] * max(1.0, cfg["width"])))


def stem_spatial(cfg: dict) -> int:
    i = cfg["image_size"]
    if cfg["variant"] == "p2m":
        return (i - cfg["p2m"]["kernel"]) // cfg["p2m"]["stride"] + 1
    return (i + 1) // 2


HIGHEST = jax.lax.Precision.HIGHEST


def _pow2(k):
    """2**k for whole k in [-126, 127], exactly, from its bits."""
    return jax.lax.bitcast_convert_type((k + 127) << 23, jnp.float32)


def round_to(a, dtype: str):
    """``a`` (float32) rounded to the nearest value of the float format
    ``dtype``, ties to even, subnormals kept, overflow not modelled.
    Plain arithmetic, not a cast there and back: the TPU's compiler drops
    such a pair as excess precision (on a v5e the round trip returned its
    input unrounded), and the control would not be the precision named."""
    info = jnp.finfo(dtype)
    _, e = jnp.frexp(a)
    k = jnp.clip(jnp.maximum(e - 1, info.minexp) - info.nmant, -126, 126)
    return jnp.round(a * _pow2(-k)) * _pow2(k)


def _rounder(operands: str):
    """Rounds a contraction's operands to ``operands`` on the forward
    pass; gradients pass straight through in float32 (a float8 cotangent
    would flush the small gradients to zero, which is no precision)."""
    if operands == "float32":
        return lambda a: a
    return lambda a: a + jax.lax.stop_gradient(round_to(a, operands) - a)


# ------------------------------------------------------------------ init


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * (2.0 / fan_in) ** 0.5


def init(key, cfg: dict):
    """(params, state) in the system's tree layout, from ``key`` alone.

    Convolutions are He-normal.  BN state starts at mean 0, variance 1;
    a BN that feeds a ReLU6 inside the blocks or the stem gets the scale
    ``init.relu_bn_gamma`` and the shift ``init.relu_bn_beta``, so that
    once its statistics are set (`calibrate`) a share of its units sits
    on each clamp of the ReLU6 and the rest in between; the head's BN
    gets ``init.head_bn_beta``; the in-pixel layer's BN gets
    ``init.p2m_bn_gamma`` and ``init.p2m_bn_beta``, which put a share of
    the ADC's counts on each end of its range too."""
    ini = cfg["init"]
    keys = iter(jax.random.split(key, 256))
    params, state = {}, {}
    gamma, beta = ini["relu_bn_gamma"], ini["relu_bn_beta"]
    bn = lambda c: {"gamma": jnp.full((c,), gamma),
                    "beta": jnp.full((c,), beta)}
    lin_bn = lambda c: {"gamma": jnp.ones((c,)), "beta": jnp.zeros((c,))}
    bst = lambda c: {"bn": {"mean": jnp.zeros((c,)), "var": jnp.ones((c,))}}
    if cfg["variant"] == "p2m":
        p = cfg["p2m"]
        k, ci, co = p["kernel"], p["in_channels"], p["out_channels"]
        params["stem"] = {
            "theta": jax.random.uniform(next(keys), (k, k, ci, co),
                                        minval=-1.0, maxval=1.0)
            * (3.0 / (k * k * ci)) ** 0.5,
            "bn_gamma": jnp.full((co,), ini["p2m_bn_gamma"]),
            "bn_beta": jnp.full((co,), ini["p2m_bn_beta"])}
        state["stem"] = {"bn_mean": jnp.zeros((co,)),
                         "bn_var": jnp.ones((co,))}
        cin = co
    else:
        c0 = int(round(cfg["first_channels"] * cfg["width"]))
        params["stem"] = {"w": _normal(next(keys), (3, 3, 3, c0), 27),
                          "bn": bn(c0)}
        state["stem"] = bst(c0)
        cin = c0
    b = 0
    for t, c, n, _ in block_schedule(cfg):
        for _ in range(n):
            hid = cin * t
            blk, st = {}, {}
            if t != 1:
                blk["expand"] = {"w": _normal(next(keys), (1, 1, cin, hid),
                                              cin), "bn": bn(hid)}
                st["expand"] = bst(hid)
            blk["dw"] = {"w": _normal(next(keys), (3, 3, 1, hid), 9),
                         "bn": bn(hid)}
            st["dw"] = bst(hid)
            blk["project"] = {"w": _normal(next(keys), (1, 1, hid, c), hid),
                              "bn": lin_bn(c)}
            st["project"] = bst(c)
            params[f"block{b}"], state[f"block{b}"] = blk, st
            b += 1
            cin = c
    ch = head_channels(cfg)
    params["head"] = {"w": _normal(next(keys), (1, 1, cin, ch), cin),
                      "bn": {"gamma": jnp.ones((ch,)),
                             "beta": jnp.full((ch,), ini["head_bn_beta"])}}
    state["head"] = bst(ch)
    params["fc"] = {"w": jax.random.normal(next(keys), (ch, cfg["num_classes"]))
                    * ini["fc_std"],
                    "b": jnp.zeros((cfg["num_classes"],))}
    return params, state


# ------------------------------------------------------------------ layers


def _conv(x, w, q, stride=1, groups=1):
    return jax.lax.conv_general_dilated(
        q(x), q(w), (stride, stride), "SAME", feature_group_count=groups,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)


def _bn(x, p, s, mode: str, axes=(0, 1, 2), eps=1e-5, momentum=0.9):
    """mode: "eval" (running stats), "train" (batch stats, running stats
    updated with ``momentum``), "calib" (batch stats become the state)."""
    if mode == "eval":
        mean, var, new = s["mean"], s["var"], s
    else:
        mean, var = x.mean(axis=axes), x.var(axis=axes)
        new = ({"mean": mean, "var": var} if mode == "calib" else
               {"mean": momentum * s["mean"] + (1 - momentum) * mean,
                "var": momentum * s["var"] + (1 - momentum) * var})
    return (x - mean) / jnp.sqrt(var + eps) * p["gamma"] + p["beta"], new


def _relu6(x):
    return jnp.clip(x, 0.0, 6.0)


def _patches(images, k):
    """(B, H, W, C) → (B, Ho, Wo, k·k·C), (kh, kw, C) fastest-varying;
    stride == kernel, so non-overlapping windows."""
    b, h, w, c = images.shape
    ho, wo = h // k, w // k
    x = images[:, :ho * k, :wo * k].reshape(b, ho, k, wo, k, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, ho, wo, k * k * c)


def pixel_conv(images, w, coeffs):
    """Σ over the receptive field of the fitted pixel function
    g(w, x) = Σ_ij a_ij w^i x^j, with the sign of w applied by the
    double sample: Σ_r sign(w_r) g(|w_r|, x_r).  w: (K, Co)."""
    k = int(round((w.shape[0] // images.shape[-1]) ** 0.5))
    x = _patches(images, k)
    sgn, aw = jnp.sign(w), jnp.abs(w)
    out = 0.0
    for j in range(len(coeffs[0])):
        wj = sum(coeffs[i][j] * sgn * aw ** (i + 1) for i in range(len(coeffs)))
        out = out + jnp.einsum("bhwk,kn->bhwn", x ** (j + 1), wj,
                               precision=HIGHEST)
    return out


def deploy_stem_params(sp, ss, cfg: dict):
    """BN folded into the pixel weights (scale) and the ADC counter
    pre-load (shift), then post-training quantized: weights per output
    channel, symmetric, to ``serve.deploy_quant_bits``; shift to the ADC
    count grid (paper §4.2)."""
    p = cfg["p2m"]
    inv = 1.0 / jnp.sqrt(ss["bn_var"] + p["bn_eps"])
    a = sp["bn_gamma"] * inv
    b = sp["bn_beta"] - sp["bn_gamma"] * ss["bn_mean"] * inv
    w = jnp.clip(sp["theta"], -1.0, 1.0).reshape(-1, p["out_channels"])
    wf = jnp.clip(w * a[None, :], -1.0, 1.0)
    qmax = float(2 ** (cfg["serve"]["deploy_quant_bits"] - 1) - 1)
    scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=0, keepdims=True) / qmax,
                        1e-12)
    q = jnp.clip(jnp.round(wf / scale), -qmax - 1, qmax)
    wq = wf + (q * scale - wf)  # quantize-dequantize, as written in §4.2
    lsb = 1.0 / (2 ** p["n_bits"] - 1)
    return {"w": wq, "shift": jnp.round(b / lsb) * lsb}


def adc_quant(raw, shift, cfg: dict):
    """Single-slope ADC with the CDS counter pre-loaded by the shift:
    integer counts clamped to [0, 2^n − 1], back in volts."""
    n = cfg["p2m"]["n_bits"]
    lsb = 1.0 / (2 ** n - 1)
    counts = jnp.round(raw / lsb) + jnp.round(shift / lsb)
    return jnp.clip(counts, 0.0, float(2 ** n - 1)) * lsb


def stem(params, state, images, cfg: dict, mode: str, q):
    """First layer; ``mode`` as `_bn`.  Eval on the P²M variant is the
    deployed sensor: folded, quantized weights and the counting ADC."""
    if cfg["variant"] == "p2m":
        coeffs = cfg["pixel_model_coeffs"]
        sp, ss = params["stem"], state["stem"]
        if mode == "eval":
            dep = deploy_stem_params(sp, ss, cfg)
            raw = pixel_conv(images, dep["w"], coeffs)
            return adc_quant(raw, dep["shift"], cfg), ss
        p = cfg["p2m"]
        w = jnp.clip(sp["theta"], -1.0, 1.0).reshape(-1, p["out_channels"])
        raw = pixel_conv(images, w, coeffs)
        y, new = _bn(raw, {"gamma": sp["bn_gamma"], "beta": sp["bn_beta"]},
                     {"mean": ss["bn_mean"], "var": ss["bn_var"]}, mode,
                     eps=p["bn_eps"], momentum=p["bn_momentum"])
        full_scale = (2 ** p["n_bits"] - 1) / (2 ** p["n_bits"] - 1)
        return (jnp.clip(y, 0.0, full_scale),
                {"bn_mean": new["mean"], "bn_var": new["var"]})
    x = _conv(images, params["stem"]["w"], q, stride=2)
    x, new = _bn(x, params["stem"]["bn"], state["stem"]["bn"], mode)
    return _relu6(x), {"bn": new}


def backbone(params, state, x, cfg: dict, mode: str, q):
    new = {}
    b, cin = 0, x.shape[-1]
    for t, c, n, s in block_schedule(cfg):
        for i in range(n):
            stride = s if i == 0 else 1
            blk, bst, nst = params[f"block{b}"], state[f"block{b}"], {}
            y = x
            if t != 1:
                y, nst["expand"] = _bn(_conv(y, blk["expand"]["w"], q),
                                       blk["expand"]["bn"],
                                       bst["expand"]["bn"], mode)
                nst["expand"] = {"bn": nst["expand"]}
                y = _relu6(y)
            y, st = _bn(_conv(y, blk["dw"]["w"], q, stride, y.shape[-1]),
                        blk["dw"]["bn"], bst["dw"]["bn"], mode)
            nst["dw"] = {"bn": st}
            y = _relu6(y)
            y, st = _bn(_conv(y, blk["project"]["w"], q), blk["project"]["bn"],
                        bst["project"]["bn"], mode)
            nst["project"] = {"bn": st}
            x = y + x if stride == 1 and cin == c else y
            new[f"block{b}"] = nst
            b += 1
            cin = c
    x, st = _bn(_conv(x, params["head"]["w"], q), params["head"]["bn"],
                state["head"]["bn"], mode)
    new["head"] = {"bn": st}
    return _relu6(x), new


def logits(params, state, images, cfg: dict, mode: str, q=_rounder("float32")):
    x, st0 = stem(params, state, images, cfg, mode, q)
    x, st = backbone(params, state, x, cfg, mode, q)
    x = x.mean(axis=(1, 2))
    out = jnp.dot(q(x), q(params["fc"]["w"]), precision=HIGHEST) \
        + params["fc"]["b"]
    return out, {"stem": st0, **st}


# ------------------------------------------------------------- entry points


class Static:
    """A configuration as a static jit argument: hashed by its JSON."""

    def __init__(self, cfg: dict):
        self.cfg, self._key = cfg, json.dumps(cfg, sort_keys=True)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, Static) and other._key == self._key


@functools.partial(jax.jit, static_argnames=("cfg", "operands"))
def _probs(params, state, images, cfg, operands):
    out, _ = logits(params, state, images, cfg.cfg, "eval", _rounder(operands))
    return jax.nn.softmax(out, axis=-1)


def probs(params, state, images, cfg: dict, operands="float32"):
    """Served class probabilities of the deployed model (eval BN)."""
    return _probs(params, state, images, Static(cfg), operands)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _calibrate(params, state, images, cfg):
    _, new = logits(params, state, images, cfg.cfg, "calib")
    return new


def calibrate(params, state, images, cfg: dict):
    """BN state set to the batch statistics of ``images``, layer by
    layer in one forward, so that the served model is normalised."""
    return _calibrate(params, state, images, Static(cfg))


# --------------------------------------------------------------- training


def _loss(params, state, batch, cfg, mode, q):
    out, new = logits(params, state, batch["images"], cfg, mode, q)
    lse = jax.nn.logsumexp(out, axis=-1)
    true = jnp.take_along_axis(out, batch["labels"][:, None], axis=-1)[:, 0]
    return (lse - true).mean(), new


@functools.partial(jax.jit, static_argnames=("cfg", "operands", "half"))
def _train_step(params, state, mu, batch, cfg, operands, half):
    """One SGD-with-momentum step (mu ← m·mu + g; p ← p − lr·mu) on the
    softmax cross-entropy with batch-statistics BN.  ``half`` keeps only
    the first half of the batch (a planted fault)."""
    cfg = cfg.cfg
    t = cfg["train"]
    if half:
        n = batch["labels"].shape[0] // 2
        batch = jax.tree.map(lambda a: a[:n], batch)
    q = _rounder(operands)
    (loss, new), g = jax.value_and_grad(
        lambda p: _loss(p, state, batch, cfg, "train", q), has_aux=True)(params)
    mu = jax.tree.map(lambda m, gi: t["momentum"] * m + gi, mu, g)
    params = jax.tree.map(lambda p, m: p - t["lr"] * m, params, mu)
    return params, new, mu, loss


def train_step(params, state, mu, batch, cfg: dict, operands="float32",
               half: bool = False):
    """(params, state, mu, loss) after one step from the given ones."""
    return _train_step(params, state, mu, batch, Static(cfg), operands, half)
