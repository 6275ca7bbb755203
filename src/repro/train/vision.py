"""Training step for the paper's VWW pipeline (MobileNetV2 ± P²M stem).

Keeps BN running stats in the train state (paper trains with standard
BN and SGD+momentum, §5.1).

Scaling story (DESIGN.md §7): the step is written to be SPMD-safe under
a data-parallel plan — the image batch carries a ``"batch"`` logical
constraint, every reduction in the model (loss mean, BN batch stats) is
a global reduction XLA lowers to the matching collectives, and the
optional int8 error-feedback gradient compression is the same transform
the LM trainer uses (`train.compression`), so the compressed VWW step is
semantically identical between one device and a DP mesh (per-tensor
quantization scales are computed on the *globally reduced* gradient; the
residual float-reassociation differences and their interaction with the
clip nonlinearities are quantified in DESIGN.md §7).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.models.mobilenetv2 import MNV2Config, apply_mnv2
from repro.optim.optimizers import Optimizer
from repro.core.pixel_model import PixelModel
from repro.parallel import shard
from repro.train.compression import compress_grads_int8_ef


def vww_train_state(params, bn, opt_state, *, step: int = 0,
                    grad_compression: str | None = None) -> dict:
    """Canonical VWW train-state dict.

    When compression is on, the error-feedback accumulator is seeded with
    zeros up front so the state *structure* is identical on step 0 and
    step N — which is what lets ``jax.jit`` take one
    (in_shardings == out_shardings) tree instead of a step-0 special case.
    """
    state = {"params": params, "bn": bn, "opt": opt_state,
             "step": jnp.asarray(step, jnp.int32)}
    if grad_compression == "int8_ef":
        state["extras"] = {"ef_error": jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)}
    elif grad_compression is not None:
        raise ValueError(f"unknown grad_compression {grad_compression!r}")
    return state


def vww_train_shardings(state: dict, batch: dict, plan):
    """(state shardings, batch shardings) for jitting the VWW step under a
    data-parallel plan: every state leaf replicated (MNV2 param stacks are
    small — DESIGN.md §7), batch dim-0 split over the data axes."""
    from repro.parallel.sharding_utils import batch_shardings, replicated_tree
    return replicated_tree(state, plan), batch_shardings(batch, plan)


def softmax_ce(logits, labels):
    lse = jax.nn.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return (lse - true).mean()


def make_vww_train_step(cfg: MNV2Config, optimizer: Optimizer,
                        pixel_model: PixelModel | None = None,
                        *, grad_compression: str | None = None) -> Callable:
    """Build the VWW train step.

    grad_compression: None | "int8_ef" — int8 quantization with error
      feedback on the (globally reduced) gradients; the EF accumulator
      rides in ``state["extras"]["ef_error"]`` exactly like the LM
      trainer's, so checkpointing and sharding treat both the same way.
    """
    def step(state: dict, batch: dict):
        images = shard(batch["images"], "batch", None, None, None)
        labels = shard(batch["labels"], "batch")

        def loss_fn(params):
            logits, new_bn = apply_mnv2(params, state["bn"], images,
                                        cfg, pixel_model, train=True)
            with jax.named_scope("loss"):
                ce = softmax_ce(logits, labels)
                acc = (logits.argmax(-1) == labels).mean()
            return ce, (new_bn, acc)

        (loss, (new_bn, acc)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["params"])

        extras = dict(state.get("extras", {}))
        with jax.named_scope("optimizer"):
            if grad_compression == "int8_ef":
                grads, extras["ef_error"] = compress_grads_int8_ef(
                    grads, extras.get("ef_error"))
            elif grad_compression is not None:
                raise ValueError(
                    f"unknown grad_compression {grad_compression!r}")
            new_params, new_opt = optimizer.update(
                grads, state["opt"], state["params"], state["step"])
        new_state = {"params": new_params, "bn": new_bn, "opt": new_opt,
                     "step": state["step"] + 1}
        if extras:
            new_state["extras"] = extras
        return new_state, {"loss": loss, "acc": acc}

    return step


def make_vww_eval(cfg: MNV2Config, pixel_model: PixelModel | None = None):
    def evaluate(params, bn_state, batch, p2m_deploy=None):
        logits, _ = apply_mnv2(params, bn_state, batch["images"], cfg,
                               pixel_model, train=False, p2m_deploy=p2m_deploy)
        acc = (logits.argmax(-1) == batch["labels"]).mean()
        return float(acc)

    return evaluate
