"""Each fault a cell can have, planted under an otherwise whole run at a
size a test run holds (the chip check bypassed), must make ``correct``
come out false: a batch half left out, a state left unchanged, an answer
altered, and (where the weights put units on it) the ReLU6 clip dropped."""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from bench_tiny import CELLS, run_tiny, tiny_cell


def _second_half(active):
    return active[len(active) // 2:]


def frames_half_batch(mp):
    """Half of each launch's frames never reach the model (zeros)."""
    from repro.serving.vision import VisionEngine

    orig = VisionEngine._launch

    def launch(self, active):
        lost = {i for i, _ in _second_half(active)}
        return orig(self, [(i, dataclasses.replace(r, image=np.zeros_like(
            r.image)) if i in lost else r) for i, r in active])

    mp.setattr(VisionEngine, "_launch", launch)


def frames_answer_altered(mp):
    """Each answer's classes are swapped where it is produced."""
    from repro.serving.vision import VisionEngine

    orig = VisionEngine._absorb

    def absorb(self, i, req, probs):
        done = orig(self, i, req, probs)
        req.probs = req.probs[::-1].copy()
        return done

    mp.setattr(VisionEngine, "_absorb", absorb)


def _traced_fault(mp, module, name, fn):
    """Replace ``module.name`` inside traced code: compiled programs are
    dropped now and again when the test ends, so that neither the fault
    nor a program compiled without it carries over."""
    import jax

    jax.clear_caches()
    _forward_cache().cache_clear()
    mp.setattr(module, name, fn)


def _forward_cache():
    from repro.serving import vision

    return vision._deploy_forward_for


def relu6_unclipped(mp):
    """Every ReLU6 of the backbone loses its upper clip."""
    import jax.numpy as jnp

    import repro.models.mobilenetv2 as m

    _traced_fault(mp, m, "_relu6", lambda x: jnp.maximum(x, 0.0))


def _wrap_step(mp, wrap):
    import repro.train.vision as tv

    make = tv.make_vww_train_step
    mp.setattr(tv, "make_vww_train_step",
               lambda *a, **k: wrap(make(*a, **k)))


def train_state_unchanged(mp):
    """The step returns the state it was given."""
    _wrap_step(mp, lambda step: lambda s, b: (s, step(s, b)[1]))


def train_half_batch(mp):
    """Half of each batch is left out, the mean taken over the rest."""
    def half(step):
        def f(s, b):
            n = b["labels"].shape[0] // 2
            return step(s, {k: v[:n] for k, v in b.items()})
        return f

    _wrap_step(mp, half)


FAULTS = {
    "frames": [frames_half_batch, frames_answer_altered, relu6_unclipped],
    "train": [train_state_unchanged, train_half_batch],
}
CASES = [(name, f) for name in CELLS
         for f in FAULTS[tiny_cell(name).traffic["kind"]]]


@pytest.mark.parametrize("name,fault", CASES,
                         ids=[f"{n}-{f.__name__}" for n, f in CASES])
def test_planted_fault_is_not_correct(name, fault, monkeypatch):
    import jax

    cell = tiny_cell(name)
    fault(monkeypatch)
    try:
        result, checks = run_tiny(cell)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
        _forward_cache().cache_clear()
    assert not result["correct"], checks.summary()
