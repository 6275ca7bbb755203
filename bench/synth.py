"""Seeded synthetic inputs: visual-wake-words frames.

A copy of the repository's generator (``data/vww_synthetic.py``), kept
here so that a change to the program cannot change the benchmark's
inputs; ``tests/test_bench_yardstick.py`` pins its checksums.  Every image is (H, W, 3) float32 in [0, 1].
"""
from __future__ import annotations

import numpy as np


def _figure_mask(h, w, rng):
    cy = rng.uniform(0.35, 0.65) * h
    cx = rng.uniform(0.25, 0.75) * w
    scale = rng.uniform(0.15, 0.35) * min(h, w)
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    head = ((yy - (cy - 1.1 * scale)) ** 2 + (xx - cx) ** 2) / (0.45 * scale) ** 2
    torso = ((yy - cy) ** 2 / (1.4 * scale) ** 2
             + (xx - cx) ** 2 / (0.7 * scale) ** 2)
    return np.exp(-np.maximum(np.minimum(head, torso) - 1.0, 0.0) * 4.0)


def _background(h, w, rng):
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    g = (rng.uniform(-1, 1) * yy / h + rng.uniform(-1, 1) * xx / w)
    stripes = 0.15 * np.sin(2 * np.pi * (xx * rng.uniform(0.02, 0.1)
                                         + rng.uniform(0, 1)))
    blob = np.zeros((h, w), np.float32)
    for _ in range(rng.integers(0, 4)):
        by, bx = rng.uniform(0, h), rng.uniform(0, w)
        r = rng.uniform(0.05, 0.2) * min(h, w)
        blob += 0.3 * np.exp(-(((yy - by) ** 2 + (xx - bx) ** 2) / r**2))
    return 0.4 + 0.2 * g + stripes + blob


def vww_batch(image_size: int, batch: int, seed: int, step: int):
    """``{"images": (batch, H, W, 3), "labels": (batch,) int32}``: half
    positives on average, a person-like figure on a textured background;
    deterministic in (seed, step)."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step]))
    h = w = image_size
    images = np.empty((batch, h, w, 3), np.float32)
    labels = rng.integers(0, 2, batch).astype(np.int32)
    for i in range(batch):
        bg = _background(h, w, rng)
        img = np.stack([bg * rng.uniform(0.7, 1.3) for _ in range(3)], -1)
        if labels[i]:
            m = _figure_mask(h, w, rng)
            color = rng.uniform(0.3, 1.0, 3).astype(np.float32)
            img = img * (1 - 0.8 * m[..., None]) + m[..., None] * color
        img += rng.normal(0, 0.03, img.shape)
        images[i] = np.clip(img, 0.0, 1.0)
    return {"images": images, "labels": labels}
