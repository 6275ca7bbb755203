"""The yardstick kept with the benchmark agrees with the program's own
arithmetic at the paper geometry, and the copied generator gives the
inputs pinned when the benchmark was defined."""
from __future__ import annotations

import hashlib

import numpy as np
import pytest

from bench_tiny import REPO, harness

from bench import synth, yardstick


def _cfg(name):
    return harness._json(REPO / "bench" / "configs" / f"{name}.json")


def test_flops_equal_the_layer_census():
    from repro.configs import p2m_vww
    from repro.models.mobilenetv2 import layer_census

    prog = p2m_vww.CONFIG
    want = sum(c.macs for c in layer_census(prog, include_in_pixel=True))
    total, stem = yardstick.frame_macs(_cfg("p2m_vww"))
    assert total == want
    assert stem == layer_census(prog, include_in_pixel=True)[0].macs
    assert stem == 112 * 112 * 75 * 8
    assert yardstick.forward_flops(_cfg("p2m_vww"), 3) == 6 * want


def test_train_flops_count_no_input_gradient_in_the_pixel_layer():
    cfg = _cfg("p2m_vww")
    total, stem = yardstick.frame_macs(cfg)
    assert yardstick.train_flops(cfg, 2) == 2 * (6 * (total - stem) + 4 * stem)


def test_pixel_layer_costs_at_paper_geometry():
    cfg = _cfg("p2m_vww")
    flops, byts = yardstick.pixel_fwd_cost(cfg, 8)
    m = 8 * 112 * 112
    assert flops == 2 * m * 3 * 75 * 8 + m * 75 * 2
    assert byts == 4 * (8 * 560 * 560 * 3 + m * 8)
    pk = yardstick.peaks("TPU v5 lite")
    best, bound = yardstick.roofline_s(flops, byts, pk)
    assert bound == "bandwidth" and best == byts / 819e9


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        yardstick.peaks("TPU v99")


def _sha(a):
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()


def test_vww_generator_checksum():
    b = synth.vww_batch(560, 2, 2**31 + 7, 3)
    assert _sha(b["images"]) == ("12724889e039ecad2b140bbd946a30ef"
                                 "6d8c4bed0ccaa54e085ebb9f69ca1ac1")
    assert _sha(b["labels"]) == ("64ed86b909d6d0502b64b28db0ea1272"
                                 "ffb358e20e9b1d88b63ccb07fa900cf5")


def test_fixed_gaps_give_every_seed_the_same_work():
    a = harness.fixed_gaps(500, 10.0, 1)
    b = harness.fixed_gaps(500, 10.0, 2**31 + 99)
    assert a[0] == b[0] == 0.0 and len(a) == len(b) == 500
    ga = np.sort(np.diff(np.append(a, 10.0)))
    gb = np.sort(np.diff(np.append(b, 10.0)))
    np.testing.assert_allclose(ga, gb, rtol=1e-9, atol=1e-12)
    assert not np.array_equal(a, b)
