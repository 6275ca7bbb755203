"""The training cell's input pipeline (`bench/runners/train.py`): each
batch is placed on the device as 8-bit images and scaled to float32
there, bit for bit as the host scales the copy that the reference and
the controls read; set-up refuses a run where the two differ, and the
window names each step's dispatch with a ``bench.step`` span."""
from __future__ import annotations

import contextlib

import jax
import numpy as np
import pytest

from bench_tiny import harness, tiny_cell

from bench import synth
from bench.run import Run

CELL = "p2m_vww.train_b32"
SEED = 2**31 + 15


class Recorded(Exception):
    pass


def _bits(a):
    return np.asarray(a).view(np.uint32)


def _quantized(cfg, tr, seed):
    """The host formula, written out: render, round to 8 bits, scale."""
    out = []
    for i in range(tr["distinct_batches"]):
        b = synth.vww_batch(cfg["image_size"], tr["batch"], seed, i)
        codes = np.round(b["images"] * 255).astype(np.uint8)
        out.append(codes.astype(np.float32) * np.float32(1 / 255))
    return out


@pytest.fixture(scope="module")
def traced():
    """One tiny traced run of the training cell, with what the feed
    placed on the device and the batches the reference compared with."""
    cell = tiny_cell(CELL)
    placed, compared = [], []
    compare = cell.runner.compare

    def put(x, *a, **k):
        if isinstance(x, dict) and "images" in x:
            placed.append({n: (v.dtype, v.nbytes, v.shape)
                           for n, v in x.items()})
        return real_put(x, *a, **k)

    def spy(cell_, params, bn, batches, got, *a, **k):
        compared.extend(batches)
        return compare(cell_, params, bn, batches, got, *a, **k)

    real_put = jax.device_put
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "device_put", put)
        mp.setattr(cell.runner, "compare", spy)
        out = cell.runner.run(Run(cell, SEED, 1.0, True, None))
    return cell, out, placed, compared


def test_traffic_asks_for_8_bit_images():
    assert tiny_cell(CELL).traffic["images"] == "uint8"


def test_device_scaling_matches_host_formula_over_all_codes():
    from bench.runners import train

    codes = np.arange(256, dtype=np.uint8)
    got = jax.jit(train.scale_images)(codes)
    assert got.dtype == np.float32
    want = codes.astype(np.float32) * np.float32(1 / 255)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    host = train.host_images({"images": codes})["images"]
    np.testing.assert_array_equal(_bits(host), _bits(want))
    assert got[0] == 0.0 and got[255] == 1.0


def test_device_scaling_matches_on_a_rendered_batch():
    from bench.runners import train

    cfg, tr = {"image_size": 20}, {"batch": 2, "distinct_batches": 2,
                                   "images": "uint8"}
    placed = train.render(cfg, tr, SEED)
    host = train.make_batches(cfg, tr, SEED)
    feed = train.Feed(placed, lambda name: contextlib.nullcontext())
    for b in host:
        got = feed()
        assert got["images"].dtype == np.float32
        np.testing.assert_array_equal(_bits(got["images"]),
                                      _bits(b["images"]))
        np.testing.assert_array_equal(np.asarray(got["labels"]), b["labels"])
    assert feed.calls == 2


@pytest.mark.parametrize("images", ["float32", "uint16"])
def test_other_image_types_are_refused(images):
    from bench.runners import train

    tr = {"batch": 2, "distinct_batches": 1, "images": images}
    with pytest.raises(harness.BenchError, match="only 'uint8'"):
        train.render({"image_size": 20}, tr, SEED)


def test_feed_places_uint8_images(traced):
    cell, out, placed, _ = traced
    b, size = cell.traffic["batch"], cell.cfg["image_size"]
    feeds = cell.traffic["checked_steps"] + out["data"]["steps"]
    assert len(placed) == feeds
    for p in placed:
        assert p["images"] == (np.dtype(np.uint8), b * size * size * 3,
                               (b, size, size, 3))
        assert p["labels"] == (np.dtype(np.int32), 4 * b, (b,))


def test_reference_batches_are_the_quantized_float32_ones(traced):
    cell, _, _, compared = traced
    want = _quantized(cell.cfg, cell.traffic, SEED)
    assert len(compared) == len(want)
    for b, w in zip(compared, want):
        assert b["images"].dtype == np.float32
        np.testing.assert_array_equal(_bits(b["images"]), _bits(w))


def test_control_batches_are_the_quantized_float32_ones(monkeypatch):
    cell = tiny_cell(CELL)
    seen = []

    def record(cell_, params, bn, batches, n, **kw):
        seen.extend(batches)
        raise Recorded

    monkeypatch.setattr(cell.runner, "reference_steps", record)
    with pytest.raises(Recorded):
        cell.runner.control(Run(cell, SEED, 0.0, False, None))
    want = _quantized(cell.cfg, cell.traffic, SEED)
    assert len(seen) == cell.traffic["checked_steps"]
    for b, w in zip(seen, want):
        np.testing.assert_array_equal(_bits(b["images"]), _bits(w))


def test_traced_run_records_one_step_span_per_step(traced):
    _, out, _, _ = traced
    data = out["data"]
    names = [h[0] for h in data["trace"]["host"]]
    assert data["steps"] > 0
    assert names.count("bench.step") == data["steps"]
    assert names.count("bench.feed") == data["steps"] == data["feeds"]
    # each step's span starts after its batch's feed has ended
    host = sorted(data["trace"]["host"], key=lambda h: h[1])
    feeds = [h for h in host if h[0] == "bench.feed"]
    steps = [h for h in host if h[0] == "bench.step"]
    for f, s in zip(feeds, steps):
        assert s[1] >= f[1] + f[2]


def test_tiny_cell_reads_correct(traced):
    _, out, _, _ = traced
    assert out["checks"].correct, out["checks"].summary()
    assert out["e2e"]["train_images_per_s"] > 0


def test_setup_fails_when_the_device_scaling_differs(monkeypatch):
    cell = tiny_cell(CELL)
    off = lambda codes: codes.astype(np.float32) * np.float32(1 / 256)
    monkeypatch.setattr(cell.runner, "scale_images", off)
    with pytest.raises(harness.BenchError, match="differs from the host"):
        cell.runner.run(Run(cell, SEED, 1.0, False, None))


def test_dispatch_reader_reads_the_host_time_of_the_step_calls(traced):
    cell, out, _, _ = traced
    data = out["data"]
    read = cell.reader("dispatch_ms.train")
    assert 0 < data["dispatch_s"] < data["wall_s"]
    assert read(data) == pytest.approx(data["dispatch_s"] / data["steps"]
                                       * 1e3)
    assert read({"steps": 0, "dispatch_s": 1.0}) is None
