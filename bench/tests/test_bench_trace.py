"""The trace reduction, on three serving launches recorded on a TPU v5e
(`bench/data/trace_small.json.gz`, P²M frames at batch 8)."""
from __future__ import annotations

import pytest

from bench_tiny import REPO

from bench import readers, tracing

TRACE = REPO / "bench" / "data" / "trace_small.json.gz"


@pytest.fixture(scope="module")
def red():
    return tracing.Reduction(tracing.load(TRACE))


def test_window_and_busy_time(red):
    assert red.window_s == pytest.approx(1.298553619)
    # busy is the union of operation intervals, never their sum
    assert red.busy_s() == pytest.approx(0.028251118)
    n, s = red.op_seconds()
    assert n == 1839 and s >= red.busy_s()
    assert red.busy_s() <= red.window_s


def test_step_program_and_kernel_are_found(red):
    calls, secs = red.module_seconds(lambda n: "jit_forward" in n)
    assert calls == 3 and secs == pytest.approx(0.028254565)
    ctx = {"reduction": red}
    assert readers.kernel_calls(ctx, "p2m_conv_pallas") == (
        3, pytest.approx(0.002089472))
    assert readers.kernel_calls(ctx, "p2m_conv_pallas_gated") is None


def test_idle_gaps_are_attributed_to_host_spans(red):
    gaps = dict(red.idle_gaps())
    assert set(gaps) <= {"bench.step", "bench.submit", "bench.idle",
                         "unattributed"}
    assert sum(gaps.values()) == pytest.approx(red.window_s - red.busy_s())
    top = red.top_ops(3)
    assert top[0][1] >= top[1][1] >= top[2][1]


def test_metric_readers_on_the_recorded_trace(red):
    from bench_tiny import harness

    cfg = harness._json(REPO / "bench" / "configs" / "p2m_vww.json")
    ctx = {"reduction": red, "cfg": cfg, "slots": 8,
           "device_kind": "TPU v5 lite"}
    read = lambda m: harness.load_module(
        REPO / "bench" / "metrics" / f"{m}.py", f"t_{m}").read(ctx)
    assert read("step_device_ms.frames") == pytest.approx(28.254565 / 3)
    idle = read("idle_share.frames")
    assert idle == pytest.approx(100 * (1 - 0.028251118 / 1.298553619))
    roof = read("p2m_conv_roofline.frames")
    assert 0 < roof < 100
    mfu = read("mfu.frames")
    assert 0 < mfu < 100


@pytest.mark.parametrize("op,kernel,hit", [
    ("p2m_conv_pallas.1", "p2m_conv_pallas", True),
    ("jvp_jit_p2m_conv_pallas__", "p2m_conv_pallas", True),
    ("transpose_jvp_jit_p2m_bwd_dw_pallas___", "p2m_bwd_dw_pallas", True),
    ("p2m_conv_pallas_gated.1", "p2m_conv_pallas", False),
    ("p2m_conv_pallas_gated.1", "p2m_conv_pallas_gated", True)])
def test_kernel_names_under_autodiff(op, kernel, hit):
    trace = {"devices": {"/device:TPU:0": {"ops": [[op, 10, 5]],
                                            "modules": []}}, "host": []}
    ctx = {"reduction": tracing.Reduction(trace)}
    assert (readers.kernel_calls(ctx, kernel) is not None) == hit


@pytest.mark.parametrize("text,name", [
    ("%p2m_conv_pallas.1 = f32[900,112,128]{2,1,0} custom-call(...)",
     "p2m_conv_pallas.1"),
    ("%fusion.893 = f32[8,112,112,16] fusion(...)", "fusion.893")])
def test_operation_names(text, name):
    assert tracing._op_name(text) == name
    assert tracing.base_name(name) == name.rsplit(".", 1)[0]
