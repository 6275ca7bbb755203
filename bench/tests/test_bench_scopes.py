"""Device time by model part (`bench/scopes.py`): the program's
`named_scope`s reach the compiled step's op_names, sort into disjoint
parts, and the five part readers add up to the step's operation time."""
from __future__ import annotations

import contextlib
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import REPO, harness, tiny_cell

from bench import readers, scopes, tracing

PARTS = scopes.PARTS
STEP = "jit_step(1234)"


def _reader(part):
    name = f"{part}_ms.train"
    return harness.load_module(REPO / "bench" / "metrics" / f"{name}.py",
                               f"t_{name}").read


@pytest.fixture(scope="module")
def tiny_step():
    """The tiny training cell's step as the benchmark builds it, its
    first state and batch."""
    cell = tiny_cell("p2m_vww.train_b32")
    run = types.SimpleNamespace(cell=cell, seed=2**31 + 11)
    _, _, step, state = cell.runner.build(run)
    batch = cell.runner.make_batches(cell.cfg, cell.traffic, run.seed)[0]
    return cell, run, step, state, batch


def test_tiny_train_step_carries_every_scope(tiny_step):
    _, _, step, state, batch = tiny_step
    smap = scopes.scope_map(step, state, batch)
    parts = {scopes.partition(v) for v in smap.values()}
    assert parts == set(PARTS)
    paths = {scopes.scope_path(v) for v in smap.values()}
    for want in ("fwd:p2m_stem", "bwd:p2m_stem", "fwd:backbone/block0",
                 "bwd:backbone/block0", "fwd:backbone/head",
                 "fwd:classifier", "fwd:loss", "bwd:loss", "fwd:optimizer"):
        assert want in paths, want
    # Of the instructions that come from a traced operation (XLA's own
    # layout copies and rewrites carry no op_name), 90 % sit in a scope.
    traced = [v for v in smap.values() if v.startswith("jit(")]
    scoped = [v for v in traced if scopes.partition(v) != "unscoped"]
    assert len(traced) > 1000
    assert len(scoped) >= 0.9 * len(traced)


def test_custom_vjp_scopes_reach_the_compiled_gradient():
    from repro.core.pixel_model import default_pixel_model
    from repro.kernels.p2m_conv import ops

    model = default_pixel_model()
    rng = np.random.default_rng(3)
    images = jnp.asarray(rng.random((2, 10, 10, 3)), jnp.float32)
    w0 = jnp.asarray(rng.uniform(-1, 1, (75, 8)), jnp.float32)
    shift = jnp.zeros((8,), jnp.float32)

    def loss(w, im):
        return ops.p2m_conv(im, w, shift, model, interpret=True,
                            bwd_impl="pallas").sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        w0, images).compile().as_text()
    paths = {scopes.scope_path(v) for v in re.findall(r'op_name="([^"]*)"',
                                                      text)}
    for want in ("fwd:p2m_conv_fwd", "bwd:p2m_conv_bwd",
                 "bwd:p2m_conv_bwd/im2col", "bwd:p2m_conv_bwd/dx_dw",
                 "bwd:p2m_conv_bwd/col2im"):
        assert want in paths, want


@pytest.mark.parametrize("op_name,part", [
    ("jit(step)/jvp(p2m_stem)/p2m_conv_fwd/jit(p2m_conv_pallas)/pallas_call",
     "stem_fwd"),
    ("jit(step)/jvp(p2m_stem)/reduce_sum", "stem_fwd"),
    ("jit(step)/jvp(p2m_stem)/transpose", "stem_fwd"),
    ("jit(step)/transpose(jvp(p2m_stem))/mul", "stem_bwd"),
    ("jit(step)/transpose(jvp(p2m_stem))/p2m_conv_bwd/im2col/jvp()/slice",
     "stem_bwd"),
    ("jit(f)/p2m_conv_bwd/col2im/reshape", "stem_bwd"),
    ("jit(step)/jvp(backbone/block3)/conv_general_dilated", "backbone"),
    ("jit(step)/transpose(jvp(backbone/head))/mul", "backbone"),
    ("jit(step)/transpose(jvp(classifier))/dot_general", "backbone"),
    ("jit(step)/jvp(loss)/jit(take_along_axis)/gather", "update"),
    ("jit(step)/optimizer/add", "update"),
    ("jit(step)/add", "unscoped"),
    ("state['params']['block0']['dw']['w']", "unscoped"),
    ("jit(step)/jvp(stem)/conv_general_dilated", "unscoped"),
    ("", "unscoped")])
def test_partition(op_name, part):
    assert scopes.partition(op_name) == part


def test_entry_scopes_reads_the_entry_computation_only():
    text = "\n".join([
        "HloModule jit_step",
        "%fused (p: f32[2]) -> f32[2] {",
        '  %inner = f32[2]{0} negate(%p), metadata={op_name="jit(step)/x"}',
        "}",
        "ENTRY %main.1 (a: f32[2]) -> (f32[2], f32[2]) {",
        '  %a.1 = f32[2]{0} parameter(0), metadata={op_name="a"}',
        '  %fusion.3 = f32[2]{0:T(256)} fusion(%a.1), kind=kLoop, '
        'calls=%fused, metadata={op_name="jit(step)/jvp(loss)/neg" '
        'stack_frame_id=2}',
        "  %copy.7 = f32[2]{0} copy(%fusion.3)",
        "  ROOT %tuple.1 = (f32[2]{0}, f32[2]{0}) tuple(%fusion.3, %copy.7)",
        "}"])
    assert scopes.entry_scopes(text) == {
        "a.1": "a", "fusion.3": "jit(step)/jvp(loss)/neg", "copy.7": "",
        "tuple.1": ""}


# ------------------------------------------- the readers, synthetic trace

MAP = {"conv.1": "jit(step)/jvp(p2m_stem)/p2m_conv_fwd/pallas_call",
       "slice.2": "jit(step)/transpose(jvp(p2m_stem))/p2m_conv_bwd/im2col/"
                  "jvp()/slice",
       "fusion.3": "jit(step)/transpose(jvp(backbone/block0))/mul",
       "fusion.4": "jit(step)/optimizer/add",
       "copy.5": ""}
SYNTH_WANT = {"stem_fwd": 3.0, "stem_bwd": 4.5, "backbone": 2.0,
              "update": 1.0, "unscoped": 0.5}


def _synthetic_trace():
    """Two steps of 11 ms of operations each inside a 100-ms window, and
    operations outside every step that the readers must leave out."""
    ms = 1_000_000
    ops, modules = [], []
    for t0 in (10 * ms, 50 * ms):
        modules.append([STEP, t0, 20 * ms])
        t = t0
        for name, dur in (("conv.1", 3), ("slice.2", 2), ("slice.2", 2.5),
                          ("fusion.3", 2), ("fusion.4", 1), ("copy.5", .5)):
            ops.append([name, t, int(dur * ms)])
            t += int(dur * ms)
    modules.append(["jit_other(7)", 80 * ms, 5 * ms])
    ops += [["fusion.3", 80 * ms, 4 * ms],   # another program
            ["conv.1", 35 * ms, 4 * ms]]     # between the steps
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": [["bench.window", 0, 100 * ms]]}


def test_part_readers_on_a_synthetic_trace():
    red = tracing.Reduction(_synthetic_trace())
    ctx = {"reduction": red, "scopes": MAP}
    got = {p: _reader(p)(ctx) for p in PARTS}
    assert got == pytest.approx(SYNTH_WANT)
    # disjoint parts: they add up to the steps' operation time, per step
    calls, ops = scopes.step_ops(red)
    assert calls == 2
    assert sum(got.values()) == pytest.approx(
        sum(o[2] for o in ops) / calls / 1e6)
    assert sum(o[2] for o in ops) < red.op_seconds()[1] * 1e9


@pytest.mark.parametrize("ctx", [
    {"reduction": None},
    {"reduction": "no step", "scopes": MAP},
    {"reduction": "trace", "scopes": {}},
    {"reduction": "trace", "scopes": {k: "jit(step)/add" for k in MAP}}],
    ids=["no trace", "no step", "empty map", "map without scopes"])
def test_part_readers_read_nothing_without_a_step_or_a_scope(ctx):
    trace = _synthetic_trace()
    if ctx["reduction"] == "no step":
        for d in trace["devices"].values():
            d["modules"] = [m for m in d["modules"] if m[0] != STEP]
    if ctx["reduction"] is not None:
        ctx = dict(ctx, reduction=tracing.Reduction(trace))
    assert all(_reader(p)(dict(ctx)) is None for p in PARTS)


@pytest.mark.parametrize("op,kernel", [
    ("p2m_conv_pallas.1", "p2m_conv_pallas"),
    ("p2m_bwd_dw_pallas.1", "p2m_bwd_dw_pallas"),
    ("jvp_jit_p2m_conv_pallas__.2", "p2m_conv_pallas"),
    ("transpose_jvp_jit_p2m_bwd_dw_pallas___.2", "p2m_bwd_dw_pallas")])
def test_kernel_names_under_scopes(op, kernel):
    """Under the scopes the kernels' instructions are named after the
    kernels themselves; the roofline readers find both namings."""
    trace = {"devices": {"/device:TPU:0": {"ops": [[op, 10, 5]],
                                            "modules": []}}, "host": []}
    ctx = {"reduction": tracing.Reduction(trace)}
    assert readers.kernel_calls(ctx, kernel) == (1, pytest.approx(5e-9))


# ------------------------------------------------ scopes change no result


def test_scopes_change_no_result(tiny_step, monkeypatch):
    """The tiny step traced with every scope and traced with the scopes'
    context managers made no-ops give bitwise the same loss and
    parameters."""
    cell, run, step, state, batch = tiny_step
    scoped = step(state, batch)
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        _, _, plain_step, plain_state = cell.runner.build(run)
        compiled = plain_step.lower(plain_state, batch).compile()
    plain = compiled(plain_state, batch)
    text = compiled.as_text()
    assert "p2m_stem" not in text and "backbone/block0" not in text
    for a, b in zip(jax.tree.leaves(scoped), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_readers_build_the_map_of_the_run_step(tiny_step, capsys):
    """Without a map in hand the readers compile the step the run built,
    from its configuration, and report on stderr once."""
    cell = tiny_step[0]
    ctx = {"reduction": tracing.Reduction(_synthetic_trace()),
           "cfg": cell.cfg, "batch": cell.traffic["batch"]}
    got = {p: _reader(p)(ctx) for p in PARTS}
    assert sum(got.values()) == pytest.approx(11.0)
    assert {scopes.partition(v) for v in ctx["scopes"].values()} == set(PARTS)
    err = capsys.readouterr().err
    assert err.count("[scopes] parts ") == 1
    assert "[autotune] autotune.traced_default" in err


def test_one_step_keeps_the_middle_step():
    from bench import record_scopes

    rec = record_scopes.one_step(_synthetic_trace())
    d = rec["devices"]["/device:TPU:0"]
    assert d["modules"] == [[STEP, 50_000_000, 20_000_000]]
    assert rec["host"] == [["bench.window", 50_000_000, 20_000_000]]
    assert [o[0] for o in d["ops"]] == ["conv.1", "slice.2", "slice.2",
                                        "fusion.3", "fusion.4", "copy.5"]
    ctx = {"reduction": tracing.Reduction(rec), "scopes": MAP}
    assert scopes.step_parts(ctx) == pytest.approx(SYNTH_WANT)


# ------------------------------------------ one step recorded on the chip

RECORDED = REPO / "bench" / "data" / "train_step_scopes.json.gz"
# ms of the recorded step by part, as PERF.md quotes them
RECORDED_MS = {"stem_fwd": 18.827036, "stem_bwd": 65.253704,
               "backbone": 11.931816, "update": 0.028948,
               "unscoped": 2.536472}


def test_part_readers_on_a_step_recorded_on_the_chip():
    """`bench/data/train_step_scopes.json.gz`: one `jit_step` of
    `p2m_vww.train_b32` traced on a TPU v5e, with its scope map."""
    rec = tracing.load(RECORDED)
    red = tracing.Reduction(rec)
    ctx = {"reduction": red, "scopes": rec["scopes"]}
    got = {p: _reader(p)(ctx) for p in PARTS}
    assert got == pytest.approx(RECORDED_MS, abs=1e-6)
    # every operation of the step is in the map, and the parts add up
    # to the step's operation time
    calls, ops = scopes.step_ops(red)
    assert calls == 1 and all(o[0] in rec["scopes"] for o in ops)
    assert sum(got.values()) == pytest.approx(sum(o[2] for o in ops) / 1e6)
    assert got["unscoped"] <= 0.1 * sum(got.values())
    # the ten largest operations of the ledger's breakdown before the scopes
    want = {"slice.6": "stem_bwd", "slice.7": "stem_bwd",
            "slice.14": "stem_bwd", "slice.15": "stem_bwd",
            "copy.61": "stem_bwd", "copy.62": "stem_bwd",
            "copy.63": "stem_bwd", "copy.64": "stem_bwd",
            "copy.50": "stem_fwd", "reshape.0": "stem_fwd"}
    assert {k: scopes.partition(rec["scopes"][k]) for k in want} == want
    # the kernels, renamed under the scopes, are still found
    assert readers.kernel_calls(ctx, "p2m_conv_pallas")[0] == 1
    assert readers.kernel_calls(ctx, "p2m_bwd_dw_pallas")[0] == 1
