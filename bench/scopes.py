"""Device time of the training step by model part, read back from the
program's own `jax.named_scope`s.

JAX writes each operation's name stack into the ``op_name`` metadata of
the compiled HLO: the program's scopes (`p2m_stem`, `backbone/block{i}`,
`backbone/head`, `classifier`, `loss`, `optimizer`; inside the P²M
custom VJP `p2m_conv_fwd` and `p2m_conv_bwd/{im2col,dx_dw,col2im}`),
wrapped in ``jvp(...)`` on the forward pass and ``transpose(jvp(...))``
on the backward pass.  A fusion carries its root's.  The profiler names
each device operation by its instruction, so the compiled entry
computation's instruction → op_name map (`scope_map`) names the trace in
the program's terms, on the device's own clock.

`partition` puts every op_name in exactly one part of the step
(`PARTS`); `step_parts` sums the device time of the operations inside
the window's `jit_step` programs by part, per step.  The parts are
disjoint, so they add up to the step's summed operation time.
"""
from __future__ import annotations

import bisect
import contextlib
import re
import sys
import types

from bench import harness, readers

PARTS = ("stem_fwd", "stem_bwd", "backbone", "update", "unscoped")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


# ------------------------------------------------------------ the map


def entry_scopes(hlo_text: str) -> dict[str, str]:
    """{instruction: op_name} of the entry computation of a compiled
    module's text; an instruction without op_name maps to ``""``."""
    out: dict[str, str] = {}
    inside = False
    for line in hlo_text.splitlines():
        if not inside:
            inside = line.startswith("ENTRY ")
            continue
        if line.startswith("}"):
            break
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = op.group(1) if op else ""
    return out


def scope_map(jitted, *args) -> dict[str, str]:
    """{instruction: op_name} of ``jitted``'s compiled entry computation
    for ``args`` (arrays or `jax.ShapeDtypeStruct`s)."""
    return entry_scopes(jitted.lower(*args).compile().as_text())


def partition(op_name: str) -> str:
    """The part of the step an instruction with this op_name belongs to:
    the in-pixel layer forward (``stem_fwd``) or backward (``stem_bwd``,
    with the P²M conv's custom backward), the backbone and classifier
    both ways (``backbone``), loss and optimizer (``update``), or
    ``unscoped``."""
    names = set(re.split(r"[/()]", op_name))
    if "p2m_conv_bwd" in names or ("p2m_stem" in names
                                   and "transpose(" in op_name):
        return "stem_bwd"
    if "p2m_stem" in names:
        return "stem_fwd"
    if "backbone" in names or "classifier" in names:
        return "backbone"
    if "loss" in names or "optimizer" in names:
        return "update"
    return "unscoped"


def scope_path(op_name: str) -> str:
    """``fwd:`` or ``bwd:`` and the program's scopes of an op_name, e.g.
    ``bwd:p2m_stem/p2m_conv_bwd/im2col``: transforms unwrapped, jitted
    functions and the primitive left out; ``-`` for none."""
    segs = op_name.split("/")[:-1]
    keep = []
    for seg in segs:
        if seg.startswith(("jit(", "pjit(")):
            continue
        inner = re.sub(r"^(?:\w+\()*|\)*$", "", seg)
        if inner:
            keep.append(inner)
    side = "bwd" if "transpose(" in op_name else "fwd"
    return f"{side}:{'/'.join(keep)}" if keep else "-"


# ------------------------------------------------ the benchmark's step


@contextlib.contextmanager
def _tuner_answers(served: list):
    """Records what the P²M block tuner serves each kernel meanwhile:
    (kind, shape arguments, blocks)."""
    from repro.kernels.p2m_conv import tune

    saved = tune.get_conv_blocks, tune.get_matmul_blocks

    def wrap(kind, fn):
        def call(*args, **kw):
            blocks = fn(*args, **kw)
            served.append((kind, args, blocks))
            return blocks
        return call

    tune.get_conv_blocks = wrap("conv", saved[0])
    tune.get_matmul_blocks = wrap("matmul", saved[1])
    try:
        yield
    finally:
        tune.get_conv_blocks, tune.get_matmul_blocks = saved


def train_step_scopes(cfg: dict, batch: int) -> tuple[dict, list]:
    """The scope map of the training step that `runners/train.build`
    makes for ``cfg`` at ``batch``, for the state the window passes it,
    compiled as the run compiled it (the persistent cache answers), and
    the tuner's answers while it traced."""
    import jax
    import jax.numpy as jnp

    bench = harness.BENCH
    ref = harness.load_module(bench / "references" / f"{cfg['reference']}.py",
                              f"bench_reference_{cfg['reference']}")
    train = harness.load_module(bench / "runners" / "train.py",
                                "bench_runner_train")
    run = types.SimpleNamespace(
        seed=0, cell=types.SimpleNamespace(cfg=cfg, reference=ref))
    _, _, step, state = train.build(run)
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    # The window's state is the step's own output, whose leaves are not
    # weakly typed as some of the initial state's are: lower for that.
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), state)
    size = cfg["image_size"]
    data = {"images": jax.ShapeDtypeStruct((batch, size, size, 3),
                                           jnp.float32, sharding=one),
            "labels": jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one)}
    served: list = []
    with _tuner_answers(served):
        smap = scope_map(step, state, data)
    return smap, served


# ------------------------------------------------------------ the trace


def step_ops(red) -> tuple[int, list]:
    """(calls, operations) of the window's `jit_step` programs over all
    chips: each operation ``[instruction, start_ns, dur_ns]`` that starts
    inside a `jit_step` interval on its chip's XLA Modules line."""
    calls, ops = 0, []
    for plane, d in red.devices.items():
        steps = sorted((m[1], m[1] + m[2]) for m in d["modules"]
                       if red.lo <= m[1] < red.hi and "jit_step" in m[0])
        calls += len(steps)
        if not steps:
            continue
        starts = [s for s, _ in steps]
        for op in red.ops(plane):
            i = bisect.bisect_right(starts, op[1]) - 1
            if i >= 0 and op[1] < steps[i][1]:
                ops.append(op)
    return calls, ops


def _tuner_counts() -> dict:
    from repro.obs.metrics import default_registry

    c = default_registry().snapshot()["counters"]
    return {k: c.get(k, 0.0) for k in ("autotune.traced_default",
                                       "autotune.cache_hit",
                                       "autotune.cache_miss")}


def step_parts(ctx) -> dict | None:
    """{part: device ms per step} of the window's training steps, or
    None without a trace, an operation in a `jit_step` program, or a
    program scope in the step's map.  Kept in ``ctx``: every part's
    reader shares one reading, and the report on stderr is printed
    once."""
    if "step_parts" not in ctx:
        ctx["step_parts"] = _read_parts(ctx)
    return ctx["step_parts"]


def _read_parts(ctx) -> dict | None:
    red = readers.reduction(ctx)
    if red is None:
        return None
    calls, ops = step_ops(red)
    if not ops:
        return None
    if "scopes" not in ctx:
        counts = _tuner_counts()
        ctx["scopes"], served = train_step_scopes(ctx["cfg"], ctx["batch"])
        print_tuner(counts, served)
    smap = ctx["scopes"]
    if not any(partition(v) != "unscoped" for v in smap.values()):
        return None  # a program without scopes: nothing to read
    per_op: dict[str, float] = {}
    for name, _, dur in ops:
        per_op[name] = per_op.get(name, 0.0) + dur / 1e6 / calls
    parts = dict.fromkeys(PARTS, 0.0)
    for name, ms in per_op.items():
        parts[partition(smap.get(name, ""))] += ms
    print_report(per_op, smap, parts)
    return parts


def part_ms(ctx, part: str) -> float | None:
    parts = step_parts(ctx)
    return None if parts is None else parts[part]


# ------------------------------------------------------------ stderr


def _say(line: str):
    print(line, file=sys.stderr, flush=True)


def print_tuner(counts: dict, served: list):
    """The tuner's counters after the run, and the blocks it served each
    P²M kernel of the step."""
    _say("[autotune] " + " ".join(f"{k} {v:g}" for k, v in counts.items()))
    from repro.kernels.p2m_conv.conv import (conv_out_spatial,
                                             default_conv_blocks)

    for kind, args, blocks in served:
        shape = args[:7] if kind == "conv" else args[:3]
        line = f"[autotune] {kind} {shape} -> {blocks}"
        if kind == "conv" and blocks[0] is None:
            b, h, w, c, n, k, s, coeffs = args[:8]
            bh, bn = default_conv_blocks(
                b, conv_out_spatial(h, k, s), conv_out_spatial(w, k, s), n,
                len(coeffs[0]) * k * c)
            line += f" (the kernel's heuristic: block_h {bh}, block_n {bn})"
        _say(line)


def print_report(per_op: dict, smap: dict, parts: dict, top: int = 15):
    """The instructions with the most device time and their op_names, the
    self time of each scope path, and the parts, all in ms per step."""
    total = sum(parts.values())
    missing = sum(ms for name, ms in per_op.items() if name not in smap)
    _say(f"[scopes] step ops {total:.3f} ms a step; instructions missing "
         f"from the map {missing:.3f} ms")
    for name, ms in sorted(per_op.items(), key=lambda kv: -kv[1])[:top]:
        _say(f"[scopes] op {name} {ms:.3f} ms {smap.get(name, '')!r}")
    paths: dict[str, float] = {}
    for name, ms in per_op.items():
        path = scope_path(smap.get(name, ""))
        paths[path] = paths.get(path, 0.0) + ms
    for path, ms in sorted(paths.items(), key=lambda kv: -kv[1]):
        _say(f"[scopes] self {path} {ms:.3f} ms")
    _say("[scopes] parts " + " ".join(f"{p} {ms:.3f}" for p, ms in
                                      parts.items())
         + f" ms; unscoped {100 * parts['unscoped'] / total:.2f} %")
