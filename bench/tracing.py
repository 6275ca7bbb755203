"""Profiler traces: capture, a compact plain form, and the reduction to
device busy time, per-operation and per-program device time, and idle
gaps attributed to what the host was doing.

The compact form keeps, of the profiler's XSpace, the device planes'
operation and program lines, and adds the host spans the benchmark
recorded (`run.Run.span`, names starting ``bench.``), as plain JSON:

    {"devices": {plane: {"ops": [[instruction, start_ns, dur_ns], ...],
                         "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}

`bench/data/trace_small.json.gz`, a few launches recorded on a TPU v5e,
is in this form, and the reduction's test reads it.
"""
from __future__ import annotations

import bisect
import contextlib
import gzip
import json
import shutil
import tempfile
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@contextlib.contextmanager
def capture(run):
    """Trace the block into a fresh temporary directory when ``run.trace``
    is set; yields a callable that returns the compact form once the
    block has ended, with the host spans the run recorded meanwhile."""
    if not run.trace:
        yield lambda: None
        return
    import jax

    tmp = Path(tempfile.mkdtemp(prefix="bench_trace_"))
    out: dict = {}
    # No host tracer: at its lowest level it still records the runtime's
    # per-chunk events of each host-to-device transfer, millions a
    # second, which slowed a serving launch twentyfold.  The benchmark's
    # own spans come from `run.span` instead.
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    run.spans = []
    try:
        with jax.profiler.trace(str(tmp), profiler_options=opts):
            yield lambda: out.get("trace")
        out["trace"] = compact(tmp, run.spans)
    finally:
        run.spans = None
        shutil.rmtree(tmp, ignore_errors=True)


def _op_name(text: str) -> str:
    """An operation event is named by its HLO text, ``%name = ...``;
    keep the instruction's name (a Pallas kernel's is its own)."""
    return text.split(" = ", 1)[0].lstrip("%")


def base_name(op: str) -> str:
    """``p2m_conv_pallas.3`` → ``p2m_conv_pallas``."""
    head, _, tail = op.rpartition(".")
    return head if head and tail.isdigit() else op


def _device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and "NON_CORE" not in name


def compact(log_dir: Path, spans=()) -> dict:
    """The compact form of the profile under ``log_dir``; ``spans`` are
    host spans ``[name, start_ns, dur_ns]`` on the wall clock, placed on
    the profile's clock by its recorded start time."""
    import jax

    files = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError(f"no profile written under {log_dir}")
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    devices, start = {}, None
    for plane in data.planes:
        if _device_plane(plane.name):
            d = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    d[key] = [[_op_name(e.name), int(e.start_ns),
                               int(e.duration_ns)] for e in line.events]
            devices[plane.name] = d
        start = dict(plane.stats).get("profile_start_time", start)
    if spans and start is None:
        raise RuntimeError("the profile records no start time")
    host = sorted(([n, t - int(start), d] for n, t, d in spans),
                  key=lambda e: e[1])
    return {"devices": devices, "host": host}


def load(path: Path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ------------------------------------------------------------ reduction


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


class Reduction:
    """Numbers from one compact trace, inside the window that the host
    span ``bench.window`` marks (the whole trace if there is none)."""

    def __init__(self, trace: dict, window_span: str = "bench.window"):
        self.trace = trace
        self.devices = trace["devices"]
        if not self.devices:
            raise ValueError("the trace has no TPU device plane")
        spans = [e for e in trace["host"] if e[0] == window_span]
        if spans:
            self.lo, self.hi = spans[0][1], spans[0][1] + spans[0][2]
        else:
            evs = [e for d in self.devices.values() for e in d["ops"]]
            self.lo = min(e[1] for e in evs)
            self.hi = max(e[1] + e[2] for e in evs)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def ops(self, plane: str):
        return [e for e in self.devices[plane]["ops"]
                if self.lo <= e[1] < self.hi]

    def busy_intervals(self, plane: str):
        return _union(_clip(((e[1], e[1] + e[2]) for e in
                             self.devices[plane]["ops"]), self.lo, self.hi))

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        tot = [sum(e - s for s, e in self.busy_intervals(p))
               for p in self.devices]
        return sum(tot) / len(tot) / 1e9

    def op_seconds(self, match=lambda name: True) -> tuple[int, float]:
        """(count, summed device seconds) of the window's operations
        whose name satisfies ``match``, averaged over the chips."""
        n = s = 0
        for p in self.devices:
            evs = [e for e in self.ops(p) if match(e[0])]
            n += len(evs)
            s += sum(e[2] for e in evs)
        k = len(self.devices)
        return n // k, s / k / 1e9

    def module_seconds(self, match) -> tuple[int, float]:
        """(calls, summed device seconds) of the window's compiled
        programs whose name satisfies ``match``, averaged over chips."""
        n = s = 0
        for p, d in self.devices.items():
            evs = [e for e in d["modules"]
                   if self.lo <= e[1] < self.hi and match(e[0])]
            n += len(evs)
            s += sum(e[2] for e in evs)
        k = len(self.devices)
        return n // k, s / k / 1e9

    def alignment(self) -> str:
        """How the host spans sit on the device clock: from the first
        ``bench.step`` or ``bench.feed`` span to the first program after
        it (a few ms when the clocks agree)."""
        first = [e for e in self.trace["host"]
                 if e[0] in ("bench.step", "bench.feed")]
        mods = sorted(e[1] for d in self.devices.values()
                      for e in d["modules"])
        if not first or not mods:
            return "no step span or no program to align"
        t = first[0][1]
        after = [m for m in mods if m >= t]
        gap = (after[0] - t) / 1e6 if after else float("nan")
        return (f"first host step span at {t / 1e6:.3f} ms, the next "
                f"program {gap:.3f} ms later; programs begin at "
                f"{mods[0] / 1e6:.3f} ms")

    def top_ops(self, k: int = 10) -> list[list]:
        tot: dict[str, float] = {}
        for p in self.devices:
            for name, _, dur in self.ops(p):
                tot[name] = tot.get(name, 0.0) + dur / 1e9
        n = len(self.devices)
        return [[name, s / n] for name, s in
                sorted(tot.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """Idle device time inside the window, summed by the innermost
        host span (``bench.*``) that covers each gap's midpoint; gaps
        that no span covers go to ``"unattributed"``."""
        host = sorted(e for e in self.trace["host"] if e[0] != "bench.window")
        host.sort(key=lambda h: h[1])
        starts = [h[1] for h in host]
        tot: dict[str, float] = {}
        for p in self.devices:
            busy = self.busy_intervals(p)
            edges = [self.lo] + [x for iv in busy for x in iv] + [self.hi]
            for s, e in zip(edges[::2], edges[1::2]):
                if e <= s:
                    continue
                mid = (s + e) / 2
                name = "unattributed"
                # the latest-starting span that still covers mid
                for j in range(bisect.bisect_right(starts, mid) - 1,
                               max(-1, bisect.bisect_right(starts, mid) - 9),
                               -1):
                    if host[j][1] + host[j][2] > mid:
                        name = host[j][0]
                        break
                tot[name] = tot.get(name, 0.0) + (e - s) / 1e9
        n = len(self.devices)
        return [[name, s / n] for name, s in
                sorted(tot.items(), key=lambda kv: -kv[1])[:k]]
