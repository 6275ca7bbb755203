"""What every cell shares: the specification, name lookup, the device
check, the compile cache, the set-up clock, compile counting, weights
and inputs from the seed, and the result line.

Everything that belongs to one configuration, one traffic mix, one
per-layer metric or one workload's limits is a file of its own, found by
the name ``BENCHMARK.json`` gives it:

    bench/configs/<config>.json       sizes, engine settings, precision
    bench/references/<reference>.py   the plain reference the config names
    bench/traffic/<traffic>.json      parameters of a mix; its ``kind``
    bench/runners/<kind>.py           names the general generator + runner
    bench/metrics/<metric>.py         one reader per per-layer metric
    bench/limits/<workload>.json      the correctness limits of a cell
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
CACHE_DIR = CHECKOUT / ".jax_cache"


class BenchError(Exception):
    """A run that cannot produce a result (no chip, a bad name)."""


def process_age_s() -> float:
    """Seconds since this process started, from the kernel's record."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


class HostWaits:
    """What may have kept the host from the run over a window: the
    machine's steal time (``/proc/stat``, 10-ms ticks; omitted where it
    cannot be read) and Python's garbage collections.  For stderr."""

    def __init__(self):
        self.gc_s: list[float] = []
        self._gc_t0 = None

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_s.append(time.perf_counter() - self._gc_t0)

    @staticmethod
    def _read() -> dict:
        try:
            with open("/proc/stat") as f:
                steal = int(f.readline().split()[8])
        except (OSError, IndexError, ValueError):
            return {}
        return {"machine_steal_ms": steal * 1e3 / os.sysconf("SC_CLK_TCK")}

    def __enter__(self):
        self._start = self._read()
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on_gc)
        self._end = self._read()

    def describe(self) -> str:
        parts = [f"{k} {self._end[k] - v:.1f}" for k, v in self._start.items()
                 if k in self._end]
        gc_ms = [s * 1e3 for s in self.gc_s]
        parts.append(f"gc {len(gc_ms)} runs {sum(gc_ms):.1f} ms, max "
                     f"{max(gc_ms, default=0.0):.1f}")
        return ", ".join(parts)


# ------------------------------------------------------------ spec lookup


def load_spec(root: Path = CHECKOUT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.exists():
        raise BenchError(f"no {path}")
    return json.loads(path.read_text())


def _json(path: Path) -> dict:
    if not path.exists():
        raise BenchError(f"missing file {path}")
    return json.loads(path.read_text())


def load_module(path: Path, name: str):
    if not path.exists():
        raise BenchError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of the specification and every file it names."""

    def __init__(self, spec: dict, workload: str, bench: Path = BENCH):
        cells = {w["name"]: w for w in spec["workloads"]}
        if workload not in cells:
            raise BenchError(f"unknown workload {workload!r}; known: "
                             f"{sorted(cells)}")
        self.spec, self.bench = spec, bench
        self.workload = cells[workload]
        self.name = workload
        configs = {c["name"]: c for c in spec["configs"]}
        self.config_entry = configs[self.workload["config"]]
        self.cfg = _json(bench.parent / self.config_entry["file"])
        self.traffic = _json(bench / "traffic"
                             / f"{self.workload['traffic']}.json")
        self.limits = _json(bench / "limits" / f"{workload}.json")
        self.reference = load_module(
            bench / "references" / f"{self.cfg['reference']}.py",
            f"bench_reference_{self.cfg['reference']}")
        self.runner = load_module(
            bench / "runners" / f"{self.traffic['kind']}.py",
            f"bench_runner_{self.traffic['kind']}")
        self.chips = int(self.workload["chips"])

    def metrics(self, kind: str) -> list[dict]:
        """The cell's end-to-end (``kind="end_to_end"``) or per-layer
        metrics: those whose ``workloads`` list it, or that have none."""
        return [m for m in self.spec[kind]
                if self.name in m.get("workloads", [self.name])]

    def reader(self, metric: str):
        return load_module(self.bench / "metrics" / f"{metric}.py",
                           f"bench_metric_{metric}").read


# ------------------------------------------------------------ device


def device_info(chips: int) -> dict:
    """The devices JAX found; no TPU, or fewer chips than the cell asks
    for, is an error, never a fallback."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, every
    program kept, so that only a cell's first run there compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # No eviction.  With a size limit, which the environment may set, JAX
    # reads an access-time file beside every entry on each write, and one
    # missing such file makes every later write fail: no program cached.
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


class CompileCounter:
    """Counts XLA compiles: backend compile requests that the persistent
    cache did not answer."""

    REQUEST = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.requests = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == self.REQUEST:
            self.requests += 1

    def _on_event(self, event, **_):
        if event == self.HIT:
            self.hits += 1

    @property
    def count(self) -> int:
        return self.requests - self.hits


# ------------------------------------------------------------ from the seed


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (also beyond 32 bits)."""
    import jax
    import jax.numpy as jnp

    hi, lo = divmod(int(seed), 1 << 32)
    parts = jnp.asarray([hi % (1 << 32), lo], jnp.uint32)
    return jax.jit(lambda p: jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(0), p[0]), p[1]))(parts)


def fixed_gaps(n: int, seconds: float, seed: int):
    """Open-loop Poisson arrivals with the same work for every seed: the
    n gaps are the exponential distribution's n quantiles, scaled to sum
    to ``seconds``; the seed draws only their order.  Returns the n due
    times (s), the first at 0."""
    import numpy as np

    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= seconds / gaps.sum()
    gaps = np.random.default_rng([seed, 0xA77]).permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def percentile(values, q: float) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), q))


# ------------------------------------------------------------ result


def finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


class Checks:
    """Numbers compared against their limits; ``correct`` iff all hold."""

    def __init__(self, limits: dict):
        self.limits = limits
        self.rows: list[tuple[str, float, float]] = []

    def add(self, name: str, value: float):
        limit = float(self.limits[name]["limit"])
        self.rows.append((name, float(value), limit))

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(
            finite(v) and v <= lim for _, v, lim in self.rows)

    def summary(self) -> dict:
        return {n: {"value": v, "limit": lim} for n, v, lim in self.rows}


def emit(result: dict, checks: Checks) -> None:
    """The compared numbers as the last lines of stderr, then the result
    as the last line of stdout, with the checks under the last key."""
    for name, value, limit in checks.rows:
        print(f"[check] {name} {value!r} limit {limit!r} "
              f"{'ok' if finite(value) and value <= limit else 'FAIL'}",
              file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = checks.summary()
    print(json.dumps(result), flush=True)
