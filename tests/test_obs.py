"""Observability subsystem (DESIGN.md §13): deterministic tick-domain
tracing, the unified metrics registry, structured logging, and the
kernel/compile counters.

The two hard contracts from §13.3 are pinned here on real scheduler
machinery (dummy adapters, no models):

* tracing is **bit-for-bit free when disabled** — an engine with
  ``tracer=None`` and one with a disabled tracer replay a seeded chaos
  trace to identical ledgers and summaries;
* tracing is **deterministic when enabled** — two fresh tracers over
  the same seeded chaos (through a mixed-cadence event-driven front
  door) export byte-identical Perfetto JSON that passes schema
  validation.
"""
import dataclasses
import json
import logging

import pytest

from repro.launch.serve import FrontDoor
from repro.obs import (
    Counter,
    MetricsRegistry,
    REQUEST_TID_BASE,
    TickHistogram,
    Tracer,
    counted_lru_cache,
    default_registry,
    format_record,
    structured,
    tick_percentiles,
    validate_trace_events,
)
from repro.serving import (
    FaultInjector,
    FaultPlan,
    ScheduledRequest,
    SlotEngine,
)

# ------------------------------------------------------------- dummy adapters
# (mirrors tests/test_faults.py: tiny SlotEngine adapters, no models)


@dataclasses.dataclass
class _Req(ScheduledRequest):
    uid: int = 0


@dataclasses.dataclass
class _ReqB(ScheduledRequest):
    uid: int = 0


@dataclasses.dataclass
class _StreamReq(ScheduledRequest):
    uid: int = 0
    length: int = 1
    observed: list = dataclasses.field(default_factory=list)


class _OneTickEngine(SlotEngine):
    request_type = _Req

    def _launch(self, active):
        return None

    def _absorb(self, i, req, result):
        return True


class _OneTickEngineB(_OneTickEngine):
    request_type = _ReqB


class _StreamEngine(SlotEngine):
    request_type = _StreamReq

    def _launch(self, active):
        return None

    def _absorb(self, i, req, result):
        req.observed.append(self.tick)
        return len(req.observed) >= req.length


def _chaos_traffic(n=12):
    """Seeded mixed traffic with staggered arrivals and deadlines."""
    reqs = [_Req(uid=i, arrival_tick=i // 3, deadline_tick=i + 20)
            for i in range(n)]
    reqs += [_ReqB(uid=100 + i, arrival_tick=i // 2) for i in range(n // 2)]
    return reqs


def _chaos_engine(tracer=None, registry=None, n_slots=2):
    inj = FaultInjector(FaultPlan(launch_error_rate=0.2, stuck_rate=0.15,
                                  seed=7),
                        registry=registry)
    return _StreamEngine(n_slots, max_queue=4, evict="deadline",
                         max_serve_ticks=6, launch_retries=1, faults=inj,
                         tracer=tracer, registry=registry)


def _chaos_run(tracer=None, registry=None):
    eng = _chaos_engine(tracer=tracer, registry=registry)
    reqs = [_StreamReq(uid=i, length=1 + i % 3, arrival_tick=i // 2,
                       deadline_tick=i + 25) for i in range(10)]
    eng.run(reqs, max_ticks=200)
    return eng


# ------------------------------------------------------ structured logging


def test_format_record_deterministic():
    a = format_record("p2m_event", zulu=1, alpha="x")
    b = format_record("p2m_event", alpha="x", zulu=1)
    assert a == b  # field order never leaks into the record
    rec = json.loads(a)
    assert rec["event"] == "p2m_event"
    assert rec["schema"] == 1
    assert " " not in a.split('"alpha"')[0]  # compact separators


def test_structured_logs_and_counts(caplog):
    reg = default_registry()
    before = reg.counter("log.obs_test_event").value
    log = logging.getLogger("test_obs")
    with caplog.at_level(logging.WARNING, logger="test_obs"):
        line = structured(log, "obs_test_event", level=logging.WARNING,
                          detail="hello")
    assert json.loads(line)["detail"] == "hello"
    assert any("obs_test_event" in r.message for r in caplog.records)
    assert reg.counter("log.obs_test_event").value == before + 1


# ------------------------------------------------------- metrics registry


def test_counter_monotone():
    c = Counter()
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_tick_histogram_matches_serving_estimator():
    h = TickHistogram()
    vals = [1, 2, 3, 5, 8, 13, 21]
    for v in vals:
        h.observe(v)
    assert h.percentiles() == tick_percentiles(vals)
    s = h.summary()
    assert s["count"] == len(vals)
    assert s["sum"] == float(sum(vals))


def test_registry_scopes_deterministic_and_views_weakref():
    reg = MetricsRegistry()
    e1, e2 = _OneTickEngine(1, registry=reg), _OneTickEngine(1, registry=reg)
    assert e1.metrics_scope == "_OneTickEngine#0"
    assert e2.metrics_scope == "_OneTickEngine#1"
    snap = reg.snapshot()
    assert set(snap["components"]) == {e1.metrics_scope, e2.metrics_scope}
    assert set(snap["components"][e1.metrics_scope]) == {"latency", "health"}
    del e2  # dead components drop out silently — the registry never
    import gc

    gc.collect()  # leaks an engine (weakref views, DESIGN.md §13.2)
    assert set(reg.snapshot()["components"]) == {e1.metrics_scope}


def test_registry_snapshot_matches_legacy_summaries():
    """The registry is a *view* over the legacy dict APIs: the snapshot
    and a direct summary call must read the same numbers."""
    reg = MetricsRegistry()
    eng = _chaos_run(registry=reg)
    snap = reg.snapshot()
    comp = snap["components"][eng.metrics_scope]
    assert comp["latency"] == eng.latency_summary()
    assert comp["health"] == eng.health()
    # the fault injector publishes its tallies into the same registry
    inj_scopes = [s for s in snap["components"] if s.startswith("FaultInjector")]
    assert inj_scopes
    assert snap["components"][inj_scopes[0]]["faults"] == eng.faults.summary()
    # tick histograms observe each completion with the exact ledger values
    hq = snap["tick_histograms"][f"{eng.metrics_scope}.queue_ticks"]
    hs = snap["tick_histograms"][f"{eng.metrics_scope}.serve_ticks"]
    s = eng.latency_summary()
    assert hq["count"] == hs["count"] == s["served"]
    assert hq["p50"] == s["p50_queue_ticks"]
    assert hs["p50"] == s["p50_serve_ticks"]


def test_counted_lru_cache_counts_and_survives_reset():
    reg = default_registry()
    calls = []

    @counted_lru_cache("obs_test_fn")
    def fn(x):
        calls.append(x)
        return x * 2

    h = reg.counter("compile_cache.obs_test_fn.hits")
    m = reg.counter("compile_cache.obs_test_fn.misses")
    h0, m0 = h.value, m.value
    assert fn(3) == 6 and fn(3) == 6 and fn(4) == 8
    assert calls == [3, 4]
    assert (h.value - h0, m.value - m0) == (1, 2)
    assert fn.cache_info().currsize == 2  # lru_cache API passes through
    # a registry reset (test isolation) must not orphan the cache:
    # counters are re-fetched per call, so counting just starts over
    reg.reset()
    fn(3)
    assert reg.counter("compile_cache.obs_test_fn.hits").value == 1


# --------------------------------------------------- autotuner observability


def test_autotune_counters_and_decision_record():
    from repro.kernels.p2m_conv import tune

    reg = default_registry()
    hit = reg.counter("autotune.cache_hit")
    miss = reg.counter("autotune.cache_miss")
    h0, m0 = hit.value, miss.value
    key = ("obs_test", 1, 2)
    tune._CACHE.pop(key, None)
    try:
        r = tune.autotune(key, [(8, 8), (16, 16)],
                          lambda c: None, iters=1,
                          vmem=lambda c: c[0] * c[1] * 4)
        assert miss.value - m0 == 1
        # second serve of the same key is a cache hit — the counter the
        # acceptance criterion pins non-zero on cached paths
        assert tune.autotune(key, [(8, 8), (16, 16)], lambda c: None) is r
        assert hit.value - h0 == 1
        recs = [d for d in tune.decision_records() if d["kind"] == "obs_test"]
        assert len(recs) == 1
        d = recs[0]
        assert d["best"] in ([8, 8], [16, 16])
        assert d["candidates"] == [[8, 8], [16, 16]]
        assert d["vmem_bytes"] == [256, 1024]
        assert d["n_viable"] == 2
    finally:
        tune._CACHE.pop(key, None)


# ------------------------------------------------------------------ tracer


def test_disabled_tracer_is_bitwise_free():
    """tracer=None, Tracer(enabled=False), and an enabled tracer all
    replay the same seeded chaos to identical ledgers — tracing never
    touches schedule state (§13.3)."""
    base = _chaos_run(tracer=None, registry=MetricsRegistry())
    off = Tracer(enabled=False)
    dis = _chaos_run(tracer=off, registry=MetricsRegistry())
    on = _chaos_run(tracer=Tracer(), registry=MetricsRegistry())
    assert off.events == []  # a disabled tracer records nothing

    def ledgers(e):
        return {
            "completed": [r.uid for r in e.completed],
            "failed": [(r.uid, r.failure) for r in e.failed],
            "evicted": [r.uid for r in e.evicted],
            "rejected": [r.uid for r in e.rejected],
            "observed": {r.uid: r.observed for r in e.completed},
            "latency": {k: v for k, v in e.latency_summary().items()
                        if not k.endswith("_us") and k != "mean_launch_us"},
        }

    assert ledgers(base) == ledgers(dis) == ledgers(on)


def _traced_door_replay(tracer):
    """One seeded chaos replay through a mixed-cadence event-driven
    front door: two modalities, tick_cost 1 and 2 (exercises the clock
    scaling), launch faults and stuck slots (exercises the containment
    events)."""
    inj = FaultInjector(FaultPlan(launch_error_rate=0.15, stuck_rate=0.1,
                                  seed=3),
                        registry=MetricsRegistry())
    a = _OneTickEngine(2, max_queue=3, evict="deadline", max_serve_ticks=5,
                       launch_retries=1, faults=inj,
                       registry=MetricsRegistry())
    b = _OneTickEngineB(1, max_queue=2, tick_cost=2,
                        registry=MetricsRegistry())
    door = FrontDoor(tracer=tracer, fast=a, slow=b,
                     registry=MetricsRegistry())
    door.run(_chaos_traffic(), max_ticks=300)
    return door


def test_enabled_tracer_deterministic_and_valid():
    tr1, tr2 = Tracer(), Tracer()
    _traced_door_replay(tr1)
    _traced_door_replay(tr2)
    e1, e2 = tr1.export(), tr2.export()
    assert e1 == e2  # byte-identical across independent replays
    payload = json.loads(e1)
    assert validate_trace_events(payload) == []
    names = {ev["name"] for ev in payload["traceEvents"]}
    # the span taxonomy's core members all appear on real chaos
    assert {"submit", "queue", "admit", "serve", "complete",
            "engine_tick", "door_tick"} <= names
    assert names & {"launch", "fail", "watchdog"}  # chaos left a mark
    # track labels follow the door's registration names
    labels = {ev["args"]["name"] for ev in payload["traceEvents"]
              if ev["ph"] == "M"}
    assert {"door", "fast", "slow"} <= labels


def test_tracer_scale_maps_engine_ticks_to_door_clock():
    tr = Tracer()
    eng = object()
    tr.attach(eng, "e")
    tr.set_scale(eng, 3)
    tr.tick_instant(eng, "engine_tick", 5)
    tr.tick_span(eng, "serve", 2, 4, 1000)
    inst, span = tr.events
    assert inst["ts"] == 15  # engine tick 5 fired at door tick 15
    assert (span["ts"], span["dur"]) == (6, 12)


def test_tracer_wall_opt_in_is_outside_byte_identity():
    """wall=True may add wall-clock args; the default export of two
    identical runs stays byte-identical (the contract the bench gate
    pins on the full chaos stack)."""
    runs = []
    for _ in range(2):
        tr = Tracer()
        eng = _OneTickEngine(1, tracer=tr, registry=MetricsRegistry())
        eng.run([_Req(uid=0)])
        runs.append(tr.export())
    assert runs[0] == runs[1]
    assert "wall_us" not in runs[0]


# -------------------------------------------------------- trace validation


def _ev(name, ph="i", pid=1, tid=0, ts=0, **kw):
    e = {"name": name, "ph": ph, "pid": pid, "tid": tid, "ts": ts}
    if ph == "i":
        e["s"] = "t"
    e.update(kw)
    return e


def test_validate_catches_orphaned_terminal():
    probs = validate_trace_events([
        _ev("complete", tid=REQUEST_TID_BASE + 5, ts=4)])
    assert any("orphaned" in p for p in probs)


def test_validate_catches_double_terminal():
    tid = REQUEST_TID_BASE
    probs = validate_trace_events([
        _ev("submit", tid=tid), _ev("complete", tid=tid, ts=2),
        _ev("evict", tid=tid, ts=3)])
    assert any("second terminal" in p for p in probs)


def test_validate_catches_nonmonotone_ts():
    probs = validate_trace_events([
        _ev("engine_tick", ts=5), _ev("engine_tick", ts=3)])
    assert any("monotone" in p for p in probs)


def test_validate_catches_unknown_name_and_malformed():
    probs = validate_trace_events([
        _ev("made_up_event"),
        {"name": "serve", "ph": "X", "pid": 1, "tid": 0, "ts": 0,
         "dur": -2},
        {"name": "admit", "ph": "i", "pid": "one", "tid": 0, "ts": 0}])
    assert any("taxonomy" in p for p in probs)
    assert any("dur" in p for p in probs)
    assert any("pid" in p for p in probs)


def test_validate_accepts_clean_payload():
    tid = REQUEST_TID_BASE + 1
    assert validate_trace_events({"traceEvents": [
        _ev("submit", tid=tid, ts=0),
        _ev("queue", ph="X", tid=tid, ts=0, dur=2),
        _ev("admit", tid=tid, ts=2),
        _ev("serve", ph="X", tid=tid, ts=2, dur=3),
        _ev("complete", tid=tid, ts=5)]}) == []


# ------------------------------------------------------ undrained reporting


def test_undrained_warning_names_uids_and_ledgers():
    """drive(on_undrained='warn') reports per-ledger undrained counts
    *and* the offending uids — a count without uids is a deadlock an
    operator cannot chase."""
    inj = FaultInjector(FaultPlan(stuck_uids=(7,)),
                        registry=MetricsRegistry())
    eng = _StreamEngine(1, faults=inj, registry=MetricsRegistry())
    eng.submit(_StreamReq(uid=7, length=1))
    eng.submit(_StreamReq(uid=9, length=1))
    with pytest.warns(RuntimeWarning, match="undrained") as rec:
        eng.run(max_ticks=5)
    msg = next(str(w.message) for w in rec if "undrained" in str(w.message))
    assert "1 queued" in msg and "1 slots occupied" in msg
    assert "queued=1 uids=[9]" in msg
    assert "occupied=1 uids=[7]" in msg


def test_undrained_warning_reports_per_engine_behind_door():
    a = _OneTickEngine(1, registry=MetricsRegistry())
    b = _StreamEngine(1, faults=FaultInjector(FaultPlan(stuck_uids=(3,)),
                                              registry=MetricsRegistry()),
                      registry=MetricsRegistry())
    door = FrontDoor(fast=a, slow=b, registry=MetricsRegistry())
    door.submit(_StreamReq(uid=3, length=1))
    with pytest.warns(RuntimeWarning, match=r"slow: .*occupied=1 uids=\[3\]"):
        door.run(max_ticks=5)
