"""Observability unit + integration tests: histogram bucketing and
percentile snapshots on a fake clock, span nesting/ordering, the Chrome
trace-event export schema, the per-request flight recorder (span presence
and queue+batch coverage of end-to-end latency), metrics flowing from every
instrumented layer, the engine host's spans inside ``execute`` and their
counts, the spans' profiler annotations on the profiler's clock, and
parity — tracing and metrics change no ids and no scores."""
import gc
import glob
import json
import math
import os
import time

import numpy as np
import pytest

import blend
from repro import obs
from repro.core.lake import synthetic_lake
from repro.obs.metrics import (Histogram, MetricsRegistry, NULL_REGISTRY,
                               NullRegistry)
from repro.obs import trace as otrace
from repro.obs.trace import (NULL_RECORDER, Recorder, Span, chrome_trace,
                             current, recording)
from repro.serve.engine import DiscoveryEngine
from repro.serve.server import DiscoveryServer


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test starts and ends with observability disabled."""
    obs.disable()
    yield
    obs.disable()


# ------------------------------------------------------------------ metrics

def test_histogram_bucket_index_and_edges():
    h = Histogram("t", lo=1e-3, growth=2.0, n_buckets=8)
    assert h.bucket_index(0.0) == 0
    assert h.bucket_index(5e-4) == 0
    assert h.bucket_index(1e-3) == 1          # [lo, 2*lo)
    assert h.bucket_index(1.9e-3) == 1
    assert h.bucket_index(2.1e-3) == 2
    assert h.bucket_index(1e9) == 7           # clamps to last bucket
    lo, hi = h.bucket_edges(1)
    assert lo == pytest.approx(1e-3) and hi == pytest.approx(2e-3)
    assert h.bucket_edges(0) == (0.0, 1e-3)


def test_histogram_percentiles_bucket_resolution():
    h = Histogram("t", lo=1e-3, growth=2.0, n_buckets=32)
    for v in [0.002] * 50 + [0.016] * 49 + [1.0]:
        h.observe(v)
    # p50 lands in 0.002's bucket: within a factor sqrt(2) of the true value
    p50 = h.percentile(50)
    assert 0.002 / math.sqrt(2) <= p50 <= 0.002 * math.sqrt(2)
    p95 = h.percentile(95)
    assert 0.016 / math.sqrt(2) <= p95 <= 0.016 * math.sqrt(2)
    # the top observation lands in [0.512, 1.024): its reported quantile is
    # bucket-resolution but never exceeds the exact observed max
    assert 0.512 <= h.percentile(99.9) <= h.max == 1.0
    snap = h.snapshot()
    assert snap["count"] == 100
    assert snap["min"] == 0.002 and snap["max"] == 1.0
    assert snap["mean"] == pytest.approx(h.sum / 100)


def test_histogram_single_value_percentile_exact():
    h = Histogram("t")
    h.observe(0.125)
    # clamped into [min, max]: a single-value distribution reports exactly
    for q in (50, 95, 99):
        assert h.percentile(q) == 0.125


def test_histogram_empty_snapshot():
    snap = Histogram("t").snapshot()
    assert snap == {"count": 0, "sum": 0.0, "mean": 0.0, "min": 0.0,
                    "max": 0.0, "p50": 0.0, "p95": 0.0, "p99": 0.0}


def test_registry_timer_fake_clock():
    clock = FakeClock()
    reg = MetricsRegistry(now=clock)
    with reg.timer("op_seconds"):
        clock.advance(0.25)
    h = reg.histogram("op_seconds")
    assert h.count == 1 and h.sum == pytest.approx(0.25)
    assert h.percentile(50) == pytest.approx(0.25)


def test_registry_counters_gauges_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2)
    reg.gauge("g").set(7)
    reg.gauge("g").dec(3)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3.0
    assert snap["gauges"]["g"] == 4.0
    assert reg.render()                        # renders without error
    # one name, one meaning
    with pytest.raises(TypeError):
        reg.gauge("c")


def test_null_registry_is_shared_noop():
    assert NULL_REGISTRY.counter("a") is NULL_REGISTRY.counter("b")
    NULL_REGISTRY.counter("a").inc(100)
    assert NULL_REGISTRY.counter("a").value == 0.0
    with NULL_REGISTRY.timer("x"):
        pass
    assert NULL_REGISTRY.snapshot() == {"counters": {}, "gauges": {},
                                        "histograms": {}}
    assert not NullRegistry.enabled


def test_enable_disable_and_sync_timing():
    assert not obs.enabled()
    assert obs.registry() is NULL_REGISTRY
    reg = obs.enable()
    assert obs.enabled() and obs.registry() is reg
    reg.counter("x").inc()
    # enable() makes a FRESH registry: no cross-test pollution
    reg2 = obs.enable()
    assert reg2 is not reg and reg2.counter("x").value == 0.0
    obs.disable()
    assert obs.registry() is NULL_REGISTRY
    # device time comes from the profiler trace: the synchronized-timing
    # mode that serialised dispatch is gone
    assert not hasattr(obs, "set_sync_timing")
    assert not hasattr(obs, "sync_timing")
    with pytest.raises(TypeError):
        obs.enable(sync_timing=True)


# ------------------------------------------------------------------ tracing

def test_span_nesting_and_ordering_fake_clock():
    clock = FakeClock()
    rec = Recorder(now=clock)
    with rec.span("outer") as outer:
        clock.advance(1.0)
        with rec.span("a", key="v"):
            clock.advance(2.0)
        with rec.span("b"):
            clock.advance(3.0)
    assert rec.roots == [outer]
    assert outer.t0 == 0.0 and outer.t1 == 6.0
    assert [c.name for c in outer.children] == ["a", "b"]
    a, b = outer.children
    assert (a.t0, a.t1) == (1.0, 3.0)
    assert (b.t0, b.t1) == (3.0, 6.0)
    assert a.attrs == {"key": "v"}
    assert outer.duration == 6.0
    assert [s.name for s in outer.walk()] == ["outer", "a", "b"]
    assert outer.find("b") is b and outer.find("zzz") is None
    assert "outer" in outer.render() and "a" in outer.render()


def test_recorder_record_premeasured_interval():
    rec = Recorder(now=FakeClock(10.0))
    with rec.span("root"):
        s = rec.record("queue", t0=4.0, t1=9.0, lane="interactive")
    assert rec.roots[0].children == [s]
    assert s.duration == pytest.approx(5.0)


def test_recording_contextvar():
    assert current() is NULL_RECORDER
    rec = Recorder()
    with recording(rec):
        assert current() is rec
        with current().span("x"):
            pass
    assert current() is NULL_RECORDER
    assert rec.roots[0].name == "x"
    # null recorder spans are inert and reusable
    with NULL_RECORDER.span("y") as s:
        assert s.set("a", 1) is s and s.duration == 0.0


def test_chrome_trace_schema_and_shared_subtree_once():
    clock = FakeClock()
    rec = Recorder(now=clock)
    with rec.span("batch", tid="dispatcher") as bspan:
        clock.advance(2.0)
    r1 = Span("request", t0=0.0, t1=2.0, tid="req-1", children=[bspan])
    r2 = Span("request", t0=0.0, t1=2.0, tid="req-2", children=[bspan])
    doc = chrome_trace([r1, r2])
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and doc["displayTimeUnit"] == "ms"
    xs = [e for e in evs if e["ph"] == "X"]
    ms = [e for e in evs if e["ph"] == "M"]
    assert len(xs) + len(ms) == len(evs)
    for e in xs:
        assert set(e) >= {"name", "ph", "pid", "tid", "ts", "dur", "args"}
        assert e["ts"] >= 0.0 and e["dur"] >= 0.0
    # the shared batch subtree is emitted exactly once
    assert sum(1 for e in xs if e["name"] == "batch") == 1
    assert sum(1 for e in xs if e["name"] == "request") == 2
    assert {e["args"]["name"] for e in ms} >= {"dispatcher", "req-1",
                                               "req-2"}
    json.dumps(doc)                            # JSON-serializable end to end


# --------------------------------------------------------- serving stack

def obs_lake():
    return synthetic_lake(n_tables=16, rows=14, cols=4, vocab=200, seed=9)


def obs_queries(lake, k=20):
    t = lake.tables[3]
    sc = blend.sc(list(t.columns[0][:8]), k=k)
    kw = blend.kw([t.columns[1][0], t.columns[1][2]], k=k)
    mc = blend.mc([(t.columns[0][r], t.columns[1][r]) for r in range(4)],
                  k=k)
    return [(sc & mc).top(10), (sc | kw).top(10), (mc - kw).top(10)]


def test_flight_recorder_and_metrics_end_to_end(tmp_path):
    lake = obs_lake()
    queries = obs_queries(lake)
    reg = obs.enable()
    with DiscoveryServer(DiscoveryEngine(lake, live=True, cache=True),
                         trace=True) as srv:
        resps = [f.result() for f in
                 [srv.submit(q) for q in queries for _ in range(2)]]
        # a repeat pass is served from the exact-result cache: it still
        # records a trace (queue/batch), just no probe work
        hits = [f.result() for f in [srv.submit(q) for q in queries]]
        assert all(h.trace is not None and h.trace.find("queue")
                   for h in hits)
        for r in resps:
            root = r.trace
            assert root is not None and root.name == "request"
            names = [s.name for s in root.walk()]
            for need in ("queue", "batch", "pin_epoch", "drain", "transfer",
                         "merge"):
                assert need in names, names
            assert any(n.startswith("probe:") for n in names)
            assert any(n.startswith("shard:") for n in names)
            # queue + batch are contiguous: spans cover end-to-end latency
            covered = sum(c.duration for c in root.children)
            assert covered == pytest.approx(root.duration, rel=0.10)
            # and the response's own telemetry agrees with the tree
            assert root.find("queue").duration == \
                pytest.approx(r.queue_seconds, abs=2e-3)
        # metrics flowed from every instrumented layer
        snap = reg.snapshot()
        assert snap["counters"]["server.served"] >= 9
        assert snap["counters"]["exec.plans"] >= 1
        assert snap["counters"]["cache.result.miss"] >= 1
        assert "server.batch_seconds" in snap["histograms"]
        # per-shard dispatch is a span under its probe, not a host-clock
        # histogram
        for r in resps:
            shard = r.trace.find("shard:0")
            assert shard is not None and shard.attrs["slots"] >= 1
            assert shard.t0 >= r.trace.find("execute").t0
        assert not any(n.startswith(("shard.probe_seconds", "exec.probe",
                                     "exec.dag", "exec.compile_seconds"))
                       for n in snap["histograms"])
        assert "shard.imbalance" not in snap["gauges"]
        # stats() is a thin reader of the same registry
        st = srv.stats()
        assert st["served"] == int(reg.counter("server.served").value)
        assert st["mutations"]["executed"] == 0
        # explain carries the metrics snapshot
        assert "== metrics ==" in str(srv.explain(queries[0]))
        # flight-recorder export is valid Chrome trace JSON
        path = srv.dump_trace(tmp_path / "trace.json")
        doc = json.loads((tmp_path / "trace.json").read_text())
        assert path == tmp_path / "trace.json"
        evs = doc["traceEvents"]
        assert evs and all(e["ph"] in ("X", "M") for e in evs)
        assert any(e["ph"] == "X" and e["name"] == "request" for e in evs)


def test_store_mutation_metrics():
    lake = obs_lake()
    reg = obs.enable()
    session = blend.connect(lake, live=True)
    t = lake.tables[0]
    tid = session.add_table(t)
    session.drop_table(tid)
    session.compact()
    snap = reg.snapshot()
    for name in ("store.add_table_seconds", "store.drop_table_seconds",
                 "store.compact_seconds"):
        assert snap["histograms"][name]["count"] == 1
    for g in ("store.segments", "store.postings", "store.live_tables",
              "store.compaction_debt", "store.tombstones"):
        assert g in snap["gauges"]
    assert snap["gauges"]["store.segments"] >= 1


def test_retrace_counter_bridges_trace_counts():
    from repro.core import seekers as seek
    reg = obs.enable()
    seek._mark_trace("TEST_KIND")
    assert reg.counter("exec.retraces").value == 1
    assert reg.counter("exec.retraces.TEST_KIND").value == 1
    seek.TRACE_COUNTS.pop("TEST_KIND", None)


def test_observability_changes_no_ids_or_scores():
    """Parity: tracing + metrics are observation only."""
    lake = obs_lake()
    queries = obs_queries(lake)
    with DiscoveryServer(DiscoveryEngine(lake, live=True)) as srv:
        base = [f.result() for f in [srv.submit(q) for q in queries]]
    obs.enable()
    with DiscoveryServer(DiscoveryEngine(lake, live=True),
                         trace=True) as srv:
        traced = [f.result() for f in [srv.submit(q) for q in queries]]
    for b, t in zip(base, traced):
        assert b.table_ids == t.table_ids
        np.testing.assert_array_equal(np.asarray(b.scores),
                                      np.asarray(t.scores))


def test_server_uses_private_registry_when_disabled():
    lake = obs_lake()
    with DiscoveryServer(DiscoveryEngine(lake)) as srv:
        srv.serve(obs_queries(lake)[0])
        st = srv.stats()
        assert st["served"] == 1
        # nothing leaked into the (disabled) global registry
        assert obs.registry() is NULL_REGISTRY
        assert srv.metrics is not NULL_REGISTRY


def test_loadgen_report_queue_percentiles():
    from repro.serve.loadgen import ReplayReport
    rep = ReplayReport(offered=4, completed=4, shed=0, mutations=0,
                       makespan_s=1.0, latencies_s=[0.01, 0.02, 0.03, 0.04],
                       queue_s=[0.001, 0.002, 0.003, 0.1],
                       batch_sizes=[2, 2, 2, 2], shed_reasons={},
                       server_stats={"batches": {"size_hist": {}}})
    d = rep.as_dict()
    assert d["queue_ms_p50"] > 0
    assert d["queue_ms_p99"] >= d["queue_ms_p50"]


# ------------------------------------------------ engine host spans

HOST_SPANS = ["plan", "optimize", "lower", "hash", "capacity"]


def _one_batch(srv, queries, clock=None):
    """Submit ``queries`` to a parked server, let them wait past the
    interactive window (on ``clock`` when the server runs on one), then
    serve them as one batch; returns the responses."""
    futs = [srv.submit(q) for q in queries]
    if clock is not None:
        clock.advance(0.5)
    srv.start()
    out = [f.result(timeout=60) for f in futs]
    srv.stop()
    assert {r.batch_size for r in out} == {len(queries)}
    return out


def _batch(resp):
    return resp.trace.children[1]


def test_engine_host_spans_under_execute_in_order():
    lake = obs_lake()
    clock = FakeClock(1.0)
    srv = DiscoveryServer(DiscoveryEngine(lake, live=True), trace=True,
                          start=False, now=clock)
    resps = _one_batch(srv, obs_queries(lake), clock)
    batch = _batch(resps[0])
    execute = batch.find("execute")
    names = [c.name for c in execute.children if c.name != "gc"]
    first_probe = next(i for i, n in enumerate(names)
                       if n.startswith("probe:"))
    assert names[:first_probe] == HOST_SPANS
    assert execute.find("plan").attrs == {"requests": 3, "plan_hits": 0}
    assert execute.find("lower").attrs == {"plans": 3}
    # the requests waited for the window: the batch's first child is the
    # former's window, from the oldest enqueue to the batch's start
    form = batch.children[0]
    assert form.name == "form" and form.attrs == {"requests": 3}
    assert (form.t0, form.t1) == (1.0, 1.5)
    assert form.t1 <= batch.t0


def test_form_span_starts_when_the_dispatcher_was_free():
    lake = obs_lake()
    clock = FakeClock(1.0)
    srv = DiscoveryServer(DiscoveryEngine(lake, live=True), trace=True,
                          start=False, now=clock)
    queries = obs_queries(lake)
    srv._free_s = 1.2            # the dispatcher was busy until then
    resp = _one_batch(srv, queries[:1], clock)[0]
    form = _batch(resp).children[0]
    assert (form.t0, form.t1) == (1.2, 1.5)
    # a request that found the dispatcher busy until its batch started
    # records no form span: all of its wait was behind earlier work
    clock.advance(1.0)           # enqueued at 2.5, served at 3.0
    srv._free_s = 3.0
    resp = _one_batch(srv, queries[:1], clock)[0]
    assert _batch(resp).find("form") is None


def test_hash_misses_count_values_new_to_the_memo():
    lake = obs_lake()
    t = lake.tables[3]
    sc_vals = list(t.columns[0][:8])
    kw_vals = [t.columns[1][0], t.columns[1][2], t.columns[2][1]]
    mc_vals = [(t.columns[0][r], t.columns[2][r]) for r in range(5)]
    # no intersection, so the optimizer hashes nothing before ``hash``
    queries = [(blend.sc(sc_vals, k=20) | blend.kw(kw_vals, k=20)).top(10),
               (blend.mc(mc_vals, k=20) - blend.kw(kw_vals[:1],
                                                   k=20)).top(10)]
    engine = DiscoveryEngine(lake, live=True)
    distinct = set(sc_vals) | set(kw_vals) | {v for tup in mc_vals
                                              for v in tup}
    hashed = len(sc_vals) + len(kw_vals) + 1 + 2 * len(set(mc_vals))
    first, second = (
        _batch(_one_batch(DiscoveryServer(engine, trace=True, start=False),
                          queries)[0]) for _ in range(2))
    assert first.find("hash").attrs == {"values": hashed,
                                        "misses": len(distinct)}
    assert second.find("hash").attrs == {"values": hashed, "misses": 0}
    cap = first.find("capacity").attrs
    assert cap["hashes"] == hashed and 0 < cap["matched"] <= cap["slots"]
    # the second batch's compiles come from the plan memo
    assert first.find("plan").attrs["plan_hits"] == 0
    assert second.find("plan").attrs["plan_hits"] == len(queries)


def test_optimize_stats_scans_count_host_counts_calls(monkeypatch):
    lake = obs_lake()
    t = lake.tables[3]
    engine = DiscoveryEngine(lake, live=True)
    store = engine.session.executor.index
    calls = []
    orig = type(store).host_counts

    def counting(self, q, live_only=False, **kw):
        calls.append(live_only)
        return orig(self, q, live_only=live_only, **kw)

    monkeypatch.setattr(type(store), "host_counts", counting)
    sc = blend.sc(list(t.columns[0][:8]), k=20)
    kw = blend.kw([t.columns[1][0], t.columns[1][2]], k=20)
    mc = blend.mc([(t.columns[0][r], t.columns[1][r]) for r in range(4)],
                  k=20)
    queries = [(sc & kw & mc).top(10), (sc & kw).top(10), (sc | kw).top(10)]
    srv = DiscoveryServer(engine, trace=True, start=False)
    batch = _batch(_one_batch(srv, queries)[0])
    opt = batch.find("optimize")
    stats_calls = [c for c in calls if c]
    assert opt.attrs["stats_scans"] == len(stats_calls) > 0
    # one scan per SC/KW seeker ranked, one per column of an MC seeker
    assert opt.attrs["seekers"] == 5
    # a tombstone-free lake: no scan gathers a posting's alive flag
    assert opt.attrs["stats_postings"] == 0
    # the optimizer hashed every value first: its lookups hold the misses
    assert 0 < opt.attrs["hash_misses"] <= opt.attrs["hash_values"]
    assert batch.find("hash").attrs["misses"] == 0
    # the one capacity lookup is not a statistics scan
    assert calls.count(False) == 1
    # a drop in a multi-table segment: the first batch passes over that
    # segment's postings once, and a repeat finds the memo
    owner = next(s for s in store.segments if 5 in s.tables)
    assert len(owner.tables) > 1
    engine.drop_table(5)
    first, again = (_batch(_one_batch(srv, queries)[0]).find("optimize")
                    for _ in range(2))
    assert first.attrs["stats_scans"] == again.attrs["stats_scans"] > 0
    assert first.attrs["stats_postings"] == owner.n_real
    assert again.attrs["stats_postings"] == 0


def _profile(tmp_path, fn):
    """Run ``fn`` under a CPU ``jax.profiler`` session; returns (host
    events by name, the marker's offset onto ``time.monotonic``)."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test_clock_marker"):
            mark = time.monotonic()
        fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[-1]
    events: dict = {}
    for pl in ProfileData.from_file(path).planes:
        if not pl.name.startswith("/host"):
            continue
        for ln in pl.lines:
            for ev in ln.events:
                events.setdefault(ev.name, []).append(
                    (ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    (m0, _), = events["test_clock_marker"]
    return events, m0 - mark


@pytest.mark.parametrize("trace", [True, False])
def test_spans_annotate_the_profiler_trace(tmp_path, trace):
    lake = obs_lake()
    queries = obs_queries(lake)
    engine = DiscoveryEngine(lake, live=True)
    with DiscoveryServer(engine) as srv:          # compile outside
        [f.result() for f in [srv.submit(q) for q in queries]]
    srv = DiscoveryServer(engine, trace=trace, start=False)
    got = []
    events, offset = _profile(
        tmp_path, lambda: got.extend(_one_batch(srv, queries)))
    if not trace:
        assert not {"batch", "execute", "hash", "plan"} & set(events)
        return
    batch = _batch(got[0])
    for name in ("execute", "hash"):
        span = batch.find(name)
        (start, dur), = events[name]
        assert start - offset == pytest.approx(span.t0, abs=1e-3)
        assert dur == pytest.approx(span.duration, abs=1e-3)


def test_untraced_server_makes_no_span_and_no_annotation(monkeypatch):
    import jax.profiler

    import repro.serve.server as server_mod

    made = {"span": 0, "annotation": 0}

    class CountingSpan(Span):
        def __init__(self, *a, **kw):
            made["span"] += 1
            super().__init__(*a, **kw)

    real = jax.profiler.TraceAnnotation

    def annotation(*a, **kw):
        made["annotation"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(otrace, "Span", CountingSpan)
    monkeypatch.setattr(server_mod, "Span", CountingSpan)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotation)
    lake = obs_lake()
    queries = obs_queries(lake)
    engine = DiscoveryEngine(lake, live=True)
    _one_batch(DiscoveryServer(engine, start=False), queries)
    assert made == {"span": 0, "annotation": 0}
    _one_batch(DiscoveryServer(engine, trace=True, start=False), queries)
    assert made["span"] > 0 and made["annotation"] > 0


def test_gc_span_under_the_open_span_while_traced(monkeypatch):
    lake = obs_lake()
    queries = obs_queries(lake)
    engine = DiscoveryEngine(lake, live=True)
    orig = DiscoveryEngine.serve_many

    def collecting(self, qs, **kw):
        gc.collect()
        return orig(self, qs, **kw)

    monkeypatch.setattr(DiscoveryEngine, "serve_many", collecting)
    users = otrace._gc_users
    srv = DiscoveryServer(engine, trace=True, start=False)
    batch = _batch(_one_batch(srv, queries)[0])
    # the explicit full collection; an automatic young one may come first
    gcs = [c for c in batch.children if c.name == "gc"]
    assert any(c.attrs["generation"] == 2 for c in gcs)
    assert all(c.t0 <= c.t1 for c in gcs)
    # the hook goes with the server; an untraced one never installs it
    assert otrace._gc_users == users
    assert (otrace._gc_callback in gc.callbacks) == (users > 0)
    _one_batch(DiscoveryServer(engine, start=False), queries)
    assert otrace._gc_users == users
