"""The reduction from a profiler trace to the traced run's device numbers,
per-layer metrics and breakdown, on a trace recorded on a TPU v5 lite."""
from pathlib import Path
from types import SimpleNamespace

import pytest

import bench_tiny  # noqa: F401  (puts the benchmark on sys.path)

from bench import harness, tracereduce

FIXTURE = Path(__file__).resolve().parent / "data" / "chip_trace.json.gz"


def _span(t):
    name, t0, t1, kids = t
    return SimpleNamespace(name=name, t0=t0, t1=t1,
                           duration=t1 - t0,
                           children=[_span(k) for k in kids])


@pytest.fixture(scope="module")
def traced():
    fx = tracereduce.load_plain(FIXTURE)
    prof = tracereduce.reduce_plain(fx["data"], fx["marker"],
                                    tuple(fx["window"]))
    spans = [_span(s) for s in fx["spans"]]
    # one request per batch, each said to need 40 KB of least bytes
    reqs = [SimpleNamespace(queue_s=0.001, batch_size=1, batch_t0=s.t0,
                            least_bytes=lambda: 40_000) for s in spans]
    ctx = SimpleNamespace(requests=reqs, batches=spans, device=prof,
                          peaks=tracereduce.peaks("TPU v5 lite"))
    return prof, spans, ctx


def test_device_intervals(traced):
    prof, _, _ = traced
    assert prof["window_s"] == pytest.approx(0.6)
    assert 0.0 < prof["busy_s"] < prof["window_s"]
    dev = prof["devices"][0]
    w0, w1 = prof["window"]
    assert all(w0 <= a < b <= w1 for _, a, b in dev["ops"])
    busy = dev["busy"]
    assert all(b0[1] < b1[0] for b0, b1 in zip(busy, busy[1:]))


def test_breakdown(traced):
    prof, spans, _ = traced
    bd = tracereduce.breakdown(prof, spans)
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    assert any(n.endswith("_seeker_seg") for n, _ in bd["device_ops"])
    assert all("(" not in n for n, _ in bd["device_ops"])
    idle = tracereduce.idle_by_host_span(prof, spans)
    assert sum(idle.values()) == pytest.approx(
        prof["window_s"] - prof["busy_s"], abs=1e-9)
    assert any(k.startswith("batch/execute") for k in idle)


def test_shares_stay_within_0_and_100(traced):
    _, _, ctx = traced
    idle = harness.load_metric("device_idle_pct").read(ctx)
    roof = harness.load_metric("probe_roofline_pct").read(ctx)
    seek = harness.load_metric("seeker_device_ms").read(ctx)
    assert 0.0 < idle < 100.0
    assert 0.0 < roof <= 100.0
    assert seek > 0.0
    assert harness.load_metric("dispatch_ms").read(ctx) > 0.0
    assert harness.load_metric("drain_ms").read(ctx) >= 0.0


def test_no_trace_reads_nothing():
    ctx = SimpleNamespace(requests=[], batches=[], device=None, peaks={})
    for name in ("device_idle_pct", "seeker_device_ms",
                 "probe_roofline_pct", "queue_ms_p95", "batch_size_mean",
                 "dispatch_ms", "drain_ms"):
        assert harness.load_metric(name).read(ctx) is None


def test_trace_without_marker_reduces_to_nothing():
    data = {"planes": [{"name": "/device:TPU:0", "lines": []}]}
    assert tracereduce.reduce_plain(data, 0.0, (0.0, 1.0)) is None
