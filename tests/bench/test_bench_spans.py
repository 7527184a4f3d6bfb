"""The engine host's per-layer readers on synthetic batch trees with known
durations and counts, and the breakdown's attribution of a gap inside the
batch former's window."""
from types import SimpleNamespace

import pytest

import bench_tiny  # noqa: F401  (puts the benchmark on sys.path)

from bench import harness, tracereduce

READERS = ("plan_ms", "optimize_ms", "hash_ms", "capacity_ms", "form_ms",
           "stats_scans_per_batch", "hash_miss_pct")


def span(name, t0, t1, kids=(), **attrs):
    return SimpleNamespace(name=name, t0=t0, t1=t1, duration=t1 - t0,
                           attrs=attrs, children=list(kids))


def batches():
    """Three batches: two ran the engine (the second with two ``execute``
    spans, as a batch split by per-request ``optimize`` does), one was all
    exact cache hits and ran none of the engine's host layers."""
    a = span("batch", 1.0, 1.1, [
        span("form", 0.995, 1.0, requests=2),
        span("execute", 1.0, 1.06, [
            span("plan", 1.000, 1.002, requests=2, plan_hits=1),
            span("optimize", 1.002, 1.012, seekers=2, stats_scans=3,
                 stats_postings=300, hash_values=30, hash_misses=12),
            span("lower", 1.012, 1.013, plans=2),
            span("hash", 1.013, 1.017, values=40, misses=10, superkeys=0),
            span("capacity", 1.017, 1.018, hashes=40),
            span("probe:SC", 1.018, 1.05)]),
        span("drain", 1.06, 1.1)])
    b = span("batch", 2.0, 2.2, [
        span("execute", 2.0, 2.05, [
            span("plan", 2.0, 2.001, requests=1, plan_hits=1),
            span("optimize", 2.001, 2.007, seekers=1, stats_scans=1,
                 stats_postings=100, hash_values=10, hash_misses=0),
            span("hash", 2.007, 2.009, values=20, misses=0, superkeys=4)]),
        span("execute", 2.05, 2.1, [
            span("plan", 2.05, 2.052, requests=1, plan_hits=0),
            span("hash", 2.052, 2.056, values=40, misses=5,
                 superkeys=0)])])
    hits = span("batch", 3.0, 3.01, [span("form", 2.99, 3.0, requests=1)])
    return [a, b, hits]


def ctx_of(bs):
    return SimpleNamespace(requests=[], batches=bs, device=None, peaks={})


def read(name, bs):
    return harness.load_metric(name).read(ctx_of(bs))


@pytest.mark.parametrize("name, want", [
    # per batch with an ``execute`` span: the sum of its spans of the name
    ("plan_ms", (2.0 + 3.0) / 2),
    ("optimize_ms", (10.0 + 6.0) / 2),
    ("hash_ms", (4.0 + 6.0) / 2),
    ("capacity_ms", (1.0 + 0.0) / 2),
    ("form_ms", (5.0 + 0.0) / 2),
    ("stats_scans_per_batch", (3 + 1) / 2),
    # the optimizer's lookups and the hash spans' together
    ("hash_miss_pct", 100.0 * (12 + 15) / (40 + 100)),
])
def test_reader_on_known_spans(name, want):
    assert read(name, batches()) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_reader_without_execute_reads_nothing(name):
    no_engine = [b for b in batches() if not any(
        c.name == "execute" for c in b.children)]
    assert no_engine and read(name, no_engine) is None
    assert read(name, []) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_of_a_program_without_the_span_reads_nothing(name):
    """A program that records only ``execute`` (as one without these
    spans does) has nothing to read: no metric, not a 0."""
    bare = [span("batch", 1.0, 1.1, [span("execute", 1.0, 1.05),
                                     span("drain", 1.05, 1.1)])]
    assert read(name, bare) is None


def test_hash_miss_pct_counts_misses_in_the_optimizer():
    """Where the optimizer's statistics hash every value first, the hash
    span only finds them in the memo; the misses still count."""
    b = span("batch", 1.0, 1.1, [span("execute", 1.0, 1.05, [
        span("optimize", 1.0, 1.02, hash_values=50, hash_misses=50),
        span("hash", 1.02, 1.03, values=50, misses=0)])])
    assert read("hash_miss_pct", [b]) == pytest.approx(50.0)


def test_gap_inside_form_before_its_batch_is_batch_form():
    """A ``form`` span starts before its batch; a device gap inside it is
    the former's window, also after an earlier batch."""
    prof = {"window": (0.99, 3.01),
            "devices": [{"busy": [[0.9, 0.996], [0.999, 1.05],
                                  [1.06, 2.1], [2.15, 2.991],
                                  [2.998, 3.01]]}]}
    idle = tracereduce.idle_by_host_span(prof, batches())
    assert idle == pytest.approx({
        "batch/form": (0.999 - 0.996) + (2.998 - 2.991),
        "batch/execute": 1.06 - 1.05,
        "batch": 2.15 - 2.1})
