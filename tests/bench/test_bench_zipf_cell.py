"""The heavy-tailed web-table cell, ``webtables.mc_zipf``: it resolves from
``BENCHMARK.json``, the benchmark's reference holds to the copied oracle and
to the served path on a Zipf lake whose hottest value passes 1,024
postings, and the flat probe window's two readers read synthetic span
trees."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench_tiny import cell, config

from bench import harness, lakegen, oracle_plain, reference
from bench.traffic import loadgen

CELL = "webtables.mc_zipf"


def test_cell_resolves_its_config_traffic_and_cell_file():
    c = harness.load_cell(CELL)
    assert c["chips"] == 1
    assert c["config"]["values"]["zipf_s"] == 1.0
    assert c["config"]["n_tables"] == 30000
    assert c["traffic"]["loop"] == "open"
    assert c["traffic"] == loadgen.load_traffic("mate")
    assert c["params"]["rate_rps"] > 0 and c["params"]["limit_ms"] > 0
    names = {m["name"] for m in c["per_layer"]}
    assert {"window_fill_pct", "probe_slots_per_req", "seeker_device_ms",
            "probe_roofline_pct"} <= names
    assert {m["name"] for m in c["end_to_end"]} >= {"p95_ms", "setup_s"}


@pytest.fixture(scope="module")
def zipf_small():
    cfg = config(lakegen.load_config("webtables"), n_tables=60, vocab=200)
    lake = lakegen.generate(cfg, 2**33 + 5)
    return lake, lake.tables(), reference.Reference(lake)


def test_reference_matches_the_oracle_on_zipf_values(zipf_small):
    lake, tables, ref = zipf_small
    counts = np.bincount(lake.cat)
    assert counts.max() > 20 * np.median(counts[counts > 0])
    hot = int(np.argmax(counts))
    rng = np.random.default_rng(1)
    tok = lakegen.token
    for _ in range(10):
        t = int(rng.integers(0, lake.n_tables))
        c0, c1 = lake.column(t, 0), lake.column(t, 1)
        rows = rng.choice(len(c0), min(5, len(c0)), replace=False)
        vals = [int(c0[r]) for r in rows] + [hot]
        words = [tok(v) for v in vals]
        tups = [(int(c0[r]), int(c1[r])) for r in rows] + [(vals[0], hot)]
        target = [float(j % 3) for j in range(len(vals))]
        assert np.array_equal(ref.sc(vals), oracle_plain.oracle_sc(tables,
                                                                   words))
        assert np.array_equal(ref.kw(vals), oracle_plain.oracle_kw(tables,
                                                                   words))
        assert np.array_equal(ref.mc(tups), oracle_plain.oracle_mc(
            tables, [tuple(map(tok, tp)) for tp in tups]))
        assert np.array_equal(ref.c(vals, target), oracle_plain.oracle_c(
            tables, words, target))


def test_served_path_is_exact_past_the_old_ceiling():
    """Requests of the cell's own traffic on a Zipf lake whose hottest
    value holds more than 1,024 postings: no overflow, and every answer
    equals the reference's, ids and float32 scores."""
    import blend
    from repro.serve.engine import DiscoveryEngine

    c = cell(CELL, n_tables=240, vocab=300)
    seed = 2**31 + 17
    lake = lakegen.generate(c["config"], seed)
    assert int(np.bincount(lake.cat).max()) > 1024
    queries = loadgen.query_pool(c["traffic"], lake,
                                 np.random.default_rng([seed, 1]))[:24]
    eng = DiscoveryEngine(None, session=blend.connect(lake.tables(),
                                                      live=True))
    ref = reference.Reference(lake)
    exprs = [loadgen.to_expr(q) for q in queries]
    got = [r for i in range(0, len(exprs), 12)
           for r in eng.serve_many(exprs[i:i + 12], fused=True)]
    for q, resp in zip(queries, got):
        ids, scores = ref.answer(q)
        s = np.asarray(resp.scores)
        assert resp.overflow == 0 and not resp.degraded
        assert resp.table_ids == ids
        assert np.array_equal(s[:lake.n_tables], scores)


# --------------------------------------------------------------------------
# the window's readers on synthetic span trees
# --------------------------------------------------------------------------

def span(name, kids=(), **attrs):
    return SimpleNamespace(name=name, t0=0.0, t1=0.0, duration=0.0,
                           attrs=attrs, children=list(kids))


def batches(with_window=True):
    """Two batches that ran the engine (the second split into two
    ``execute`` spans, each with its own ``capacity``) and one of exact
    cache hits that ran none of it."""
    def cap(slots, matched):
        return span("capacity", hashes=9, **(
            {"slots": slots, "matched": matched} if with_window else {}))

    a = span("batch", [span("execute", [cap(4096, 3000)], requests=3)])
    b = span("batch", [span("execute", [cap(1024, 700)], requests=1),
                       span("execute", [cap(512, 300)], requests=2)])
    hits = span("batch", [span("form", requests=2)])
    return [a, b, hits]


def read(name, bs):
    ctx = SimpleNamespace(requests=[], batches=bs, device=None, peaks={})
    return harness.load_metric(name).read(ctx)


@pytest.mark.parametrize("name, want", [
    ("window_fill_pct", 100.0 * (3000 + 700 + 300) / (4096 + 1024 + 512)),
    ("probe_slots_per_req", (4096 + 1024 + 512) / (3 + 1 + 2)),
])
def test_window_reader_on_known_spans(name, want):
    assert read(name, batches()) == pytest.approx(want)


@pytest.mark.parametrize("name", ["window_fill_pct", "probe_slots_per_req"])
def test_window_reader_without_the_window_reads_nothing(name):
    """A program without the flat window (no ``slots``), batches without
    the engine, and no batches at all: nothing to read."""
    assert read(name, batches(with_window=False)) is None
    assert read(name, batches()[2:]) is None
    assert read(name, []) is None
