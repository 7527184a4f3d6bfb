"""Session-API workloads of the benchmark runner: BENCH_2..BENCH_5.

Exercises the paper workloads through the same code paths users hit
(``blend.connect`` / ``session.query`` / ``session.sql`` /
``DiscoveryEngine.serve_many``) and writes, next to ``--out``:

* ``BENCH_2.json``: per-workload ops/sec + latency percentiles;
* ``BENCH_3.json``: LiveLake mutation workloads (``mutate/add_table_p50``,
  ``mutate/compact``, ``snapshot/load_vs_rebuild``);
* ``BENCH_4.json``: semantic query-cache workloads (repeat hits vs cold,
  partial hits over a shared subtree, unique-query miss overhead, batched
  warm serving, the mutation-invalidation cycle);
* ``BENCH_5.json``: fused execution (deep-DAG latency fused vs unfused,
  12-request ``serve_many`` throughput, launch counts).

``--paper-tables`` folds the per-table JSON of ``benchmarks/run.py`` (run
first) into BENCH_2.  ``benchmarks/run_all.py`` runs this script as one of
its phases.

    PYTHONPATH=src python benchmarks/core_bench.py [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]
for p in (REPO_ROOT, REPO_ROOT / "src"):       # runnable as a plain script
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np

import blend
from repro.core.cost_model import train_cost_model
from repro.core.lake import synthetic_lake
from repro.serve.engine import DiscoveryEngine


def _stats(seconds: list) -> dict:
    a = np.asarray(seconds)
    return {
        "iters": int(a.size),
        "ops_per_sec": float(a.size / a.sum()) if a.sum() else 0.0,
        "mean_ms": float(a.mean() * 1e3),
        "p50_ms": float(np.percentile(a, 50) * 1e3),
        "p95_ms": float(np.percentile(a, 95) * 1e3),
    }


def _measure(fn, warmup: int = 2, iters: int = 10) -> dict:
    for _ in range(warmup):
        fn()
    seconds = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        seconds.append(time.perf_counter() - t0)
    return _stats(seconds)


def _requests(lake, rng, n: int):
    from examples.serve_discovery import build_request
    kinds = ["imputation", "union", "enrichment"]
    return [build_request(lake, rng, kinds[i % 3]) for i in range(n)]


def live_workloads(lake, iters: int = 5) -> dict:
    """LiveLake mutation + persistence workloads (BENCH_3)."""
    import tempfile

    from repro.core.index import build_index
    from repro.core.lake import Table

    rng = np.random.default_rng(3)

    def fresh_table(i, rows=40):
        return Table(f"bench_add_{i}",
                     [[f"tok_{int(x)}" for x in rng.integers(0, 1500, rows)],
                      [f"tok_{int(x)}" for x in rng.integers(0, 1500, rows)],
                      [float(x) for x in np.round(rng.normal(0, 5, rows), 3)]])

    workloads = {}

    # baseline: what a mutation would cost without LiveLake
    rebuild_s = []
    for _ in range(3):
        t0 = time.perf_counter()
        build_index(lake)
        rebuild_s.append(time.perf_counter() - t0)
    rebuild_p50 = float(np.percentile(rebuild_s, 50))

    # mutate/add_table_p50: one 40-row table in, one delta segment out
    session = blend.connect(lake, live=True)
    session.query(blend.kw(["tok_1"], k=5))        # resident + warm
    k = [0]

    def add_drop():
        tid = session.add_table(fresh_table(k[0]))
        k[0] += 1
        session.drop_table(tid)                    # keep state stable

    stats = _measure(add_drop, warmup=2, iters=iters * 4)
    stats["rebuild_p50_ms"] = rebuild_p50 * 1e3
    stats["speedup_vs_rebuild"] = rebuild_p50 / (stats["p50_ms"] / 1e3)
    workloads["mutate/add_table_p50"] = stats

    # mutate/compact: merge a burst of 8 deltas back into the base
    # (auto-compact off so the timed call does the whole merge)
    from repro.store import LiveLake
    compact_s = []
    for it in range(max(iters // 2, 3)):
        s2 = blend.connect(LiveLake(lake, auto_compact=False), live=True)
        for j in range(8):
            s2.add_table(fresh_table(100 + it * 8 + j))
        t0 = time.perf_counter()
        s2.compact()
        compact_s.append(time.perf_counter() - t0)
    workloads["mutate/compact"] = _stats(compact_s)

    # snapshot/load_vs_rebuild: restart path vs indexing from scratch
    with tempfile.TemporaryDirectory() as td:
        path = Path(td) / "bench.snap"
        session.snapshot(path)
        load_s = []
        for _ in range(iters):
            t0 = time.perf_counter()
            blend.restore(path)
            load_s.append(time.perf_counter() - t0)
        stats = _stats(load_s)
        stats["rebuild_p50_ms"] = rebuild_p50 * 1e3
        stats["speedup_vs_rebuild"] = \
            rebuild_p50 / float(np.percentile(load_s, 50))
        workloads["snapshot/load_vs_rebuild"] = stats
    return workloads


def cache_workloads(lake, iters: int = 10) -> dict:
    """Semantic query-cache serving workloads (BENCH_4)."""
    from repro.core.lake import Table
    from repro.serve.engine import DiscoveryEngine

    rng = np.random.default_rng(4)
    t = lake.tables[11]
    rows = list(range(8))
    impute = (blend.mc([(t.columns[0][r], t.columns[1][r]) for r in rows],
                       k=40)
              & blend.sc([t.columns[0][r] for r in rows], k=40)).top(10)
    shared_sc = blend.sc([t.columns[0][r] for r in rows], k=40)
    union_vote = blend.counter(
        *[blend.sc(list(t.columns[c]), k=60) for c in range(3)], k=10)

    def fresh_table(i, rows=40):
        return Table(f"bench_cache_{i}",
                     [[f"tok_{int(x)}" for x in rng.integers(0, 1500, rows)],
                      [f"tok_{int(x)}" for x in rng.integers(0, 1500, rows)],
                      [float(x) for x in np.round(rng.normal(0, 5, rows), 3)]])

    workloads = {}
    cold = blend.connect(lake)
    cached = blend.connect(lake, cache=True)

    # repeat-query: the identical request served over and over — the
    # acceptance workload (hit p50 vs cold serving p50, >= 10x)
    cold_stats = _measure(lambda: cold.query(impute).ids, iters=iters)
    hit_stats = _measure(lambda: cached.query(impute).ids, iters=iters * 4)
    hit_stats["cold_p50_ms"] = cold_stats["p50_ms"]
    hit_stats["speedup_vs_cold"] = cold_stats["p50_ms"] / hit_stats["p50_ms"]
    workloads["cache/repeat_hit"] = hit_stats

    # partial hit: a stream of distinct queries all sharing one hot subtree
    # (the subplan cache carries the shared seeker, the cold sibling runs)
    def partial_stream(session, i):
        q = (shared_sc | blend.kw([t.columns[1][i[0] % 30]], k=40)).top(10)
        i[0] += 1
        return session.query(q).ids

    ic, iw = [0], [0]
    cold_partial = _measure(lambda: partial_stream(cold, ic), iters=iters)
    cached.query(shared_sc)                       # warm the shared subtree
    part_stats = _measure(lambda: partial_stream(cached, iw),
                          iters=iters)
    part_stats["cold_p50_ms"] = cold_partial["p50_ms"]
    part_stats["speedup_vs_cold"] = \
        cold_partial["p50_ms"] / part_stats["p50_ms"]
    workloads["cache/partial_hit"] = part_stats

    # miss overhead: every query unique — the fingerprint + insert cost the
    # cache adds on a workload it can never serve
    def unique_stream(session, i):
        base = int(i[0] * 8) % 1400
        i[0] += 1
        return session.query(
            blend.sc([f"tok_{base + j}" for j in range(8)], k=40)).ids

    iu, iv = [0], [500]
    cold_uni = _measure(lambda: unique_stream(cold, iu), iters=iters)
    miss_stats = _measure(lambda: unique_stream(cached, iv), iters=iters)
    miss_stats["cold_p50_ms"] = cold_uni["p50_ms"]
    miss_stats["overhead_vs_cold"] = \
        miss_stats["p50_ms"] / cold_uni["p50_ms"]
    workloads["cache/miss_overhead"] = miss_stats

    # batched warm serving: serve_many over a fully-warmed request set —
    # cache hits pay no drain share, so the whole batch collapses to lookups
    engine = DiscoveryEngine(lake, cache=True)
    reqs = _requests(lake, rng, 12)
    engine.serve_many(reqs)                       # warm jit + cache
    warm_stats = _measure(lambda: engine.serve_many(reqs), warmup=1,
                          iters=max(iters // 2, 3))
    warm_stats["requests_per_sec"] = warm_stats["ops_per_sec"] * len(reqs)
    warm_stats["hit_ratio"] = (engine.session.cache.hits /
                               max(engine.session.cache.hits
                                   + engine.session.cache.misses
                                   + engine.session.cache.partial, 1))
    workloads["cache/batch12_warm"] = warm_stats

    # mutation-invalidation: add -> serve (recompute) -> drop -> serve; the
    # epoch wipe forces cold work, so this bounds the cost of staying fresh
    # (bit-identity to a cold rebuild is asserted in tests/test_query_cache)
    live_sess = blend.connect(lake, live=True, cache=True)
    pool = [impute, union_vote]
    for q in pool:
        live_sess.query(q)
    k = [0]

    def mutate_cycle():
        tid = live_sess.add_table(fresh_table(k[0]))
        k[0] += 1
        for q in pool:
            live_sess.query(q).ids
        live_sess.drop_table(tid)
        for q in pool:
            live_sess.query(q).ids

    mut_stats = _measure(mutate_cycle, warmup=1, iters=max(iters // 2, 3))
    mut_stats["invalidations"] = live_sess.cache.invalidations
    mut_stats["cache_stats"] = live_sess.cache.stats()
    workloads["cache/mutation_invalidation"] = mut_stats
    return workloads


def fused_workloads(lake, iters: int = 10) -> dict:
    """Fused-execution workloads (BENCH_5): deep-DAG plan latency fused vs
    unfused, batched serve_many throughput, and the launch counts that
    explain the difference.  Cold here means cold *query cache* (none is
    attached) with a warm jit cache — the steady serving state."""
    from examples.fused_serving import deep_query

    session = blend.connect(lake)
    engine = DiscoveryEngine(lake, session=session)
    q = deep_query(lake)

    workloads = {}
    unf = _measure(lambda: session.query(q).ids, iters=iters)
    fus = _measure(lambda: session.query(q, fused=True).ids, iters=iters)
    n_unf = session.query(q).info.launches
    n_fus = session.query(q, fused=True).info.launches
    assert session.query(q, fused=True).ids == session.query(q).ids
    unf["launches"] = n_unf
    fus["launches"] = n_fus
    fus["speedup_vs_unfused"] = unf["p50_ms"] / fus["p50_ms"]
    workloads["fused/deep_dag_unfused"] = unf
    workloads["fused/deep_dag_fused"] = fus

    reqs = [deep_query(lake, tab) for tab in range(12)]
    engine.serve_many(reqs)                       # warm every program
    engine.serve_many(reqs, fused=True)
    unf = _measure(lambda: engine.serve_many(reqs), warmup=1,
                   iters=max(iters // 2, 3))
    fus = _measure(lambda: engine.serve_many(reqs, fused=True), warmup=1,
                   iters=max(iters // 2, 3))
    resp = engine.serve_many(reqs, fused=True)
    unf["requests_per_sec"] = unf["ops_per_sec"] * len(reqs)
    fus["requests_per_sec"] = fus["ops_per_sec"] * len(reqs)
    fus["speedup_vs_unfused"] = unf["p50_ms"] / fus["p50_ms"]
    fus["launches_per_request"] = max(r.launches for r in resp)
    workloads["serve/batch12_deep_unfused"] = unf
    workloads["serve/batch12_deep_fused"] = fus
    return workloads


def _write(path: Path, bench: str, lake, workloads: dict, **extra):
    payload = {"bench": bench, "platform": platform.platform(),
               "python": platform.python_version(), "lake": lake.stats(),
               "workloads": workloads, **extra}
    path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    print(f"wrote {path}")
    return payload


def main(out_path: Path, paper_tables: bool = False,
         iters: int = 10) -> dict:
    rng = np.random.default_rng(7)
    lake = synthetic_lake(n_tables=200, rows=40, vocab=1500, seed=1)
    session = blend.connect(lake)
    t = lake.tables[11]
    rows = list(range(8))

    impute = (blend.mc([(t.columns[0][r], t.columns[1][r]) for r in rows],
                       k=40)
              & blend.sc([t.columns[0][r] for r in rows], k=40)).top(10)
    union_vote = blend.counter(
        *[blend.sc(list(t.columns[c]), k=60) for c in range(3)], k=10)
    negative = (blend.mc([(t.columns[0][r], t.columns[1][r])
                          for r in rows[:5]], k=40)
                - blend.mc([(t.columns[0][6], t.columns[1][7])], k=40)).top(10)
    enrich_sql = (blend.kw([t.columns[0][0], t.columns[1][1]], k=10)
                  | blend.corr([t.columns[0][r] for r in rows],
                               list(map(float, rows)), k=10)).top(20).to_sql()

    workloads = {}

    workloads["query/imputation_fluent"] = _measure(
        lambda: session.query(impute).ids, iters=iters)
    workloads["query/imputation_noopt"] = _measure(
        lambda: session.query(impute, optimize=False).ids, iters=iters)
    workloads["query/union_counter"] = _measure(
        lambda: session.query(union_vote).ids, iters=iters)
    workloads["query/negative_examples"] = _measure(
        lambda: session.query(negative).ids, iters=iters)
    workloads["sql/enrichment"] = _measure(
        lambda: session.sql(enrich_sql).ids, iters=iters)
    workloads["compile/parse_rewrite_lower"] = _measure(
        lambda: session.compile(enrich_sql), iters=max(iters * 20, 100))

    # batched serving through the engine (12 heterogeneous requests/batch),
    # reusing the session so the warm jit cache carries over
    engine = DiscoveryEngine(lake, session=session)
    engine.cost_model = train_cost_model(session.executor, lake, n_samples=10)
    reqs = _requests(lake, rng, 12)
    engine.serve_many(reqs)               # warm every capacity bucket
    batch_stats = _measure(lambda: engine.serve_many(reqs),
                           warmup=1, iters=max(iters // 2, 3))
    batch_stats["requests_per_sec"] = \
        batch_stats["ops_per_sec"] * len(reqs)
    workloads["serve/batch12_mixed"] = batch_stats

    extra = {}
    if paper_tables:
        results_dir = REPO_ROOT / "benchmarks" / "results"
        extra["paper_tables"] = {
            p.stem: json.loads(p.read_text())
            for p in sorted(results_dir.glob("*.json"))}
    payload = _write(out_path, "BENCH_2", lake, workloads, **extra)

    live = live_workloads(lake, iters=max(iters // 2, 5))
    _write(out_path.parent / "BENCH_3.json", "BENCH_3", lake, live)
    cache = cache_workloads(lake, iters=iters)
    _write(out_path.parent / "BENCH_4.json", "BENCH_4", lake, cache)
    fused = fused_workloads(lake, iters=iters)
    _write(out_path.parent / "BENCH_5.json", "BENCH_5", lake, fused)

    for name, s in {**workloads, **live, **cache, **fused}.items():
        extra = "".join(
            f" ({s[key]:.0f}x vs {key.rsplit('_', 1)[-1]})"
            for key in ("speedup_vs_rebuild", "speedup_vs_cold",
                        "speedup_vs_unfused")
            if key in s)
        print(f"{name:32s} {s['ops_per_sec']:10.1f} ops/s "
              f"p50={s['p50_ms']:.2f}ms p95={s['p95_ms']:.2f}ms{extra}")
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=Path, default=REPO_ROOT / "BENCH_2.json")
    ap.add_argument("--paper-tables", action="store_true",
                    help="fold benchmarks/results/*.json into BENCH_2")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(args.out, paper_tables=args.paper_tables, iters=args.iters)
