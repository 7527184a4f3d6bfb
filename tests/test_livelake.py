"""LiveLake: mutation parity with from-scratch rebuilds, LSM segment
behavior, compaction, snapshot persistence, rowkey-stride guards, and the
retrace-free mutation contract."""
import time

import numpy as np
import pytest
from hypothesis import HealthCheck, given, settings, strategies as st

import blend
from repro.core import seekers as seek
from repro.core.executor import Executor
from repro.core.index import build_index, validate_row_stride
from repro.core.lake import DataLake, Table, synthetic_lake
from repro.core.plan import Combiners, Plan, Seekers
from repro.store import CompactionPolicy, LiveLake
from repro.store import snapshot as snap


def small_live_lake(seed=5, n_tables=16):
    return synthetic_lake(n_tables=n_tables, rows=14, cols=4, vocab=200,
                          seed=seed)


def extra_table(i, rows=10, vocab=200):
    rng = np.random.default_rng(1000 + i)
    return Table(f"extra{i}",
                 [[f"tok_{int(x)}" for x in rng.integers(0, vocab, rows)],
                  [f"tok_{int(x)}" for x in rng.integers(0, vocab, rows)],
                  [float(x) for x in np.round(rng.normal(0, 5, rows), 3)]])


def all_specs(lake_table, k):
    vals = list(lake_table.columns[0][:8])
    tuples = [(lake_table.columns[0][r], lake_table.columns[1][r])
              for r in range(6)]
    return [Seekers.SC(vals, k=k), Seekers.KW(vals, k=k),
            Seekers.MC(tuples, k=k),
            Seekers.Correlation(vals, [float(i) for i in range(8)], k=k,
                                h=64)]


def combiner_plan(lake_table, k):
    vals = list(lake_table.columns[0][:8])
    tuples = [(lake_table.columns[0][r], lake_table.columns[1][r])
              for r in range(5)]
    plan = Plan()
    plan.add("sc", Seekers.SC(vals, k=k))
    plan.add("kw", Seekers.KW(vals[:4], k=k))
    plan.add("mc", Seekers.MC(tuples, k=k))
    plan.add("c", Seekers.Correlation(vals, [float(i) for i in range(8)],
                                      k=k, h=64))
    plan.add("and", Combiners.Intersect(k=k), ["sc", "mc"])
    plan.add("or", Combiners.Union(k=k), ["and", "c"])
    plan.add("cnt", Combiners.Counter(k=k), ["sc", "kw"])
    plan.add("out", Combiners.Difference(k=k), ["or", "cnt"])
    return plan


def assert_rebuild_parity(session, tables_by_tid, probe_table,
                          backend="sorted", interpret=False):
    """Post-mutation scores must be bit-identical to a from-scratch rebuild
    of the live tables, for all four seekers and a 4-combiner plan."""
    live_ids = session.live.live_ids()
    rebuilt = DataLake([tables_by_tid[t] for t in live_ids])
    ref = Executor(build_index(rebuilt), backend=backend, interpret=interpret)
    k = session.index.n_tables
    for spec in all_specs(probe_table, k):
        a = np.asarray(session.executor.run_seeker(spec).scores)
        b = np.asarray(ref.run_seeker(spec).scores)
        np.testing.assert_array_equal(a[live_ids], b, err_msg=spec.kind)
        dead = np.ones(len(a), bool)
        dead[live_ids] = False
        assert (a[dead] == 0).all(), spec.kind
    pa, _ = session.executor.run(combiner_plan(probe_table, k))
    pb, _ = ref.run(combiner_plan(probe_table, k))
    np.testing.assert_array_equal(np.asarray(pa.scores)[live_ids],
                                  np.asarray(pb.scores))
    np.testing.assert_array_equal(np.asarray(pa.mask)[live_ids],
                                  np.asarray(pb.mask))


# --------------------------------------------------------------------------
# mutation parity (tentpole acceptance)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend,interpret",
                         [("sorted", False), ("bucket", True)])
def test_mutation_parity_add_drop_compact(backend, interpret):
    lake = small_live_lake()
    session = blend.connect(lake, live=True, backend=backend,
                            interpret=interpret)
    tbl = dict(enumerate(lake.tables))
    probe = lake.tables[3]

    tids = []
    for i in range(3):
        t = extra_table(i)
        tids.append(session.add_table(t))
        tbl[tids[-1]] = t
    assert_rebuild_parity(session, tbl, probe, backend, interpret)

    session.drop_table(5)            # tombstone inside the base segment
    del tbl[5]
    session.drop_table(tids[1])      # whole-run delete of an L0 delta
    del tbl[tids[1]]
    assert_rebuild_parity(session, tbl, probe, backend, interpret)

    session.compact()                # merge + tombstone GC
    assert session.index_shape()["segments"] == 1
    assert_rebuild_parity(session, tbl, probe, backend, interpret)

    t = extra_table(9, rows=12)
    tbl[session.add_table(t)] = t    # delta on top of the compacted base
    assert_rebuild_parity(session, tbl, probe, backend, interpret)


@settings(max_examples=6, deadline=None,
          suppress_health_check=[HealthCheck.too_slow,
                                 HealthCheck.data_too_large])
@given(st.lists(st.tuples(st.sampled_from(["add", "drop", "compact"]),
                          st.integers(0, 10 ** 6)),
                min_size=1, max_size=5))
def test_mutation_parity_hypothesis_random_sequences(ops):
    """Property: any add/drop/compact sequence preserves rebuild parity."""
    lake = small_live_lake(seed=11, n_tables=10)
    session = blend.connect(lake, live=True)
    tbl = dict(enumerate(lake.tables))
    for i, (op, arg) in enumerate(ops):
        if op == "add":
            t = extra_table(arg % 50, rows=6 + arg % 9)
            tbl[session.add_table(t, name=f"h{i}_{arg}")] = t
        elif op == "drop" and len(tbl) > 4:
            tid = sorted(tbl)[arg % len(tbl)]
            session.drop_table(tid)
            del tbl[tid]
        elif op == "compact":
            session.compact(full=arg % 2 == 0)
    assert_rebuild_parity(session, tbl, lake.tables[2])


def test_reclaim_ids_remaps_and_preserves_results():
    lake = small_live_lake(seed=13)
    session = blend.connect(lake, live=True)
    tbl = dict(enumerate(lake.tables))
    for ref in (1, 7, 9):
        session.drop_table(ref)
        del tbl[ref]
    vals = list(lake.tables[3].columns[0][:8])
    before = {session.live.store.table_names[t]
              for t in session.query(blend.sc(vals, k=30)).ids}
    remap = session.compact(reclaim_ids=True)
    assert sorted(remap.values()) == list(range(len(tbl)))
    after = {session.live.store.table_names[t]
             for t in session.query(blend.sc(vals, k=30)).ids}
    assert before == after            # same tables by name, new dense ids
    tbl2 = {remap[t]: tab for t, tab in tbl.items()}
    assert_rebuild_parity(session, tbl2, lake.tables[3])


# --------------------------------------------------------------------------
# LSM mechanics
# --------------------------------------------------------------------------

def test_add_is_delta_drop_is_tombstone_or_run_delete():
    lake = small_live_lake()
    ll = LiveLake(lake, auto_compact=False)
    base = ll.store.segments[0]
    tid = ll.add_table(extra_table(0))
    assert ll.store.segments[0] is base          # base untouched
    assert len(ll.store.segments) == 2
    ll.drop_table(tid)                           # sole table of its run
    assert len(ll.store.segments) == 1
    assert not ll.store.pending_dead
    assert tid in ll.store.free_ids              # slot immediately reusable
    ll.drop_table(2)                             # lives inside the base
    assert len(ll.store.segments) == 1           # no rewrite: tombstoned
    assert 2 in ll.store.pending_dead
    shape = ll.shape()
    assert shape["tombstoned"] == [lake.tables[2].name]


def test_auto_compact_bounds_segment_count():
    lake = small_live_lake(n_tables=8)
    policy = CompactionPolicy(max_segments=4, tier_fanout=2)
    ll = LiveLake(lake, policy=policy)
    for i in range(12):
        ll.add_table(extra_table(i))
    assert len(ll.store.segments) <= policy.max_segments
    # every live table still wholly inside exactly one segment
    owners = [s for i in range(ll.store.n_slots) if ll.store.alive[i]
              for s in ll.store.segments if i in s.tables]
    assert len(owners) == int(ll.store.alive.sum())


def test_id_reuse_never_resurrects_postings():
    lake = small_live_lake(seed=21)
    session = blend.connect(lake, live=True)
    ghost = Table("ghost", [["spectral_token"] * 6,
                            [float(i) for i in range(6)]])
    tid = session.add_table(ghost)
    session.drop_table(tid)
    reborn = Table("reborn", [["solid_token"] * 6,
                              [float(i) for i in range(6)]])
    tid2 = session.add_table(reborn)
    assert tid2 == tid                            # slot reused
    assert session.query(blend.kw(["spectral_token"], k=5)).ids == []
    assert session.query(blend.kw(["solid_token"], k=5)).ids == [tid2]


def test_plan_pins_epoch_against_midplan_mutation():
    """A mutation landing while a plan executes must not be observed until
    the next plan: every seeker of one request sees one epoch."""
    lake = small_live_lake()
    session = blend.connect(lake, live=True)
    ex = session.executor
    session.query(blend.kw(["tok_1"], k=5))
    engine = ex.engine
    ex._in_plan = True            # emulate: plan in flight, epoch pinned
    try:
        session.add_table(extra_table(0))
        rs = ex.run_seeker(Seekers.KW(["tok_1"], k=5))
        assert ex.engine is engine                     # old epoch served
        assert len(np.asarray(rs.scores)) == ex.n_tables
    finally:
        ex._in_plan = False
    session.query(blend.kw(["tok_1"], k=5))
    assert ex.engine is not engine                     # next plan refreshes


def test_epoch_bumps_and_engine_refresh():
    lake = small_live_lake()
    session = blend.connect(lake, live=True)
    ex = session.executor
    e0 = session.live.epoch
    engine0 = ex.engine
    tid = session.add_table(extra_table(0))
    assert session.live.epoch > e0
    assert ex.engine is engine0       # refresh is lazy ...
    session.query(blend.kw(["tok_1"], k=5))
    assert ex.engine is not engine0   # ... and happens at query entry
    assert ex._engine_epoch == session.live.epoch
    session.drop_table(tid)


# --------------------------------------------------------------------------
# retrace-free mutation serving + add_table speed (acceptance criteria)
# --------------------------------------------------------------------------

def test_add_table_zero_retrace_within_capacity_bucket():
    lake = small_live_lake(seed=31)
    session = blend.connect(lake, live=True)
    t3 = lake.tables[3]
    q = (blend.sc(list(t3.columns[0][:8]), k=20)
         & blend.mc([(t3.columns[0][r], t3.columns[1][r])
                     for r in range(5)], k=20)).top(10)
    session.query(q)
    # warm the mutated-topology jit entries once
    tid = session.add_table(extra_table(0))
    session.query(q)
    session.drop_table(tid)
    session.query(q)
    before = dict(seek.TRACE_COUNTS)
    # same capacity bucket (similar-size table, same padded segment rung):
    # the mutation and the queries after it compile nothing new
    tid = session.add_table(extra_table(1))
    session.query(q)
    session.drop_table(tid)
    session.query(q)
    assert dict(seek.TRACE_COUNTS) == before


@pytest.mark.slow
def test_add_table_much_faster_than_rebuild_bench_lake():
    """>= 10x on the 200-table bench lake (ISSUE 3 acceptance)."""
    lake = synthetic_lake(n_tables=200, rows=40, vocab=1500, seed=1)
    session = blend.connect(lake, live=True)
    small = extra_table(0, rows=40)
    t0 = time.perf_counter()
    tid = session.add_table(small)
    add_s = time.perf_counter() - t0
    session.drop_table(tid)
    t0 = time.perf_counter()
    build_index(lake)
    rebuild_s = time.perf_counter() - t0
    assert rebuild_s / add_s >= 10, (add_s, rebuild_s)


# --------------------------------------------------------------------------
# rowkey stride guards (satellite: aliasing fix)
# --------------------------------------------------------------------------

def test_row_stride_validation_guards():
    with pytest.raises(ValueError, match="alias"):
        validate_row_stride(10, 1 << 4, max_rows=100)
    with pytest.raises(ValueError, match="shard the lake"):
        validate_row_stride(2 ** 10, 1 << 22)
    validate_row_stride(100, 1 << 7, max_rows=100)


def test_build_index_auto_widens_stride():
    lake = small_live_lake()
    idx = build_index(lake)
    assert idx.row_stride >= max(t.n_rows for t in lake.tables)
    wide = build_index(lake, row_stride=1 << 10)
    assert wide.row_stride == 1 << 10      # explicit stride honored upward


def test_live_add_long_table_widens_stride_with_parity():
    lake = small_live_lake(seed=41)
    session = blend.connect(lake, live=True)
    stride0 = session.live.store.row_stride
    long = extra_table(3, rows=4 * stride0)
    tbl = dict(enumerate(lake.tables))
    tbl[session.add_table(long)] = long
    assert session.live.store.row_stride >= 4 * stride0
    assert_rebuild_parity(session, tbl, lake.tables[2])


def test_live_stride_overflow_raises():
    lake = small_live_lake()
    ll = LiveLake(lake)

    class HugeTable:            # geometry-only stand-in: rejected pre-build
        name = "huge"
        n_rows = (1 << 26) + 1
        n_cols = 2
        columns = []

    with pytest.raises(ValueError, match="shard the lake"):
        ll.add_table(HugeTable())
    assert ll.store.n_slots == lake.n_tables      # nothing was allocated


# --------------------------------------------------------------------------
# snapshot persistence
# --------------------------------------------------------------------------

def test_snapshot_roundtrip_parity(tmp_path):
    lake = small_live_lake(seed=51)
    session = blend.connect(lake, live=True)
    tbl = dict(enumerate(lake.tables))
    t = extra_table(2)
    tbl[session.add_table(t)] = t
    session.drop_table(4)
    del tbl[4]
    man = session.snapshot(tmp_path / "lake")
    assert man.exists() and (tmp_path / "lake.npz").exists()

    restored = blend.restore(tmp_path / "lake")
    probe = lake.tables[3]
    k = session.index.n_tables
    for spec in all_specs(probe, k):
        a = np.asarray(session.executor.run_seeker(spec).scores)
        b = np.asarray(restored.executor.run_seeker(spec).scores)
        live = session.live.live_ids()
        np.testing.assert_array_equal(a[live], b[restored.live.live_ids()])
    # restored lakes stay mutable
    t2 = extra_table(7)
    tid = restored.add_table(t2)
    assert tid in restored.live.live_ids()


def test_alloc_growth_validation_leaves_store_intact():
    """A rejected slot-capacity growth must not corrupt the store."""
    lake = small_live_lake(n_tables=8)           # slot capacity 16
    ll = LiveLake(lake, auto_compact=False)
    ll.store.row_stride = 1 << 26                # growth to 32 would overflow
    for i in range(8):                           # fill the remaining slots
        ll.add_table(extra_table(i))
    with pytest.raises(ValueError, match="shard the lake"):
        ll.add_table(extra_table(99))
    assert ll.store.n_slots == len(ll.store.alive) == 16
    assert ll.store.live_ids() == list(range(16))   # still consistent


def test_snapshot_preserves_with_quadrants(tmp_path):
    from repro.store.segments import SegmentStore
    lake = small_live_lake()
    ll = LiveLake(store=SegmentStore(lake, with_quadrants=False))
    ll.snapshot(tmp_path / "nq")
    restored = snap.load(tmp_path / "nq")
    assert restored.with_quadrants is False


def test_snapshot_version_check(tmp_path):
    lake = small_live_lake()
    ll = LiveLake(lake)
    ll.snapshot(tmp_path / "s")
    manifest = (tmp_path / "s.json")
    import json
    bad = json.loads(manifest.read_text())
    bad["version"] = 99
    manifest.write_text(json.dumps(bad))
    with pytest.raises(ValueError, match="version"):
        snap.load(tmp_path / "s")


# --------------------------------------------------------------------------
# observability + serving integration
# --------------------------------------------------------------------------

def test_explain_reports_index_shape():
    lake = small_live_lake()
    session = blend.connect(lake, live=True)
    session.add_table(extra_table(0))
    session.drop_table(1)
    ex = session.explain(blend.kw(["tok_1"], k=5))
    s = ex.index_shape
    assert s["mode"] == "live" and s["segments"] == 2
    assert s["epoch"] == session.live.epoch
    assert len(s["postings_per_segment"]) == 2
    assert s["tombstoned"] == [lake.tables[1].name]
    text = str(ex)
    assert "segments: 2" in text and "tombstoned" in text
    # static sessions report a single-segment shape
    st_shape = blend.connect(lake).explain(blend.kw(["tok_1"], k=5),
                                           execute=False).index_shape
    assert st_shape["mode"] == "static" and st_shape["segments"] == 1


def test_discovery_engine_live_mutations():
    from repro.serve.engine import DiscoveryEngine
    lake = small_live_lake()
    eng = DiscoveryEngine(lake, live=True)
    t = extra_table(0)
    tid = eng.add_table(t)
    resp = eng.serve(blend.kw([t.columns[0][0]], k=30))
    assert tid in resp.table_ids
    eng.drop_table(tid)
    assert tid not in eng.serve(blend.kw([t.columns[0][0]], k=30)).table_ids
    eng.compact()
    static = DiscoveryEngine(lake)
    with pytest.raises(RuntimeError, match="live=True"):
        static.add_table(t)


def test_sharded_store_accepts_live_mutations():
    from repro.dist.shard import ShardedStore
    lake = small_live_lake()
    ll = LiveLake(lake)
    ll.add_table(extra_table(0))
    ll.drop_table(2)
    merged = ll.store.merged_index()
    assert (np.diff(merged.cell_hash.astype(np.int64)) >= 0).all()
    assert 2 not in set(merged.table_id.tolist())
    # the sharded coordinator observes the same mutations shard-locally
    store = ShardedStore(lake, n_shards=2)
    sl = LiveLake(lake, store=store)
    sl.add_table(extra_table(0))
    sl.drop_table(2)
    assert sorted(sl.live_ids()) == sorted(ll.live_ids())
    assert store.n_postings == sum(s.n_postings for s in store.shards)
    assert 2 in store.pending_dead


def test_host_counts_live_only_excludes_tombstones():
    from repro.core.hashing import hash_array
    lake = small_live_lake()
    ll = LiveLake(lake)
    vals = list(lake.tables[2].columns[0][:6])
    h = np.unique(hash_array(vals))
    full = ll.store.host_counts(h)
    ll.drop_table(2)
    assert (ll.store.host_counts(h) == full).all()          # slots still held
    live = ll.store.host_counts(h, live_only=True)
    assert live.sum() < full.sum()


def per_posting_counts(store, q, live_only):
    """The per-posting formula ``host_counts`` must equal: every posting's
    alive flag gathered and prefix-summed, in every segment of every
    shard."""
    total = np.zeros(len(q), np.int64)
    for shard in getattr(store, "shards", [store]):
        for seg in shard.segments:
            keys = seg.cell_hash[: seg.n_real]
            lo = np.searchsorted(keys, q, side="left")
            hi = np.searchsorted(keys, q, side="right")
            total += hi - lo
            if live_only:
                dead = ~shard.alive[seg.table_id[: seg.n_real]]
                csum = np.concatenate([[0], np.cumsum(dead)])
                total -= csum[hi] - csum[lo]
    return total


def _then(mutate):
    """A step that mutates the LiveLake and goes on with it."""
    def step(ll, tmp_path):
        mutate(ll)
        return ll
    return step


def _grow_capacity(ll):
    for s in getattr(ll.store, "shards", [ll.store]):
        s.grow_capacity(ll.store.n_tables * 2)


def _snapshot_restore(ll, tmp_path):
    snap.save(ll.store, tmp_path / "lake")
    return LiveLake(store=snap.load(tmp_path / "lake"), auto_compact=False)


# drops 2 and 4 land in one multi-table segment on both stores (a sharded
# lake places g on shard g % 2); reclaiming ids on compaction reassigns
# ``alive`` (a sharded lake cannot reclaim)
COUNT_STEPS = [
    ("add_table", _then(lambda ll: ll.add_table(extra_table(0)))),
    ("drop_in_multi_table_segment", _then(lambda ll: ll.drop_table(2))),
    ("second_drop_same_segment", _then(lambda ll: ll.drop_table(4))),
    ("drop_last_live_table_of_run",
     _then(lambda ll: ll.drop_table(max(ll.live_ids())))),
    ("compact", _then(lambda ll: ll.compact(
        full=True, reclaim_ids=not hasattr(ll.store, "shards")))),
    ("drop_after_compact", _then(lambda ll: ll.drop_table(6))),
    ("snapshot_restore", _snapshot_restore),
    ("drop_after_restore", _then(lambda ll: ll.drop_table(8))),
    ("grow_capacity", _then(_grow_capacity)),
]


@pytest.mark.parametrize("last", [name for name, _ in COUNT_STEPS])
@pytest.mark.parametrize("sharded", [False, True], ids=["single", "sharded"])
def test_host_counts_live_only_matches_per_posting_formula(sharded, last,
                                                           tmp_path):
    """Live-only counts key their tombstone correction on each segment's
    dead tables; after every mutation of the sequence (up to ``last``)
    they equal the per-posting formula exactly, and the tombstone-inclusive
    counts are untouched."""
    from repro.core.hashing import hash_array
    from repro.dist.shard import ShardedStore
    lake = small_live_lake(seed=14)
    store = ShardedStore(lake, n_shards=2) if sharded else None
    ll = LiveLake(lake, store=store, auto_compact=False)
    vals = [v for t in list(lake.tables) + [extra_table(0)]
            for c in t.columns for v in c]
    q = np.unique(hash_array(vals))
    for name, step in COUNT_STEPS:
        ll = step(ll, tmp_path)
        for live_only in (True, False, True):        # the memo's second use
            got = ll.store.host_counts(q, live_only=live_only)
            np.testing.assert_array_equal(
                got, per_posting_counts(ll.store, q, live_only), err_msg=name)
        if name == last:
            break


def test_seeker_ranking_and_answers_match_per_posting_counts(monkeypatch):
    """With one table tombstoned, the optimizer's cost features, its ranked
    order, and the served ids and float32 scores of an ``mc & sc & kw``
    query are those the per-posting live-only formula gives."""
    from repro.core.cost_model import CostModel
    from repro.serve.engine import DiscoveryEngine
    from repro.store.segments import SegmentStore
    lake = small_live_lake(seed=23)
    dead, other = lake.tables[2], lake.tables[7]
    # a second SC seeker: the estimates, not the rules, order the two SCs
    q = (blend.mc([(dead.columns[0][r], dead.columns[1][r])
                   for r in range(6)], k=20)
         & blend.sc(list(dead.columns[0][:10]), k=20)
         & blend.kw([dead.columns[1][0], other.columns[1][1]], k=20)
         & blend.sc(list(other.columns[0][:4]), k=20)).top(10)
    cost = CostModel()
    for kind in ("KW", "SC", "MC"):
        cost.weights[kind] = np.array([0.0, 0.0, 0.0, 1.0])   # log1p(freq)

    def served():
        engine = DiscoveryEngine(lake, live=True, cost_model=cost)
        engine.drop_table(2)
        session = engine.session
        plan = session.compile(q).plan
        stats = [session.executor.seeker_stats(n.spec)
                 for n in plan.nodes.values() if n.is_seeker]
        ranked = session.explain(q, execute=False).physical_order
        resp = engine.serve(q, fused=True)
        return stats, ranked, resp.table_ids, np.asarray(resp.scores)

    stats, ranked, ids, scores = served()
    monkeypatch.setattr(
        SegmentStore, "host_counts", lambda self, h, live_only=False:
        per_posting_counts(self, np.asarray(h), live_only))
    want_stats, want_ranked, want_ids, want_scores = served()
    assert stats == want_stats
    assert ranked == want_ranked and any(len(v) == 4 for v in ranked.values())
    assert ids == want_ids
    assert scores.dtype == np.float32
    np.testing.assert_array_equal(scores, want_scores)


# --------------------------------------------------------------------------
# sketch tier: mutation / compaction / snapshot parity (approx discovery)
# --------------------------------------------------------------------------

SKETCH_FIELDS = ("kmv", "kmv_m", "tbl_kmv", "minhash", "samp_rows",
                 "samp_hash", "samp_quad")


def _assert_sketches_equal(got, want, msg=""):
    assert set(got) == set(want), msg
    for t in got:
        assert got[t].tbl_m == want[t].tbl_m, (msg, t)
        for f in SKETCH_FIELDS:
            np.testing.assert_array_equal(
                getattr(got[t], f), getattr(want[t], f),
                err_msg=f"{msg} table {t} field {f}")


def test_sketch_tier_survives_mutations_bit_identically():
    """Live-store sketches after add/drop/compact == a from-scratch build of
    the surviving tables (sketches are content-addressed, so the comparison
    is field-exact even though the rebuild assigns different table ids)."""
    lake = small_live_lake(seed=61)
    session = blend.connect(lake, live=True)
    tbl = dict(enumerate(lake.tables))
    for i in range(3):
        t = extra_table(i)
        tbl[session.add_table(t)] = t
    session.drop_table(5)
    del tbl[5]
    live_ids = session.live.live_ids()
    live_map = session.live.store.sketch_map()
    assert set(live_map) == set(live_ids)
    rebuilt = build_index(DataLake([tbl[t] for t in live_ids]))
    for pos, tid in enumerate(live_ids):
        for f in SKETCH_FIELDS:
            np.testing.assert_array_equal(
                getattr(live_map[tid], f), getattr(rebuilt.sketches[pos], f),
                err_msg=f"tid {tid} field {f}")
    before = dict(live_map)
    session.compact()                # merge must re-derive identical sketches
    _assert_sketches_equal(session.live.store.sketch_map(), before, "compact")


def test_sketch_tier_snapshot_roundtrip(tmp_path):
    lake = small_live_lake(seed=63)
    session = blend.connect(lake, live=True)
    session.add_table(extra_table(4))
    session.drop_table(2)
    before = dict(session.live.store.sketch_map())
    session.snapshot(tmp_path / "sk")
    restored = blend.restore(tmp_path / "sk")
    assert (restored.live.store.sketch_config
            == session.live.store.sketch_config)
    _assert_sketches_equal(restored.live.store.sketch_map(), before,
                           "restore")


def test_approx_query_parity_through_mutations():
    """approx(epsilon=0) ids stay identical to exact ids at every mutation
    stage — the sketch packs must track the store epoch, not go stale."""
    lake = small_live_lake(seed=65)
    session = blend.connect(lake, live=True, cache=True)
    t3 = lake.tables[3]
    vals = list(t3.columns[0][:8])
    specs = [Seekers.SC(vals, k=10), Seekers.KW(vals, k=10),
             Seekers.Correlation(vals, [float(i) for i in range(8)], k=10,
                                 h=64)]

    def check(stage):
        for spec in specs:
            p = Plan()
            p.add("out", spec)
            exact = session.query(p)
            approx = session.query(p, approx={"epsilon": 0.0})
            assert approx.ids == exact.ids, (stage, spec.kind)
            assert approx.approx is not None, (stage, spec.kind)

    check("initial")
    tid = session.add_table(extra_table(6))
    check("after add")
    session.drop_table(tid)
    session.drop_table(5)
    check("after drop")
    session.compact()
    check("after compact")
