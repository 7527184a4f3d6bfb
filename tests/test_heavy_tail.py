"""Heavy-tailed values on the served path: one value holds more postings
than the dense window's old 1,024-slot ceiling, and every answer still
equals the brute-force oracle (tests/oracle.py) exactly, with overflow 0.

The fused seekers probe one flat window sized by the summed match counts
(``MatchEngine.window``); the MC validation reads each candidate's row of
cell hashes (``MatchEngine.row_holds``).  Covered: SC, KW, MC and C through
``DiscoveryEngine.serve_many`` on both probe backends, 1 vs 2 shards bit for
bit, a live lake after a table holding hot postings is dropped, and the MC
tuple of a cold initiator and a hot partner, which the dense window
answered wrong with no overflow flag.  Also the primitives themselves and
the unfused MC seekers' overflow for a truncated validation probe.
"""
import jax
import numpy as np
import pytest

import blend
from repro.core.executor import Executor
from repro.core import fused
from repro.core import seekers as seek
from repro.core.index import build_index
from repro.core.lake import DataLake, Table
from repro.core.match import flat_slots
from repro.core.plan import Plan, Seekers
from repro.obs import trace as otrace
from repro.serve.engine import DiscoveryEngine
from repro.serve.server import DiscoveryServer

from oracle import (oracle_c, oracle_ids, oracle_kw, oracle_mc, oracle_sc,
                    oracle_topk)

HOT = "NA"
N_TABLES, ROWS = 14, 90
K = 20


def heavy_lake(seed: int = 3) -> DataLake:
    """Tables of a cold key column, a column that is the hot value in nine
    rows of ten (a Zipf tail elsewhere), and a numeric column.  The hot
    value also fills a few key cells, so a table holds it in two columns."""
    rng = np.random.default_rng(seed)
    tables = []
    for t in range(N_TABLES):
        key = [f"k{t}_{r}" if r % 15 else HOT for r in range(ROWS)]
        tail = [HOT if rng.random() < 0.9 else f"v{int(rng.zipf(1.5)) % 40}"
                for _ in range(ROWS)]
        num = [float(np.round(rng.normal(), 3)) for _ in range(ROWS)]
        tables.append(Table(f"t{t}", [key, tail, num]))
    return DataLake(tables)


LAKE = heavy_lake()


def hot_postings(lake) -> int:
    return sum(v == HOT for t in lake.tables for c in t.columns for v in c)


def queries(lake):
    """One query per seeker kind, each probing the hot value; MC pairs cold
    initiators with the hot partner across every table, the last ones
    included (the hot run's tail, past the old ceiling)."""
    last = lake.tables[-1]
    sc_vals = [HOT, "v1", "v2", last.columns[0][1], lake.tables[0].columns[0][2]]
    tuples = [(lake.tables[t].columns[0][r], lake.tables[t].columns[1][r])
              for t in (0, 5, N_TABLES - 1) for r in (1, 2, 3, 4)]
    tuples += [(HOT, HOT), ("v1", HOT)]
    target = [float(i % 3) for i in range(len(sc_vals))]
    return {
        "SC": (blend.sc(sc_vals, k=K), lambda lk: oracle_sc(lk, sc_vals)),
        "KW": (blend.kw(sc_vals, k=K), lambda lk: oracle_kw(lk, sc_vals)),
        "MC": (blend.mc(tuples, k=K), lambda lk: oracle_mc(lk, tuples)),
        "C": (blend.corr(sc_vals, target, k=K, h=64),
              lambda lk: oracle_c(lk, sc_vals, target, h_sample=64)),
    }


def serve(session, exprs):
    return DiscoveryEngine(None, session=session).serve_many(exprs,
                                                             fused=True)


def assert_oracle(resp, want_scores, n_tables):
    scores, mask = oracle_topk(want_scores, K)
    got = np.asarray(resp.scores)
    assert resp.overflow == 0 and not resp.degraded
    assert resp.table_ids == oracle_ids(scores, mask)
    assert np.array_equal(got[:n_tables], scores)
    assert not got[n_tables:].any()


def test_lake_passes_the_old_ceiling():
    assert hot_postings(LAKE) > 1024


@pytest.mark.parametrize("backend", ["sorted", "bucket"])
@pytest.mark.parametrize("kind", ["SC", "KW", "MC", "C"])
def test_heavy_values_served_exactly(backend, kind):
    expr, oracle = queries(LAKE)[kind]
    session = blend.connect(LAKE, live=True, backend=backend,
                            interpret=True)
    (resp,) = serve(session, [expr])
    assert_oracle(resp, oracle(LAKE), LAKE.n_tables)


def test_mc_cold_initiator_with_hot_partner():
    """The silent wrong answer: the initiator is the cold key, the partner
    the hot value, and the matching row sits in the last table, past the
    first 1,024 postings of the hot value.  The dense window probed the
    partner at the initiator's capacity and dropped its overflow."""
    last = LAKE.tables[-1]
    rows = [r for r in range(ROWS) if last.columns[1][r] == HOT
            and last.columns[0][r] != HOT][:3]
    tuples = [(last.columns[0][r], HOT) for r in rows]
    want = oracle_mc(LAKE, tuples)
    assert want[N_TABLES - 1] == len(tuples)
    (resp,) = serve(blend.connect(LAKE, live=True),
                    [blend.mc(tuples, k=K)])
    assert_oracle(resp, want, LAKE.n_tables)


def test_one_and_two_shards_bit_identical():
    qs = queries(LAKE)
    exprs = [e for e, _ in qs.values()]
    one = serve(blend.connect(LAKE, live=True), exprs)
    two = serve(blend.connect(LAKE, shards=2), exprs)
    for (_, oracle), a, b in zip(qs.values(), one, two):
        assert a.table_ids == b.table_ids
        assert np.array_equal(np.asarray(a.scores), np.asarray(b.scores))
        assert_oracle(b, oracle(LAKE), LAKE.n_tables)


@pytest.mark.parametrize("backend", ["sorted", "bucket"])
def test_live_lake_after_dropping_a_hot_table(backend):
    """A dropped table's hot postings stay tombstoned in the base segment
    (they still take window slots); a delta segment adds more of the hot
    value.  Answers equal the oracle over the lake as it now stands."""
    session = blend.connect(LAKE, live=True, backend=backend,
                            interpret=True)
    extra = Table("extra", [[HOT] * 40, [f"x{i}" for i in range(40)],
                            [float(i) for i in range(40)]])
    session.add_table(extra)
    session.drop_table(5)
    now = list(LAKE.tables)
    now[5] = Table("t5", [[], [], []])
    now = DataLake(now + [extra])
    qs = queries(LAKE)
    resps = serve(session, [e for e, _ in qs.values()])
    for (_, oracle), resp in zip(qs.values(), resps):
        assert_oracle(resp, oracle(now), now.n_tables)


def holders(ex, lists) -> dict:
    """Each distinct value hash of a batch's seekers -> the seekers (their
    places in ``lists``) that hold it."""
    out: dict = {}
    for i, vals in enumerate(lists):
        for h in ex._hash_many(vals):
            out.setdefault(int(h), set()).add(i)
    return out


def launch_spans(rec, kind):
    """The ``capacity`` and ``probe:<kind>`` spans of a recorded batch."""
    spans = [s for root in rec.roots for s in root.walk()]
    return (next(s for s in spans if s.name == "capacity"),
            next(s for s in spans if s.name == f"probe:{kind}"))


@pytest.mark.parametrize("n_seekers,backend", [(3, "sorted"),
                                               (3, "bucket"),
                                               (40, "sorted")])
@pytest.mark.parametrize("kind", ["SC", "KW"])
def test_seekers_sharing_values_walk_each_value_once(kind, n_seekers,
                                                     backend):
    """Seekers of one batch share the hot value and some others: the
    window holds each distinct value once (``capacity.matched`` is the
    union's postings, not the seekers' sum), and every seeker still gets
    its own exact answer, past 32 seekers too.  The ``probe`` span counts
    the batch's distinct holder sets, none of them expanded."""
    make, oracle = {"SC": (blend.sc, oracle_sc), "KW": (blend.kw, oracle_kw)}[
        kind]
    lists = [[HOT, f"v{i % 7}", LAKE.tables[i % N_TABLES].columns[0][i + 1]]
             for i in range(n_seekers)]
    session = blend.connect(LAKE, live=True, backend=backend,
                            interpret=True)
    rec = otrace.Recorder()
    with otrace.recording(rec):
        resps = serve(session, [make(v, k=K) for v in lists])
    for v, resp in zip(lists, resps):
        assert_oracle(resp, oracle(LAKE, v), LAKE.n_tables)
    cap, probe = launch_spans(rec, kind)
    ex = session.executor
    union = np.unique(ex._hash_many([x for v in lists for x in v]))
    each = sum(int(ex.index.host_counts(ex._hashed(v)).sum()) for v in lists)
    assert cap.attrs["matched"] == int(ex.index.host_counts(union).sum())
    assert cap.attrs["matched"] < each
    sets = {frozenset(s) for s in holders(ex, lists).values()}
    assert probe.attrs["sets"] == len(sets)
    assert probe.attrs["expanded"] == 0


N_PAIRED = 16


def paired_lists():
    """16 seekers whose values make more holder sets than a launch of 16
    holds (4 x 16 = 64): one value per pair of seekers (120 sets), the hot
    value held by all, and one value of each seeker's own."""
    pairs = [(a, b) for a in range(N_PAIRED) for b in range(a + 1, N_PAIRED)]
    keys = [c for t in LAKE.tables for c in t.columns[0] if c != HOT]
    pair_vals = [f"v{i}" for i in range(40)] + keys[:len(pairs) - 40]
    own = keys[len(pairs) - 40:len(pairs) - 40 + N_PAIRED]
    return [[HOT, own[i]] + [v for (a, b), v in zip(pairs, pair_vals)
                             if i in (a, b)] for i in range(N_PAIRED)]


@pytest.mark.parametrize("backend,shards", [("sorted", 1), ("bucket", 1),
                                            ("sorted", 2)])
@pytest.mark.parametrize("kind", ["SC", "KW"])
def test_holder_sets_past_the_launch_are_walked_once_per_holder(
        kind, backend, shards):
    """More distinct holder sets than the launch holds: the sets whose
    values would cost the fewest extra slots are expanded, each of their
    values walked once per holding seeker.  Every seeker's ids and float32
    scores still equal the oracle's (1 and 2 shards bit for bit), the
    launch counts 64 sets, and ``capacity.matched`` counts every copy."""
    make, oracle = {"SC": (blend.sc, oracle_sc), "KW": (blend.kw, oracle_kw)}[
        kind]
    lists = paired_lists()
    exprs = [make(v, k=K) for v in lists]
    session = blend.connect(LAKE, live=True, backend=backend,
                            interpret=True) if shards == 1 else \
        blend.connect(LAKE, shards=shards)
    rec = otrace.Recorder()
    with otrace.recording(rec):
        resps = serve(session, exprs)
    for v, resp in zip(lists, resps):
        assert_oracle(resp, oracle(LAKE, v), LAKE.n_tables)
    if shards > 1:
        one = serve(blend.connect(LAKE, live=True), exprs)
        for a, b in zip(one, resps):
            assert a.table_ids == b.table_ids
            assert np.array_equal(np.asarray(a.scores), np.asarray(b.scores))
    cap, probe = launch_spans(rec, kind)
    ex = blend.connect(LAKE, live=True).executor
    held = holders(ex, lists)
    hashes = np.array(list(held), np.uint32)
    counts = dict(zip(held, ex.index.host_counts(hashes)))
    # one value per multi-holder set: its extra slots if walked per holder
    extra = sorted((len(s) - 1) * int(counts[h]) for h, s in held.items()
                   if len(s) > 1)
    n_fit = 4 * N_PAIRED - N_PAIRED          # room left for the singletons
    assert len(extra) > n_fit
    assert probe.attrs["sets"] == 4 * N_PAIRED
    assert probe.attrs["expanded"] == len(extra) - n_fit
    assert cap.attrs["matched"] == int(sum(counts.values())) + \
        sum(extra[:len(extra) - n_fit])


@pytest.mark.parametrize("sync", [True, False])
def test_unfused_mc_flags_a_truncated_validation(sync):
    """The unfused MC seekers probe each validation column at the ladder's
    top rung; when the hot partner passes it, the answer is flagged as
    overflowed instead of coming back short."""
    last = LAKE.tables[-1]
    tuples = [(last.columns[0][r], HOT) for r in (1, 2, 3)]
    plan = Plan()
    plan.add("mc", Seekers.MC(tuples, k=K))
    ex = Executor(build_index(LAKE))
    _, info = ex.run(plan, optimize=False, sync=sync)
    assert info.overflow > 0


# --------------------------------------------------------------------------
# the primitives
# --------------------------------------------------------------------------

@pytest.mark.parametrize("counts", [
    [3, 0, 2, 0, 0, 4],             # empty runs between and after
    [0, 0, 5],                      # empty runs first
    [6, 5, 1],
    [0, 0, 0],                      # nothing matched
])
def test_flat_slots_lay_runs_end_to_end(counts):
    """Every range of slots, from before the first to past the last, holds
    each run's postings in order, marks each run's first slot, and carries
    per-run values (unsigned ones that wrap too) to its slots."""
    cnt = np.array(counts, np.int32)
    lo = np.arange(len(cnt), dtype=np.int32) * 100
    ends = np.cumsum(cnt).astype(np.int32)
    tag = (np.arange(len(cnt), dtype=np.uint32) * np.uint32(0x9E3779B9))
    want = [(r, lo[r] + o, o == 0) for r in range(len(cnt))
            for o in range(cnt[r])]
    total = len(want)
    for length in (1, 3, 7):
        for start in range(-2, total + 2):
            pidx, valid, first, (run, t) = (
                x if isinstance(x, tuple) else np.asarray(x)
                for x in flat_slots(lo, cnt, ends, np.int32(start), length,
                                    (np.arange(len(cnt), dtype=np.int32),
                                     tag)))
            run, t = np.asarray(run), np.asarray(t)
            slots = np.arange(start, start + length)
            assert (valid == ((slots >= 0) & (slots < total))).all()
            got = [(int(r), int(p), bool(f)) for r, p, f, v in
                   zip(run, pidx, first, valid) if v]
            assert got == [w for i, w in enumerate(want)
                           if start <= i < start + length]
            assert (t[valid] == tag[run[valid]]).all()


@pytest.mark.parametrize("kind", ["SC", "KW", "MC", "C"])
def test_chunks_split_groups_exactly(kind, monkeypatch):
    """Windows walked in chunks of 16 to 64 slots, so the hot value's run
    and its (table, column) groups span many chunks: the same answers as
    the oracle's."""
    monkeypatch.setattr(fused, "MIN_CHUNK", 16)
    monkeypatch.setattr(fused, "MAX_CHUNK", 64)
    expr, oracle = queries(LAKE)[kind]
    (resp,) = serve(blend.connect(LAKE, live=True), [expr])
    assert_oracle(resp, oracle(LAKE), LAKE.n_tables)


@pytest.mark.parametrize("matched", [0, 1, 1024, 1025, 20000, 16384,
                                     16385, 10 ** 6])
def test_walk_covers_the_postings(matched):
    chunk, n_chunks = fused._walk(matched)
    assert fused.MIN_CHUNK <= chunk <= fused.MAX_CHUNK
    assert chunk & (chunk - 1) == 0
    assert (n_chunks - 1) * chunk < matched <= n_chunks * chunk or \
        matched == n_chunks == 0


def test_row_holds_finds_a_value_in_any_column_of_the_row():
    session = blend.connect(LAKE, live=True)
    ex = session.executor
    eng = ex.engine
    seg = ex.index.segments[0]
    n = seg.n_real
    rng = np.random.default_rng(0)
    rows = {}
    for h, t, r in zip(seg.cell_hash[:n], seg.table_id[:n], seg.row_id[:n]):
        rows.setdefault((int(t), int(r)), set()).add(int(h))
    pidx = rng.choice(n, 256).astype(np.int32)
    # half the queries a cell of some row, half a cell of the posting's own
    q = np.where(np.arange(256) % 2 == 0,
                 seg.cell_hash[rng.choice(n, 256)],
                 seg.cell_hash[[rng.choice(
                     np.flatnonzero((seg.table_id[:n] == seg.table_id[p]) &
                                    (seg.row_id[:n] == seg.row_id[p])))
                     for p in pidx]]).astype(np.uint32)
    got = np.asarray(eng.row_holds(pidx, q))
    want = [int(h) in rows[(int(seg.table_id[p]), int(seg.row_id[p]))]
            for p, h in zip(pidx, q)]
    assert got.tolist() == want
    assert got[1::2].all() and not got[0::2].all()


def test_capacity_and_probe_spans_count_slots_and_matches():
    """``capacity`` carries the window's slots over the batch's launches and
    the matched postings it holds; each ``probe:<kind>`` span its own
    launch's slots: whole chunks of a power of two of slots."""
    qs = queries(LAKE)
    session = blend.connect(LAKE, live=True)
    server = DiscoveryServer(DiscoveryEngine(None, session=session),
                             trace=True, start=False)
    futs = [server.submit(e) for e, _ in qs.values()]
    server.start()
    resps = [f.result(timeout=600) for f in futs]
    server.stop()
    batches = {id(r.trace.children[1]): r.trace.children[1] for r in resps}
    store = session.executor.index
    for b in batches.values():
        cap = b.find("capacity").attrs
        probes = [s for s in b.walk() if s.name.startswith("probe:")]
        assert cap["slots"] == sum(p.attrs["slots"] for p in probes)
        assert cap["matched"] <= cap["slots"]
        for p in probes:
            shard = p.find("shard:0").attrs
            assert p.attrs["slots"] == shard["slots"]
            assert shard["slots"] % shard["chunk"] == 0
    # one batch of SC over the hot value: matched is its summed counts
    exec_rec = otrace.Recorder()
    with otrace.recording(exec_rec):
        serve(session, [qs["SC"][0]])
    cap = next(s for root in exec_rec.roots for s in root.walk()
               if s.name == "capacity")
    h = np.unique(session.executor._hash_many(
        [v for v in qs["SC"][0].values]))
    assert cap.attrs["matched"] == int(store.host_counts(h).sum())
    chunk, n_chunks = fused._walk(cap.attrs["matched"])
    assert cap.attrs["slots"] == chunk * n_chunks


def test_holder_sets_add_no_program(monkeypatch):
    """SC batches of 1, 3, 8 and 16 seekers, and 16 whose holder sets do
    not all fit: ``SC_seg`` traces once per distinct (chunk, width, padded
    seekers) it meets, and the holder-set operands take their shapes from
    those alone (``[width]`` ids, ``[4 * nsp, nsp]`` sets)."""
    calls = []
    real = seek.sc_seeker_seg

    def recording(eng, q_hash, q_mask, set_id, sets, n_chunks, *, chunk,
                  **kw):
        calls.append((chunk, q_hash.shape, q_mask.shape, set_id.shape,
                      sets.shape))
        return real(eng, q_hash, q_mask, set_id, sets, n_chunks,
                    chunk=chunk, **kw)

    monkeypatch.setattr(seek, "sc_seeker_seg", recording)
    jax.clear_caches()
    before = seek.TRACE_COUNTS["SC_seg"]
    session = blend.connect(LAKE, live=True)
    lists = [[HOT, f"v{i % 7}", LAKE.tables[i % N_TABLES].columns[0][i + 1]]
             for i in range(16)]
    for n in (1, 3, 8, 16):
        serve(session, [blend.sc(v, k=K) for v in lists[:n]])
    rec = otrace.Recorder()
    with otrace.recording(rec):
        serve(session, [blend.sc(v, k=K) for v in paired_lists()])
    assert launch_spans(rec, "SC")[1].attrs["expanded"] > 0
    shapes = {(chunk, q[0], s[1]) for chunk, q, _, _, s in calls}
    assert len(calls) == 5
    assert seek.TRACE_COUNTS["SC_seg"] - before == len(shapes) == \
        len(set(calls))
    for _, q, m, ids, s in calls:
        assert q == m == ids and s == (fused.SETS_PER_SEEKER * s[1], s[1])
