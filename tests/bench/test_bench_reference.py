"""The benchmark's reference against the repository's oracle (seeker by
seeker) and against the served path (whole requests of each cell's
traffic), and its control against the reference."""
import numpy as np
import pytest

from bench_tiny import CELLS, cell, config

from bench import lakegen, oracle_plain, reference
from bench.traffic import loadgen


@pytest.fixture(scope="module")
def small():
    lake = lakegen.generate(config(lakegen.load_config("gittables_uniform"),
                                   n_tables=70, vocab=250), 2**33 + 1)
    return lake, lake.tables(), reference.Reference(lake)


def test_seekers_match_the_oracle(small):
    lake, tables, ref = small
    rng = np.random.default_rng(0)
    tok = lakegen.token
    for _ in range(12):
        t = int(rng.integers(0, lake.n_tables))
        c0, c1 = lake.column(t, 0), lake.column(t, 1)
        rows = rng.choice(len(c0), min(6, len(c0)), replace=False)
        vals = [int(c0[r]) for r in rows] + [int(c1[rows[0]])]
        words = [tok(v) for v in vals]
        tups = [(int(c0[r]), int(c1[r])) for r in rows] + [(vals[0],
                                                            vals[0])]
        target = [float(j % 4) for j in range(len(vals))]
        assert np.array_equal(ref.sc(vals), oracle_plain.oracle_sc(tables,
                                                                   words))
        assert np.array_equal(ref.kw(vals), oracle_plain.oracle_kw(tables,
                                                                   words))
        assert np.array_equal(ref.mc(tups), oracle_plain.oracle_mc(
            tables, [tuple(map(tok, tp)) for tp in tups]))
        assert np.array_equal(ref.c(vals, target), oracle_plain.oracle_c(
            tables, words, target))


def _served(name, seed=2**31 + 9, c=None, **opts):
    """(lake, queries, reference answers, served responses, engine,
    requests) for tiny-lake requests of one cell's traffic; ``opts`` go to
    the program's ``connect``."""
    import blend
    from repro.serve.engine import DiscoveryEngine

    c = cell(name) if c is None else c
    lake = lakegen.generate(c["config"], seed)
    tr = c["traffic"]
    if tr["loop"] == "open":
        queries = loadgen.query_pool(tr, lake,
                                     np.random.default_rng([seed, 1]))[:32]
    else:
        queries = [loadgen.make_query(tr["query"], lake, t,
                                      np.random.default_rng([seed, t]), 6)
                   for t in range(16)]
    eng = DiscoveryEngine(None, session=blend.connect(lake.tables(),
                                                      live=True, **opts))
    exprs = [loadgen.to_expr(q) for q in queries]
    ref = reference.Reference(lake)
    want = [ref.answer(q) for q in queries]
    got = [r for i in range(0, len(exprs), 16)
           for r in eng.serve_many(exprs[i:i + 16], fused=True)]
    return lake, queries, want, got, eng, exprs


@pytest.mark.parametrize("name", CELLS)
def test_reference_matches_the_served_path(name):
    lake, _, want, got, _, _ = _served(name)
    for (ids, scores), resp in zip(want, got):
        s = np.asarray(resp.scores)
        assert resp.overflow == 0 and not resp.degraded
        assert resp.table_ids == ids
        assert np.array_equal(s[:lake.n_tables], scores)
        assert not s[lake.n_tables:].any()


def test_reference_follows_the_optimizer(small):
    """An AND of seekers with small cuts: the optimizer threads each
    seeker's surviving tables into the next, so with it off answers differ.
    The reference agrees with the served path, which runs it."""
    import blend
    from repro.serve.engine import DiscoveryEngine

    lake, tables, ref = small
    rng = np.random.default_rng(5)
    queries = []
    for _ in range(16):
        t = int(rng.integers(0, lake.n_tables))
        rows = rng.choice(int(lake.rows[t]), 4, replace=False)
        vals = tuple(int(lake.column(t, 0)[r]) for r in rows)
        tups = tuple((int(lake.column(t, 0)[r]), int(lake.column(t, 1)[r]))
                     for r in rows)
        queries.append(("and", 5, (("seek", "MC", tups, 2, ()),
                                   ("seek", "SC", vals, 2, ()),
                                   ("seek", "KW", vals[:2], 3, ()))))
    eng = DiscoveryEngine(None, session=blend.connect(tables, live=True))
    exprs = [loadgen.to_expr(q) for q in queries]
    want = [ref.answer(q)[0] for q in queries]
    served = eng.serve_many(exprs, fused=True)
    plain = eng.serve_many(exprs, optimize=False, fused=True)
    assert [r.table_ids for r in served] == want
    assert any(r.table_ids != ids for r, ids in zip(plain, want))


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_comparison(name):
    """A probe window cut to the first postings of each value (the control)
    answers differently from the reference."""
    c = cell(name)
    lake = lakegen.generate(c["config"], 21)
    tr = c["traffic"]
    if tr["loop"] == "open":
        queries = loadgen.query_pool(tr, lake, np.random.default_rng(3))
    else:
        queries = [loadgen.make_query(tr["query"], lake, t,
                                      np.random.default_rng(t), 6)
                   for t in range(20)]
    exact = reference.Reference(lake)
    control = reference.Reference(lake, cap=2)
    wrong = sum(exact.answer(q)[0] != control.answer(q)[0] for q in queries)
    assert wrong > len(queries) // 4


def test_zipf_lake_overflows_the_program_and_a_wider_window_agrees():
    """The held-out cells' fault at a size a test holds: on a Zipf lake
    whose hottest value passes m_cap_max=1024 postings, the served union
    requests overflow and come back with other answers than the
    reference's; the same program with the window widened past the
    hottest value (a second witness) answers as the reference does."""
    from bench import harness

    c = cell("gittables_uniform.union", n_tables=150, vocab=400,
             rows_clip=64)
    c["config"]["values"]["zipf_s"] = 1.0
    lake, _, want, got, _, _ = _served("", c=c)
    hottest = int(np.bincount(lake.cat).max())
    assert hottest > 1024
    assert any(r.overflow for r in got)
    assert any(r.table_ids != ids for (ids, _), r in zip(want, got))
    cap = 1 << int(np.ceil(np.log2(hottest)))
    lake, _, want, got, _, _ = _served("", c=c, m_cap_max=cap)
    for (ids, scores), resp in zip(want, got):
        assert resp.overflow == 0
        assert resp.table_ids == ids
        assert np.array_equal(np.asarray(resp.scores)[:lake.n_tables],
                              scores)
    assert harness.load_cell("gittables.union",
                             spec_file="bench/held_out.json")[
        "config"]["values"]["zipf_s"] == 1.0
