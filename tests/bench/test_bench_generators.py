"""The lake and traffic generators: a pure function of the seed, and the
same amount of work for every seed."""
import numpy as np
import pytest

from bench_tiny import CELLS, cell, config, full_cell

from bench import harness, lakegen
from bench.traffic import loadgen

SEEDS = (3, 2**31 + 17)


@pytest.mark.parametrize("name", ["gittables_uniform", "webtables_uniform",
                                  "gittables", "webtables"])
def test_config_postings_are_what_the_generator_builds(name):
    cfg = lakegen.load_config(name)
    rows, ncat, nnum = lakegen.table_shapes(cfg)
    assert len(rows) == cfg["n_tables"]
    assert int((rows * (ncat + nnum)).sum()) == cfg["n_postings"]
    assert int((rows * nnum).sum()) == cfg["n_numeric"]
    assert rows.max() <= cfg["row_stride"]
    assert (ncat + nnum).max() <= cfg["max_cols"]


def test_lake_is_a_function_of_the_seed():
    cfg = config(lakegen.load_config("gittables_uniform"))
    a, b = lakegen.generate(cfg, SEEDS[1]), lakegen.generate(cfg, SEEDS[1])
    c = lakegen.generate(cfg, SEEDS[0])
    for f in ("rows", "ncat", "nnum", "cat", "num"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.cat, c.cat)
    # another seed permutes the same table shapes: same postings
    assert a.n_postings == c.n_postings
    assert sorted(zip(a.rows, a.ncat)) == sorted(zip(c.rows, c.ncat))


def test_lake_tables_carry_the_generated_values():
    cfg = config(lakegen.load_config("webtables_uniform"))
    lake = lakegen.generate(cfg, 5)
    t = lake.tables()
    for i in (0, lake.n_tables - 1):
        assert t.tables[i].n_rows == lake.rows[i]
        assert t.tables[i].n_cols == lake.ncat[i] + lake.nnum[i]
        assert t.tables[i].columns[0] == [lakegen.token(v)
                                          for v in lake.column(i, 0)]


@pytest.mark.parametrize("name", [c for c in CELLS
                                  if not c.endswith("union")])
def test_open_loop_schedule_is_seeded_and_same_sized(name):
    c = full_cell(name)
    tr, rate = c["traffic"], float(c["params"]["rate_rps"])
    a = loadgen.open_loop(tr, rate, 10.0, SEEDS[0])
    b = loadgen.open_loop(tr, rate, 10.0, SEEDS[0])
    d = loadgen.open_loop(tr, rate, 10.0, SEEDS[1])
    w = loadgen.open_loop(tr, rate, 10.0, SEEDS[0],
                          offset=loadgen.WARM_OFFSET)
    assert [(r.due, r.qid, r.lane) for r in a] == \
        [(r.due, r.qid, r.lane) for r in b]
    assert len(a) == len(d) == len(w)
    assert [r.qid for r in a] != [r.qid for r in d]
    # every seed sends each pool rank, lane and tenant equally often
    for f in ("qid", "lane", "tenant"):
        assert sorted(getattr(r, f) for r in a) == \
            sorted(getattr(r, f) for r in d)
    assert [r.due for r in a] != [r.due for r in w]
    assert all(0.0 <= r.due < 10.0 for r in a)
    # the arrival process keeps its gaps: same sorted gaps bar the wrap
    ga = np.sort(np.diff([r.due for r in a]))
    gd = np.sort(np.diff([r.due for r in d]))
    assert np.isclose(np.median(ga), np.median(gd), rtol=0.05)


@pytest.mark.parametrize("name", CELLS)
def test_queries_are_seeded_and_use_every_template(name):
    c = cell(name)
    lake = lakegen.generate(c["config"], 11)
    tr = c["traffic"]
    if tr["loop"] == "open":
        p1 = loadgen.query_pool(tr, lake, np.random.default_rng([11, 1]))
        p2 = loadgen.query_pool(tr, lake, np.random.default_rng([11, 1]))
        other = lakegen.generate(c["config"], 12)
        p3 = loadgen.query_pool(tr, other, np.random.default_rng([12, 1]))
        assert p1 == p2 and p1 != p3
        if tr["pool"].get("table_seed") is not None:
            # every seed gives each pool rank a table of the same size
            assert [len(q[2][0][2]) for q in p1] == \
                [len(q[2][0][2]) for q in p3]
        ops = {q[0] for q in p1[:len(tr["shapes"])]}
        assert ops == {("seek" if "seek" in s else
                        [k for k in s if k != "k"][0])
                       for s in tr["shapes"]}
        queries = p1
    else:
        sched = tr["schedule_seed"]
        tabs = loadgen.closed_loop_tables(lake, sched, 2 * lake.n_tables)
        other = lakegen.generate(c["config"], 12)
        otabs = loadgen.closed_loop_tables(other, sched, 2 * lake.n_tables)
        # every seed asks for the same table sizes in the same order, and
        # each pass asks for every table once
        assert list(lake.rows[tabs]) == list(other.rows[otabs])
        assert list(lake.ncat[tabs]) == list(other.ncat[otabs])
        assert list(tabs) != list(otabs)
        assert sorted(tabs[:lake.n_tables]) == list(range(lake.n_tables))
        assert list(loadgen.closed_loop_tables(lake, sched, 50, offset=1)) \
            != list(tabs[:50])
        tabs = tabs[:20]
        queries = [loadgen.make_query(tr["query"], lake, int(t),
                                      np.random.default_rng([11, int(t)]), 6)
                   for t in tabs]
        for t, q in zip(tabs, queries):
            assert q[0] == "counter" and len(q[2]) == lake.ncat[t]
    for q in queries[:12]:
        loadgen.to_expr(q)


def test_zipf_values_are_heavy_tailed():
    """The corpus value model: a few tokens hold most postings, the tail
    holds few; the uniform stand-in keeps every token near the mean."""
    z = lakegen.generate(config(lakegen.load_config("gittables"),
                                n_tables=120, vocab=2000), 9)
    u = lakegen.generate(config(lakegen.load_config("gittables_uniform"),
                                n_tables=120, vocab=200), 9)
    cz = np.bincount(z.cat, minlength=2000)
    cu = np.bincount(u.cat, minlength=200)
    assert len(z.cat) == len(u.cat)
    assert cz.max() > 100 * np.median(cz[cz > 0])
    assert cz[0] > cz[9] > cz[99]
    assert cu.max() < 2 * cu.mean()
