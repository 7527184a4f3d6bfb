import numpy as np
import pytest


@pytest.fixture(scope="session")
def small_lake():
    from repro.core.lake import synthetic_lake
    return synthetic_lake(n_tables=60, rows=24, cols=4, vocab=800, seed=7)


@pytest.fixture(scope="session")
def small_index(small_lake):
    from repro.core.index import build_index
    return build_index(small_lake)


@pytest.fixture(scope="session")
def small_executor(small_index):
    from repro.core.executor import Executor
    return Executor(small_index)


def brute_force_sc(lake, query_values):
    """Best single-column distinct overlap per table."""
    qs = set(query_values)
    out = np.zeros(lake.n_tables)
    for t, tab in enumerate(lake.tables):
        out[t] = max(len(qs & set(c)) for c in tab.columns)
    return out


def brute_force_kw(lake, query_values):
    qs = set(query_values)
    out = np.zeros(lake.n_tables)
    for t, tab in enumerate(lake.tables):
        allv = set()
        for c in tab.columns:
            allv |= set(c)
        out[t] = len(qs & allv)
    return out


def brute_force_mc(lake, tuples):
    """Tuples exactly joinable (all values in one row, any column order)."""
    out = np.zeros(lake.n_tables)
    for t, tab in enumerate(lake.tables):
        rows = [set(tab.row(r)) for r in range(tab.n_rows)]
        n = 0
        for tup in set(tuples):
            if any(all(v in row for v in tup) for row in rows):
                n += 1
        out[t] = n
    return out
