"""Brute-force oracle: the ground truth the engine is conformance-tested to.

A pure-NumPy reference implementation of all four seekers and all four
combiners over a *raw* lake — no unified index, no MatchEngine, no kernels,
no jax.  Every score is computed by direct set algebra over the table cells,
mirroring the executor's documented semantics:

* value identity follows ``core.hashing.hash_value`` canonicalization
  (integral floats join like ints, bools like ints) — but by *value*, never
  by hash;
* SC/KW query values and C (join, target) pairs dedupe; MC tuples dedupe
  raw (a permuted duplicate tuple still scores separately);
* C replicates the in-index QCR reformulation: per (table, join-col,
  num-col) triple, ``|2 * n_agree - n_all| / n_all`` over the h-sampled
  numeric cells row-joined to the query key matches, with the ``rand``
  sampling permutation re-derived from the index's documented per
  (table-name, column) seeding;
* the top-k select matches ``combiners.topk_result`` bit-for-bit (stable
  index-order tie-break, positive scores only), and the QCR division is done
  in float32 so fractional scores compare exactly against the device.

The fused path holds every posting of a value, however hot
(tests/test_heavy_tail.py holds it to this oracle past 1,024 postings); the
unfused seekers' dense windows stop at the m_cap ladder, and the correlation
row join at ``row_cap`` numeric columns per row — the sweep in
tests/test_oracle.py sizes its lakes accordingly.

Used by tests/test_oracle.py (both probe backends vs this oracle) and by
tests/test_query_cache.py (cache parity leans on the same ground truth).
"""
from __future__ import annotations

import numpy as np

from repro.core.hashing import fnv1a_bytes

MIN_SUPPORT = 3


def canon(v):
    """Value canonicalization mirroring ``hash_value`` (2.0 == 2, True == 1),
    applied before any set membership below."""
    if isinstance(v, (bool, np.bool_)):
        return int(v)
    if isinstance(v, (float, np.floating)):
        v = float(v)
        return int(v) if v.is_integer() else v
    if isinstance(v, (int, np.integer)):
        return int(v)
    return v


def _columns(table):
    return [[canon(v) for v in col] for col in table.columns]


def _is_numeric_col(values) -> bool:
    seen = False
    for v in values:
        if v is None:
            continue
        if isinstance(v, (bool, str)):
            return False
        if not isinstance(v, (int, float, np.integer, np.floating)):
            return False
        seen = True
    return seen


# --------------------------------------------------------------------- seekers
def oracle_sc(lake, values) -> np.ndarray:
    """COUNT(DISTINCT value) per (table, column), table score = best column."""
    qs = {canon(v) for v in values}
    out = np.zeros(lake.n_tables, np.float32)
    for t, tab in enumerate(lake.tables):
        cols = _columns(tab)
        out[t] = max((len(qs & set(c)) for c in cols), default=0)
    return out


def oracle_kw(lake, values) -> np.ndarray:
    """Distinct query values present anywhere in the table."""
    qs = {canon(v) for v in values}
    out = np.zeros(lake.n_tables, np.float32)
    for t, tab in enumerate(lake.tables):
        allv: set = set()
        for c in _columns(tab):
            allv |= set(c)
        out[t] = len(qs & allv)
    return out


def oracle_mc(lake, tuples) -> np.ndarray:
    """Query tuples exactly joinable with some row (every tuple value in the
    same row, any column, any order).  Tuples dedupe raw, like the executor's
    ``dict.fromkeys`` — permuted duplicates each count."""
    qts = list(dict.fromkeys(tuple(t) for t in tuples))
    out = np.zeros(lake.n_tables, np.float32)
    for t, tab in enumerate(lake.tables):
        cols = _columns(tab)
        rows = [{c[r] for c in cols} for r in range(tab.n_rows)]
        n = 0
        for tup in qts:
            vals = [canon(v) for v in tup]
            if any(all(v in row for v in vals) for row in rows):
                n += 1
        out[t] = n
    return out


def _rand_ranks(table_name: str, col: int, n_rows: int,
                seed: int = 0) -> np.ndarray:
    """The index's ``rank_rand`` shuffle, re-derived from its documented per
    (table name, column) seeding (core/index.py table_postings)."""
    rng = np.random.default_rng(
        [seed, fnv1a_bytes(str(table_name).encode()), col])
    return rng.permutation(n_rows)


def oracle_c(lake, join_values, target_values, h_sample: int = 256,
             sampling: str = "conv", seed: int = 0,
             min_support: int = MIN_SUPPORT) -> np.ndarray:
    """QCR correlation scores: for every (join value -> target) pair, join
    on rows containing the value (any column is the join column), collect
    the h-sampled numeric cells of those rows per numeric column, and score
    each (join-col, num-col) triple ``|2a - n| / n``; table score = best
    triple with ``n >= min_support``."""
    pairs = list(dict.fromkeys(zip(join_values, target_values)))
    tgt = np.array([float(p[1]) for p in pairs])
    qbit = (tgt >= tgt.mean()).astype(np.int8)
    out = np.zeros(lake.n_tables, np.float32)
    for t, tab in enumerate(lake.tables):
        cols = _columns(tab)
        numeric = [c for c, col in enumerate(tab.columns)
                   if _is_numeric_col(col)]
        quad = {c: (np.array([float(v) for v in tab.columns[c]])
                    >= np.mean([float(v) for v in tab.columns[c]]))
                .astype(np.int8) for c in numeric}
        rank = {c: (np.arange(tab.n_rows) if sampling == "conv"
                    else _rand_ranks(tab.name, c, tab.n_rows, seed))
                for c in numeric}
        n_all: dict = {}
        n_agree: dict = {}
        for (v, _), bit in zip(pairs, qbit):
            vq = canon(v)
            for cj, col in enumerate(cols):
                for r, cell in enumerate(col):
                    if cell != vq:
                        continue
                    for nc in numeric:
                        if rank[nc][r] >= h_sample:
                            continue
                        key = (cj, nc)
                        n_all[key] = n_all.get(key, 0) + 1
                        if quad[nc][r] == bit:
                            n_agree[key] = n_agree.get(key, 0) + 1
        best = np.float32(0.0)
        for key, n in n_all.items():
            if n < min_support:
                continue
            a = np.float32(n_agree.get(key, 0))
            score = np.abs(np.float32(2.0) * a - np.float32(n)) / np.float32(n)
            best = max(best, score)
        out[t] = best
    return out


def oracle_seeker(lake, spec) -> np.ndarray:
    """Raw (pre-top-k) scores for one ``SeekerSpec``."""
    if spec.kind == "SC":
        return oracle_sc(lake, spec.values)
    if spec.kind == "KW":
        return oracle_kw(lake, spec.values)
    if spec.kind == "MC":
        return oracle_mc(lake, spec.values)
    if spec.kind == "C":
        return oracle_c(lake, spec.values, spec.target, h_sample=spec.h,
                        sampling=spec.sampling)
    raise ValueError(spec.kind)


# ------------------------------------------------------------------- combiners
def oracle_topk(scores: np.ndarray, k: int):
    """``combiners.topk_result``: top-k positive scores, stable index-order
    tie-break (lax.top_k keeps the lower index first on ties)."""
    scores = np.asarray(scores, np.float32)
    k = min(k, scores.shape[0])
    order = np.argsort(-scores, kind="stable")[:k]
    keep = scores[order] > 0
    mask = np.zeros(scores.shape[0], bool)
    mask[order[keep]] = True
    return np.where(mask, scores, np.float32(0.0)), mask


def _maybe_topk(scores, mask, k):
    if k is None:
        return np.where(mask, scores, np.float32(0.0)), mask
    return oracle_topk(np.where(mask, scores, np.float32(0.0)), k)


def oracle_intersect(results, k=None):
    scores, mask = results[0]
    scores, mask = scores.copy(), mask.copy()
    for s, m in results[1:]:
        mask &= m
        scores = scores + s
    return _maybe_topk(scores, mask, k)


def oracle_union(results, k=None):
    scores, mask = results[0]
    scores, mask = scores.copy(), mask.copy()
    for s, m in results[1:]:
        mask |= m
        scores = np.maximum(scores, s)
    return _maybe_topk(scores, mask, k)


def oracle_difference(a, b, k=None):
    mask = a[1] & ~b[1]
    return _maybe_topk(np.where(mask, a[0], np.float32(0.0)), mask, k)


def oracle_counter(results, k=None):
    counts = np.zeros_like(results[0][0])
    for _, m in results:
        counts = counts + m.astype(np.float32)
    return _maybe_topk(counts, counts > 0, k)


# ------------------------------------------------------------- plan evaluation
def oracle_run(lake, plan):
    """Evaluate a physical ``Plan`` the way ``Executor.run(optimize=False)``
    does — every seeker unrestricted, memoized per node — entirely against
    the raw lake.  Returns ``(scores, mask)`` of the output node."""
    memo: dict = {}

    def eval_node(name):
        if name in memo:
            return memo[name]
        node = plan.nodes[name]
        if node.is_seeker:
            rs = oracle_topk(oracle_seeker(lake, node.spec), node.spec.k)
        else:
            deps = [eval_node(d) for d in node.deps]
            kind, k = node.spec.kind, node.spec.k
            if kind == "intersect":
                rs = oracle_intersect(deps, k)
            elif kind == "union":
                rs = oracle_union(deps, k)
            elif kind == "difference":
                rs = oracle_difference(deps[0], deps[1], k)
            elif kind == "counter":
                rs = oracle_counter(deps, k)
            else:
                raise ValueError(kind)
        memo[name] = rs
        return rs

    return eval_node(plan.output)


def oracle_ids(scores: np.ndarray, mask: np.ndarray) -> list:
    """Selected table ids sorted by score desc — ``ResultSet.ids``."""
    ids = np.nonzero(mask)[0]
    return [int(t) for t in ids[np.argsort(-scores[ids], kind="stable")]]
