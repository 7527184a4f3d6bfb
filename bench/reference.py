"""The plain reference that decides ``correct``.

It evaluates the benchmark's own query trees (``bench/traffic/loadgen.py``)
over the generator's integer lake (``bench/lakegen.py``) with NumPy, and
imports nothing of the program.  Its semantics are those of
``bench/oracle_plain.py`` (a copy of the repository's brute-force oracle),
which ``tests/bench`` holds it to seeker by seeker; it is vectorised over an
inverted index of token ids so that thousands of answers check in seconds.

What the served path computes, and so what this evaluates:

* seekers: SC = distinct query values in the best column; KW = distinct
  query values anywhere in the table; MC = distinct query tuples whose
  values all occur in one row; C = the QCR score ``|2a - n| / n`` in
  float32 over the (join column, numeric column) pairs with ``n >= 3``,
  numeric cells split at their column's mean.  Each seeker keeps its
  top-``k`` positive scores, ties to the lower table id.
* combiners: AND sums scores and intersects masks, OR takes the max and
  unites, SUB keeps the left side's tables that the right side lacks,
  COUNTER counts the inputs that matched; then the node's own top-``k``.
* the optimizer, which the server runs by default (the paper's Section
  VII-B): the seeker children of an AND run in rule order (KW, SC, C, MC,
  then fewer query values first), and each seeker used only there sees
  only the tables every seeker ranked before it kept; the right side of a
  SUB, when it is a seeker used only there, sees only the left side's
  tables.  A seeker shared by several parents runs unrestricted once.
"""
from __future__ import annotations

import numpy as np

RULE_RANK = {"KW": 0, "SC": 1, "C": 2, "MC": 3}
MIN_SUPPORT = 3
H_SAMPLE = 256


class Reference:
    """``cap`` keeps only the first ``cap`` postings of every value, in
    (table, column, row) order: the control of ``bench/control.py``, a
    probe window too small for the lake, never used to decide ``correct``."""

    def __init__(self, lake, cap: int | None = None):
        self.lake = lake
        self.cap = cap
        T = lake.n_tables
        self.n_tables = T
        self.stride = int(lake.rows.max(initial=1))
        n_cat = lake.rows * lake.ncat
        tab = np.repeat(np.arange(T, dtype=np.int64), n_cat)
        pos = np.arange(len(lake.cat), dtype=np.int64) - lake.cat_off[tab]
        col = pos // lake.rows[tab]
        row = pos % lake.rows[tab]
        order = np.argsort(lake.cat, kind="stable")
        self.p_tok = lake.cat[order].astype(np.int64)
        self.p_tab = tab[order]
        self.p_col = col[order]
        self.p_row = row[order]
        self.off = np.searchsorted(self.p_tok, np.arange(lake.vocab + 1))
        # numeric cells: 1 where the value is >= its column's mean (the
        # mean taken as np.mean over the column's float64 values)
        quad = np.zeros(len(lake.num), np.int8)
        for t in np.nonzero(lake.nnum)[0]:
            r = int(lake.rows[t])
            for k in range(int(lake.nnum[t])):
                o = int(lake.num_off[t]) + k * r
                vals = lake.num[o:o + r] / lake.scale
                quad[o:o + r] = vals >= vals.mean()
        self.quad = quad

    # ------------------------------------------------------------ postings
    def gather(self, toks):
        """Indices of every posting of ``toks`` and, for each, the position
        in ``toks`` it belongs to."""
        toks = np.asarray(toks, np.int64)
        starts, ends = self.off[toks], self.off[toks + 1]
        lens = ends - starts
        if self.cap is not None:
            lens = np.minimum(lens, self.cap)
        rep = np.repeat(np.arange(len(toks)), lens)
        first = np.cumsum(lens) - lens
        idx = starts[rep] + np.arange(int(lens.sum())) - first[rep]
        return idx, rep

    def n_postings(self, toks) -> int:
        toks = np.asarray(toks, np.int64)
        return int((self.off[toks + 1] - self.off[toks]).sum())

    # ------------------------------------------------------------- seekers
    def sc(self, values):
        q = np.unique(np.asarray(values, np.int64))
        idx, rep = self.gather(q)
        tc = self.p_tab[idx] * 8 + self.p_col[idx]
        tc = np.unique(tc * len(q) + rep) // max(len(q), 1)
        counts = np.bincount(tc, minlength=self.n_tables * 8)
        return counts.reshape(self.n_tables, 8).max(axis=1).astype(np.float32)

    def kw(self, values):
        q = np.unique(np.asarray(values, np.int64))
        idx, rep = self.gather(q)
        t = np.unique(self.p_tab[idx] * len(q) + rep) // max(len(q), 1)
        return np.bincount(t, minlength=self.n_tables).astype(np.float32)

    def mc(self, tuples):
        tups = list(dict.fromkeys(tuple(t) for t in tuples))
        if not tups:
            return np.zeros(self.n_tables, np.float32)
        n_cols = len(tups[0])
        arr = np.asarray(tups, np.int64)
        span = self.n_tables * self.stride
        keys = []
        for j in range(n_cols):
            idx, rep = self.gather(arr[:, j])
            rk = self.p_tab[idx] * self.stride + self.p_row[idx]
            keys.append(np.unique(rep * span + rk))
        k, n = np.unique(np.concatenate(keys), return_counts=True)
        hit = k[n == n_cols]
        tt = np.unique(hit // span * self.n_tables
                       + (hit % span) // self.stride)
        return np.bincount(tt % self.n_tables,
                           minlength=self.n_tables).astype(np.float32)

    def c(self, values, target, h=H_SAMPLE, sampling="conv"):
        if sampling != "conv":
            raise NotImplementedError("the reference covers conv sampling")
        pairs = list(dict.fromkeys(zip(values, target)))
        out = np.zeros(self.n_tables, np.float32)
        if not pairs:
            return out
        tgt = np.array([float(p[1]) for p in pairs])
        qbit = (tgt >= tgt.mean()).astype(np.int8)
        idx, rep = self.gather([p[0] for p in pairs])
        tab, cj, row = self.p_tab[idx], self.p_col[idx], self.p_row[idx]
        lake = self.lake
        nn = lake.nnum[tab]
        rep2 = np.repeat(np.arange(len(idx)), nn)
        k = np.arange(int(nn.sum())) - (np.cumsum(nn) - nn)[rep2]
        t2, r2 = tab[rep2], row[rep2]
        keep = r2 < h                       # conv sampling: rank == row
        rep2, k, t2, r2 = rep2[keep], k[keep], t2[keep], r2[keep]
        quad = self.quad[lake.num_off[t2] + k * lake.rows[t2] + r2]
        agree = quad == qbit[rep[rep2]]
        key = (t2 * 8 + cj[rep2]) * 8 + k
        uk, inv = np.unique(key, return_inverse=True)
        n_all = np.bincount(inv).astype(np.float32)
        n_agree = np.bincount(inv, weights=agree).astype(np.float32)
        ok = n_all >= MIN_SUPPORT
        score = np.abs(np.float32(2.0) * n_agree[ok] - n_all[ok]) / n_all[ok]
        np.maximum.at(out, uk[ok] // 64, score.astype(np.float32))
        return out

    def raw(self, seek):
        _, kind, values, _k, target = seek
        if kind == "SC":
            return self.sc(values)
        if kind == "KW":
            return self.kw(values)
        if kind == "MC":
            return self.mc(values)
        if kind == "C":
            return self.c(values, target)
        raise ValueError(kind)

    def least_bytes(self, q) -> int:
        """The fewest bytes any probe of ``q``'s seekers must move: 4 per
        distinct query value and 4 per posting it matches (for MC the
        postings of each tuple's least frequent value; for C also 1 per
        numeric cell of a joined row).  Counted from the request alone."""
        total = 0
        for s in seekers(q):
            _, kind, values, _k, _t = s
            if kind == "MC":
                tups = list(dict.fromkeys(values))
                arr = np.asarray(tups, np.int64)
                cnt = self.off[arr + 1] - self.off[arr]
                total += 4 * arr.size + 4 * int(cnt.min(axis=1).sum())
                continue
            q_ids = np.unique(np.asarray(values, np.int64))
            total += 4 * len(q_ids) + 4 * self.n_postings(q_ids)
            if kind == "C":
                idx, _ = self.gather(q_ids)
                total += int(self.lake.nnum[self.p_tab[idx]].sum())
        return total

    # ----------------------------------------------------------- combiners
    def topk(self, scores, k):
        k = self.n_tables if k is None else min(int(k), self.n_tables)
        pos = np.nonzero(scores > 0)[0]
        pos = pos[np.lexsort((pos, -scores[pos]))][:k]
        mask = np.zeros(self.n_tables, bool)
        mask[pos] = True
        return np.where(mask, scores, np.float32(0.0)), mask

    def run(self, q):
        """(scores float32 [n_tables], mask) of one query tree."""
        q = normalize(q)
        parents: dict = {}
        _parents(q, None, parents)
        memo: dict = {}

        def seeker(s, allowed=None):
            if s in memo:
                return memo[s]
            raw = self.raw(s)
            if allowed is not None and len(parents[s]) == 1:
                raw = np.where(allowed, raw, np.float32(0.0))
            memo[s] = self.topk(raw, s[3])
            return memo[s]

        def ev(n):
            if n in memo:
                return memo[n]
            op = n[0]
            if op == "seek":
                return seeker(n)
            _, k, kids = n
            if op == "and":
                seekers = [c for c in kids if c[0] == "seek"]
                if len(seekers) >= 2:
                    ranked = sorted(seekers, key=lambda s: (RULE_RANK[s[1]],
                                                            len(s[2])))
                    results, allowed = [], None
                    for s in ranked:
                        r = seeker(s, allowed)
                        results.append(r)
                        allowed = r[1] if allowed is None else allowed & r[1]
                    results += [ev(c) for c in kids if c[0] != "seek"]
                else:
                    results = [ev(c) for c in kids]
                scores, mask = results[0]
                for s, m in results[1:]:
                    mask = mask & m
                    scores = scores + s
            elif op == "or":
                results = [ev(c) for c in kids]
                scores, mask = results[0]
                for s, m in results[1:]:
                    mask = mask | m
                    scores = np.maximum(scores, s)
            elif op == "sub":
                a = ev(kids[0])
                right = kids[1]
                if right[0] == "seek" and right not in memo:
                    b = seeker(right, a[1])
                else:
                    b = ev(right)
                scores, mask = a[0], a[1] & ~b[1]
            elif op == "counter":
                scores = np.zeros(self.n_tables, np.float32)
                for c in kids:
                    scores = scores + ev(c)[1].astype(np.float32)
                mask = scores > 0
            else:
                raise ValueError(op)
            memo[n] = self.topk(np.where(mask, scores, np.float32(0.0)), k)
            return memo[n]

        return ev(q)

    def answer(self, q):
        """(ranked table ids, float32 scores [n_tables]) of one query."""
        scores, mask = self.run(q)
        ids = np.nonzero(mask)[0]
        ids = ids[np.argsort(-scores[ids], kind="stable")]
        return [int(t) for t in ids], scores


def seekers(q):
    if q[0] == "seek":
        yield q
        return
    for c in q[2]:
        yield from seekers(c)


def _parents(n, parent, out):
    if parent is not None:
        out.setdefault(n, set()).add(parent)
    else:
        out.setdefault(n, set())
    if n[0] != "seek":
        for c in n[2]:
            _parents(c, n, out)


def normalize(q):
    """The expression as the server's rewrite leaves it: nested AND (OR)
    children without their own cut merge into their parent, and repeated
    children of an AND (OR) count once."""
    if q[0] == "seek":
        return q
    op, k, kids = q
    kids = tuple(normalize(c) for c in kids)
    if op in ("and", "or"):
        flat = []
        for c in kids:
            flat.extend(c[2] if c[0] == op and c[1] is None else (c,))
        kids = tuple(dict.fromkeys(flat))
        if len(kids) == 1:
            kid = kids[0]
            if k is None:
                return kid
            ck = kid[3] if kid[0] == "seek" else kid[1]
            nk = k if ck is None else min(ck, k)
            return kid[:3] + (nk,) + kid[4:] if kid[0] == "seek" \
                else (kid[0], nk, kid[2])
    return (op, k, kids)
