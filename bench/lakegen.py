"""Seeded lake generator: one configuration file in, one lake out.

A configuration (``bench/configs/<name>.json``) fixes the lake's scale and
per-table shape.  The multiset of table shapes (rows, categorical columns,
numeric columns) is drawn from the configuration's own ``shape_seed``, so
every run of a configuration indexes exactly the same number of postings;
``--seed`` only permutes which table gets which shape and draws the cell
values.  That keeps the build's work, and with it ``setup_s``, the same
from seed to seed.

Cell values come in two kinds:

* categorical cells: token ids drawn from ``vocab`` tokens, uniformly, or
  with ``"zipf_s"`` set, token ``i`` with weight ``1 / (i + 1)**zipf_s``
  (Zipf's law); the program sees the string ``tok_<id>``;
* numeric cells: integers ``g`` on a grid, clipped to ``[-clip, clip]``;
  the program sees the float ``g / 10**decimals``.

The generator keeps the integer form (:class:`Lake`) for the reference in
``bench/reference.py``; :meth:`Lake.tables` builds the program's input.
Categorical columns come first in every table, numeric ones last.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

CONFIG_DIR = Path(__file__).resolve().parent / "configs"

#: independent random streams derived from --seed
STREAM_SHAPE_ORDER, STREAM_VALUES = 1, 2


def load_config(name_or_path) -> dict:
    p = Path(name_or_path)
    if not p.suffix:
        p = CONFIG_DIR / f"{name_or_path}.json"
    with open(p) as f:
        return json.load(f)


def token(i: int) -> str:
    return f"tok_{i}"


def table_shapes(cfg: dict):
    """(rows, categorical cols, numeric cols) per table, in shape-seed
    order."""
    t = cfg["tables"]
    rng = np.random.default_rng(t["shape_seed"])
    n = cfg["n_tables"]
    lo, hi = t["rows_log_uniform"]
    rows = np.floor(lo * (hi / lo) ** rng.random(n)).astype(np.int64)
    rows = np.minimum(rows, t["rows_clip"])
    clo, chi = t["categorical_cols"]
    ncat = rng.integers(clo, chi + 1, n)
    by_cat = {int(k): int(v)
              for k, v in t["numeric_cols_by_categorical"].items()}
    nnum = np.array([by_cat[int(c)] for c in ncat], np.int64)
    return rows, ncat.astype(np.int64), nnum


@dataclass
class Lake:
    """The generated lake in integer form.

    Table ``t`` has ``rows[t]`` rows, ``ncat[t]`` categorical columns whose
    token ids are ``cat[cat_off[t] + c * rows[t] + r]``, and ``nnum[t]``
    numeric columns whose grid values are ``num[num_off[t] + k * rows[t] +
    r]``.  Numeric column ``k`` is column ``ncat[t] + k`` of the table."""
    rows: np.ndarray
    ncat: np.ndarray
    nnum: np.ndarray
    cat_off: np.ndarray
    num_off: np.ndarray
    cat: np.ndarray
    num: np.ndarray
    vocab: int
    scale: float
    #: shape index (``table_shapes`` order) of each table; traffic picks
    #: tables by shape, so every seed sends the same sizes
    shape: np.ndarray

    def table_of_shape(self, j) -> np.ndarray:
        """The table that has shape ``j`` in this lake."""
        return np.argsort(self.shape)[j]

    @property
    def n_tables(self) -> int:
        return len(self.rows)

    @property
    def n_postings(self) -> int:
        return len(self.cat) + len(self.num)

    def column(self, t: int, c: int) -> np.ndarray:
        """Token ids of categorical column ``c`` of table ``t``."""
        o = self.cat_off[t] + c * self.rows[t]
        return self.cat[o:o + self.rows[t]]

    def tables(self):
        """The lake as the program's ``Table`` objects (lists of Python
        strings and floats)."""
        from repro.core.lake import DataLake, Table

        words = np.array([token(i) for i in range(self.vocab)], dtype=object)
        cat_vals = words[self.cat].tolist()
        num_vals = (self.num / self.scale).tolist()
        out = []
        for t in range(self.n_tables):
            r = int(self.rows[t])
            cols = []
            o = int(self.cat_off[t])
            for _ in range(int(self.ncat[t])):
                cols.append(cat_vals[o:o + r])
                o += r
            o = int(self.num_off[t])
            for _ in range(int(self.nnum[t])):
                cols.append(num_vals[o:o + r])
                o += r
            out.append(Table(f"t{t}", cols))
        return DataLake(out)


def generate(cfg: dict, seed: int) -> Lake:
    rows, ncat, nnum = table_shapes(cfg)
    order = np.random.default_rng([seed, STREAM_SHAPE_ORDER]).permutation(
        len(rows))
    rows, ncat, nnum = rows[order], ncat[order], nnum[order]
    vals = cfg["values"]
    rng = np.random.default_rng([seed, STREAM_VALUES])
    n_cat, n_num = rows * ncat, rows * nnum
    cat_off = np.concatenate([[0], np.cumsum(n_cat)[:-1]])
    num_off = np.concatenate([[0], np.cumsum(n_num)[:-1]])
    n = int(n_cat.sum())
    if vals.get("zipf_s") is None:
        cat = rng.integers(0, vals["vocab"], n, dtype=np.int32)
    else:
        w = 1.0 / np.arange(1, vals["vocab"] + 1) ** float(vals["zipf_s"])
        cat = np.searchsorted(np.cumsum(w / w.sum()), rng.random(n),
                              side="right")
        cat = np.minimum(cat, vals["vocab"] - 1).astype(np.int32)
    nv = vals["numeric"]
    scale = 10.0 ** nv["decimals"]
    x = rng.normal(nv["mean"], nv["std"], int(n_num.sum()))
    num = np.clip(np.rint(x * scale), -nv["clip"] * scale,
                  nv["clip"] * scale).astype(np.int32)
    return Lake(rows=rows, ncat=ncat, nnum=nnum, cat_off=cat_off,
                num_off=num_off, cat=cat, num=num, vocab=vals["vocab"],
                scale=scale, shape=order)
