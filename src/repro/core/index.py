"""The unified BLEND index: one columnar fact table serving all seekers.

AllTables(CellValue, TableId, ColumnId, RowId, SuperKey, Quadrant) from the
paper becomes a struct-of-arrays sorted by (cell_hash, table, col, row):

* ``cell_hash``      u32 — FNV-1a of the cell value (string-free TPU layout)
* ``table_id/col_id/row_id`` i32 — the DataXFormer inverted-index columns
* ``superkey lo/hi`` u32x2 — XASH-style 64-bit row bloom digest (MATE)
* ``quadrant``       i8  — 1/0 = numeric >= / < column mean, -1 = non-numeric
                     (our in-DB QCR reformulation: one boolean per cell
                     instead of the baseline's per-column-pair sketches)
* ``rank_conv/rank_rand`` i32 — position of the posting within its
                     (table, column) group in RowId order / in a seeded
                     shuffle — realizing the paper's convenience vs random
                     h-sampling entirely inside the index.

Auxiliary views derived from the same arrays (not separate indexes):
* bucket offsets over the top ``bucket_bits`` hash bits (the B-tree analogue;
  also the layout the Pallas ``bucket_probe`` kernel consumes),
* a numeric-postings permutation sorted by (table, row) — the join side of
  the correlation seeker,
* an optional AoS (row-store) interleave for the PostgreSQL-vs-column-store
  comparison of Fig 5.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from repro.core import hashing
from repro.core.lake import DataLake
from repro.core.sketch import SketchConfig, sketch_tables

def _ceil_pow2(n: int) -> int:
    m = 1
    while m < n:
        m *= 2
    return m


def validate_row_stride(n_tables: int, row_stride: int, max_rows: int = 0):
    """Rowkey soundness guard: ``rowkey = table * row_stride + row`` must be
    collision-free and fit int32.  A stride smaller than the longest table
    silently aliases rowkeys across tables and corrupts the MC validation and
    correlation joins — reject it loudly instead."""
    if max_rows > row_stride:
        raise ValueError(
            f"row_stride={row_stride} is smaller than the longest table "
            f"({max_rows} rows): rowkeys would alias across tables and "
            f"corrupt MC/correlation joins; widen the stride (build_index "
            f"auto-widens; pass row_stride >= {_ceil_pow2(max_rows)})")
    if n_tables * row_stride >= 2 ** 31:
        raise ValueError(
            f"int32 rowkey overflow: {n_tables} tables * row_stride="
            f"{row_stride} exceeds 2^31; shard the lake "
            f"(see dist/shard.py)")


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


@dataclass
class UnifiedIndex:
    cell_hash: np.ndarray        # u32 [N] sorted
    table_id: np.ndarray         # i32 [N]
    col_id: np.ndarray           # i32 [N]
    row_id: np.ndarray           # i32 [N]
    superkey_lo: np.ndarray      # u32 [N]
    superkey_hi: np.ndarray      # u32 [N]
    quadrant: np.ndarray         # i8  [N]
    rank_conv: np.ndarray        # i32 [N]
    rank_rand: np.ndarray        # i32 [N]
    # numeric-by-row view (indices into the arrays above)
    num_perm: np.ndarray         # i32 [M] numeric postings by (table,row)
    num_rowkey: np.ndarray       # i32 [M] sorted rowkeys of num_perm
    # metadata
    n_tables: int
    max_cols: int
    bucket_bits: int
    bucket_offsets: np.ndarray   # i64 [2^bits + 1]
    table_rows: np.ndarray       # i32 [n_tables]
    # rowkey = table * row_stride + row.  No silent default: a stride smaller
    # than the longest table aliases rowkeys across tables (validated by
    # ``validate_row_stride`` at build time; build_index auto-widens).
    row_stride: int
    # approximate tier: {table_id: core.sketch.TableSketch} built from the
    # same posting arrays (see core/sketch.py for the determinism contract)
    sketches: dict = field(default_factory=dict, compare=False)
    sketch_config: SketchConfig = field(default_factory=SketchConfig,
                                        compare=False)

    @property
    def n_postings(self) -> int:
        return len(self.cell_hash)

    def storage_bytes(self) -> int:
        core = sum(a.nbytes for a in (
            self.cell_hash, self.table_id, self.col_id, self.row_id,
            self.superkey_lo, self.superkey_hi, self.quadrant,
            self.rank_conv, self.rank_rand))
        views = self.num_perm.nbytes + self.num_rowkey.nbytes + \
            self.bucket_offsets.nbytes
        return core + views

    def device_arrays(self):
        """The jnp-side dict the seekers consume."""
        import jax.numpy as jnp
        return {
            "hash": jnp.asarray(self.cell_hash),
            "table": jnp.asarray(self.table_id),
            "col": jnp.asarray(self.col_id),
            "row": jnp.asarray(self.row_id),
            "sk_lo": jnp.asarray(self.superkey_lo),
            "sk_hi": jnp.asarray(self.superkey_hi),
            "quadrant": jnp.asarray(self.quadrant),
            "rank_conv": jnp.asarray(self.rank_conv),
            "rank_rand": jnp.asarray(self.rank_rand),
            "row_cells": jnp.asarray(row_cells_view(
                self.cell_hash, self.table_id, self.col_id, self.row_id,
                self.max_cols)),
            "num_rowkey": jnp.asarray(self.num_rowkey),
            "num_table": jnp.asarray(self.table_id[self.num_perm]),
            "num_col": jnp.asarray(self.col_id[self.num_perm]),
            "num_quadrant": jnp.asarray(self.quadrant[self.num_perm]),
            "num_rank_conv": jnp.asarray(self.rank_conv[self.num_perm]),
            "num_rank_rand": jnp.asarray(self.rank_rand[self.num_perm]),
        }

    def host_counts(self, q_hashes: np.ndarray) -> np.ndarray:
        """Match counts per query hash (planner statistics, O(|Q| log N))."""
        lo = np.searchsorted(self.cell_hash, q_hashes, side="left")
        hi = np.searchsorted(self.cell_hash, q_hashes, side="right")
        return (hi - lo).astype(np.int64)

    def padded_buckets(self, width: int):
        """Padded radix-bucket layout for the Pallas probe kernel: returns
        (bucket_hashes u32 [2^bits, width], bucket_payload i32 [...],
        overflow_count).  Fully vectorized: one scatter over the postings
        instead of a Python loop over 2^bits buckets."""
        nb = 1 << self.bucket_bits
        bh = np.full((nb, width), hashing.MISSING, np.uint32)
        bp = np.full((nb, width), -1, np.int32)
        shift = 32 - self.bucket_bits
        buckets = (self.cell_hash >> shift).astype(np.int64)
        # position of each posting within its bucket
        starts = self.bucket_offsets[:-1]
        pos = np.arange(self.n_postings, dtype=np.int64) - starts[buckets]
        keep = pos < width
        counts = np.diff(self.bucket_offsets)
        overflow = int(np.maximum(counts - width, 0).sum())
        bh[buckets[keep], pos[keep]] = self.cell_hash[keep]
        bp[buckets[keep], pos[keep]] = np.nonzero(keep)[0].astype(np.int32)
        return bh, bp, overflow

    def max_bucket_count(self) -> int:
        """Largest bucket population (the lossless probe-kernel width)."""
        return int(np.diff(self.bucket_offsets).max(initial=0))

    def aos_view(self) -> np.ndarray:
        """Row-store interleave (hash,t,c,r,sk_lo,sk_hi,quadrant) i64-packed
        into an int32 [N, 7] matrix — the 'PostgreSQL layout' of Fig 5."""
        out = np.empty((self.n_postings, 7), np.int32)
        out[:, 0] = self.cell_hash.view(np.int32)
        out[:, 1] = self.table_id
        out[:, 2] = self.col_id
        out[:, 3] = self.row_id
        out[:, 4] = self.superkey_lo.view(np.int32)
        out[:, 5] = self.superkey_hi.view(np.int32)
        out[:, 6] = self.quadrant
        return out


POSTING_KEYS = ("cell_hash", "table_id", "col_id", "row_id", "superkey_lo",
                "superkey_hi", "quadrant", "rank_conv", "rank_rand")


def table_postings(table, tid: int, *, seed: int = 0,
                   with_quadrants: bool = True) -> dict:
    """Unsorted posting arrays for one table (dict over ``POSTING_KEYS``).

    Shared by ``build_index`` and the LiveLake segment builder
    (store/segments.py), so an incrementally-built segment holds exactly the
    arrays a from-scratch rebuild would produce.  ``rank_rand`` is therefore
    seeded per (table name, column) — not from one build-wide RNG stream —
    so the shuffle a column gets is independent of build order.
    """
    nr, nc = table.n_rows, table.n_cols
    col_hashes, col_quads, col_rand = [], [], []
    for c, col in enumerate(table.columns):
        col_hashes.append(hashing.hash_array(col))
        if with_quadrants and _is_numeric_col(col):
            vals = np.array([float(v) for v in col])
            col_quads.append((vals >= vals.mean()).astype(np.int8))
        else:
            col_quads.append(np.full(nr, -1, np.int8))
        rng = np.random.default_rng(
            [seed, hashing.fnv1a_bytes(str(table.name).encode()), c])
        col_rand.append(rng.permutation(nr).astype(np.int32))
    # row superkeys: OR of position-independent cell bits (MATE-style
    # bloom; alignment is verified exactly at query time)
    if nc:
        all_h = np.concatenate(col_hashes)
        all_r = np.tile(np.arange(nr), nc)
        sk = hashing.superkeys_for_rows(all_h, np.zeros_like(all_h), all_r, nr)
    else:
        sk = np.zeros(0, np.uint64)
    lo32, hi32 = hashing.split_u64(sk)
    n = nr * nc
    return {
        "cell_hash": np.concatenate(col_hashes) if nc
        else np.zeros(0, np.uint32),
        "table_id": np.full(n, tid, np.int32),
        "col_id": np.repeat(np.arange(nc, dtype=np.int32), nr),
        "row_id": np.tile(np.arange(nr, dtype=np.int32), nc),
        "superkey_lo": np.tile(lo32, nc),
        "superkey_hi": np.tile(hi32, nc),
        "quadrant": np.concatenate(col_quads) if nc else np.zeros(0, np.int8),
        "rank_conv": np.tile(np.arange(nr, dtype=np.int32), nc),
        "rank_rand": np.concatenate(col_rand) if nc else np.zeros(0, np.int32),
    }


_POSTING_DTYPES = {"cell_hash": np.uint32, "quadrant": np.int8,
                   "superkey_lo": np.uint32, "superkey_hi": np.uint32}


def concat_postings(per_table: list) -> dict:
    """Concatenate per-table posting dicts (empty-safe)."""
    return {k: np.concatenate([p[k] for p in per_table]) if per_table
            else np.zeros(0, _POSTING_DTYPES.get(k, np.int32))
            for k in POSTING_KEYS}


def sort_postings(parts: dict) -> dict:
    """Lexsort concatenated posting arrays by (cell_hash, table, col, row)."""
    order = np.lexsort((parts["row_id"], parts["col_id"], parts["table_id"],
                        parts["cell_hash"]))
    return {k: v[order] for k, v in parts.items()}


def bucket_offsets_for(cell_hash: np.ndarray, bucket_bits: int) -> np.ndarray:
    """Offsets of the radix buckets over the top ``bucket_bits`` hash bits."""
    nb = 1 << bucket_bits
    shift = 32 - bucket_bits
    return np.searchsorted(
        (cell_hash >> shift).astype(np.uint32),
        np.arange(nb + 1, dtype=np.uint32), side="left").astype(np.int64)


def numeric_view(parts: dict, row_stride: int):
    """(num_perm, num_rowkey) — numeric postings permuted to (table, row)
    order.  The permutation itself is stride-independent (any collision-free
    stride induces the same (table, row) order), so widening the stride only
    recomputes ``num_rowkey`` values."""
    numeric = np.nonzero(parts["quadrant"] >= 0)[0]
    rowkey = parts["table_id"][numeric].astype(np.int64) * row_stride + \
        parts["row_id"][numeric].astype(np.int64)
    np_order = np.argsort(rowkey, kind="stable")
    return numeric[np_order].astype(np.int32), \
        rowkey[np_order].astype(np.int32)


def row_cells_view(cell_hash, table_id, col_id, row_id, width: int):
    """Each posting's row as its cells' hashes, ``[n, width]`` (u32): column
    ``c`` holds the hash of the row's cell in column ``c``, and a column the
    row lacks repeats the posting's own hash.  Whether a posting's row holds
    a value is then one gather and ``width`` compares, whatever the value's
    count (the fused MC validation); a repeated cell of the row changes no
    answer."""
    out = np.repeat(np.asarray(cell_hash, np.uint32)[:, None], width, axis=1)
    if not len(cell_hash):
        return out
    rowkey = table_id.astype(np.int64) * (int(row_id.max()) + 1) + row_id
    _, row = np.unique(rowkey, return_inverse=True)
    row = row.reshape(-1)
    cells = np.zeros((int(row.max()) + 1, width), np.uint32)
    held = np.zeros(cells.shape, bool)
    cells[row, col_id] = cell_hash
    held[row, col_id] = True
    return np.where(held[row], cells[row], out)


def build_index(lake: DataLake, bucket_bits: int = 12, seed: int = 0,
                with_quadrants: bool = True,
                row_stride: int | None = None,
                sketch_config: SketchConfig | None = None) -> UnifiedIndex:
    max_cols = 1
    table_rows = np.zeros(max(lake.n_tables, 1), np.int32)
    per_table = []
    for t, table in enumerate(lake.tables):
        max_cols = max(max_cols, table.n_cols)
        table_rows[t] = table.n_rows
        per_table.append(table_postings(table, t, seed=seed,
                                        with_quadrants=with_quadrants))
    parts = concat_postings(per_table)
    parts = sort_postings(parts)

    max_rows = int(table_rows.max(initial=1))
    row_stride = max(_ceil_pow2(max_rows), row_stride or 0)
    validate_row_stride(lake.n_tables, row_stride, max_rows)

    bucket_offsets = bucket_offsets_for(parts["cell_hash"], bucket_bits)
    num_perm, num_rowkey = numeric_view(parts, row_stride)

    cell_hash, table_id, col_id, row_id = (
        parts["cell_hash"], parts["table_id"], parts["col_id"],
        parts["row_id"])
    superkey_lo, superkey_hi = parts["superkey_lo"], parts["superkey_hi"]
    quadrant = parts["quadrant"]
    rank_conv, rank_rand = parts["rank_conv"], parts["rank_rand"]

    sketch_config = sketch_config or SketchConfig()
    return UnifiedIndex(
        cell_hash=cell_hash, table_id=table_id, col_id=col_id, row_id=row_id,
        superkey_lo=superkey_lo, superkey_hi=superkey_hi, quadrant=quadrant,
        rank_conv=rank_conv, rank_rand=rank_rand,
        num_perm=num_perm, num_rowkey=num_rowkey,
        n_tables=lake.n_tables, max_cols=max_cols, bucket_bits=bucket_bits,
        bucket_offsets=bucket_offsets, table_rows=table_rows,
        row_stride=row_stride,
        sketches=sketch_tables(parts, seed=seed, config=sketch_config),
        sketch_config=sketch_config)
