"""MatchEngine: the unified probe layer every seeker routes through.

One object owns the device-resident index arrays, the padded radix-bucket
layouts, and the low-level match primitives.  Since the LiveLake subsystem
(repro/store) the engine is *segment-aware*: the resident index is an ordered
list of immutable sorted segments (one large base + small L0 deltas) and

* ``window(q_hash, q_mask)`` — the flat probe window of the fused seekers:
  every query hash's run of postings in every segment, laid end to end.
  The seekers walk it in fixed-size chunks of slots (``slots``), as many as
  the summed match counts need, so a hot value holds as many slots as it
  has postings and nothing is cut; postings past the last chunk (a stale
  count) are counted as overflow.
* ``probe(q_hash, q_mask, m_cap)`` — the unfused seekers' dense window:
  ``m_cap`` slots per query and segment, concatenated along the match axis
  (``[nq, n_segments * m_cap]``).
* tombstone masks (dropped tables) are applied to ``valid`` inside
  ``probe`` / ``rowjoin``, *before* any group-by stage, and to the fused
  seekers' per-table scores (``live``; a dead table's postings score only
  that table), so mutation parity with a from-scratch rebuild holds
  bit-exactly.

Two interchangeable probe backends: ``"sorted"`` (binary search over each
segment's hash-sorted run) and ``"bucket"`` (the Pallas ``bucket_probe``
kernel over each segment's padded radix-bucket table).  Seeker outputs are
bit-identical across backends (parity-tested in tests/test_match_engine.py)
and across mutation histories (tests/test_livelake.py).

* ``rowjoin(rowkeys, mask, row_cap)`` — the numeric-postings-by-row probe of
  the correlation seeker (same fan-out over per-segment ``num_rowkey`` runs).
* ``bloom(...)`` — the unfused MC seekers' XASH superkey containment stage,
  routed through the ``superkey_filter`` kernel package (the fused MC runs
  no superkey filter: ``row_holds`` validates exactly for less).
* ``qcr(n_agree, n_all)`` — the correlation seeker's scoring epilogue,
  routed through the ``qcr_score`` kernel package.
* ``row_holds(pidx, q_hash)`` — whether a posting's row holds a value: one
  gather of the row's cell hashes (``row_cells``) and a compare per column
  (the fused MC validation; its cost follows the candidates, not the
  value's count).
* ``member(sorted_keys, queries)`` — batched sorted-membership (the
  unfused MC validation join).

The engine is a registered pytree: its arrays are leaves (so jitted seekers
close over nothing) and its configuration — including the static per-segment
bounds — is hashable aux data.  Segments are length-padded onto a power-of-
two ladder (store/segments.py), so a mutation that lands in an already-seen
segment topology re-uses the compiled seekers (zero new traces — the
retrace-free serving contract extends to live lakes).

A sharded lake (dist/shard.py) builds one engine per shard with
``from_store(..., device=...)``, pinning each shard's concatenated arrays to
its own mesh device; the fused executor then dispatches the same jitted
seekers per shard and sums the score matrices on the merge device.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.bucket_probe import ops as bucket_ops
from repro.kernels.qcr_score import ops as qcr_ops
from repro.kernels.superkey_filter import ops as sk_ops

BACKENDS = ("sorted", "bucket")


def probe_sorted(sorted_keys, queries, q_mask, cap):
    """Match range per query in a sorted key array, expanded to [nq, cap].

    Returns (pidx i32 [nq, cap] clipped gather indices, valid bool [nq, cap],
    overflow = matches beyond cap, summed)."""
    lo = jnp.searchsorted(sorted_keys, queries, side="left")
    hi = jnp.searchsorted(sorted_keys, queries, side="right")
    pidx = lo[:, None] + jnp.arange(cap)[None, :]
    valid = (pidx < hi[:, None]) & q_mask[:, None]
    pidx = jnp.clip(pidx, 0, sorted_keys.shape[0] - 1)
    overflow = jnp.sum(jnp.where(q_mask, jnp.maximum(hi - lo - cap, 0), 0))
    return pidx, valid, overflow


def probe_sorted_bounded(sorted_keys, n_real: int, queries, q_mask, cap):
    """``probe_sorted`` over a length-padded sorted run: only the first
    ``n_real`` keys are live postings; the tail is sort-stable sentinel
    padding that must never match (clamping lo/hi to ``n_real`` keeps even
    queries that equal the sentinel from touching it)."""
    lo = jnp.minimum(jnp.searchsorted(sorted_keys, queries, side="left"),
                     n_real)
    hi = jnp.minimum(jnp.searchsorted(sorted_keys, queries, side="right"),
                     n_real)
    pidx = lo[:, None] + jnp.arange(cap)[None, :]
    valid = (pidx < hi[:, None]) & q_mask[:, None]
    pidx = jnp.clip(pidx, 0, sorted_keys.shape[0] - 1)
    overflow = jnp.sum(jnp.where(q_mask, jnp.maximum(hi - lo - cap, 0), 0))
    return pidx, valid, overflow


class Window(NamedTuple):
    """A flat probe window (``MatchEngine.window``): every (segment, query)
    run of postings, laid end to end in segment-major order, so slot
    ``ends[r] - cnt[r] + o`` holds posting ``lo[r] + o``."""
    lo: jax.Array         # i32 [R]: each run's first posting
    cnt: jax.Array        # i32 [R]: its postings (0 for a masked query)
    ends: jax.Array       # i32 [R]: the inclusive prefix sum of ``cnt``


class Slots(NamedTuple):
    """A range of slots of a flat window (``MatchEngine.slots``)."""
    pidx: jax.Array       # i32: posting index (clipped on empty slots)
    valid: jax.Array      # bool: a posting (tombstoned too) sits there
    first: jax.Array      # bool: the first slot of its run


def flat_slots(lo, cnt, ends, start, length: int, per_run=()):
    """Slots ``start .. start + length - 1`` (``start`` may be negative) of
    the runs ``[lo[r], lo[r] + cnt[r])`` laid end to end.  Returns (pidx
    i32 unclipped, valid bool, first bool the first slot of its run, and
    each ``[R]`` array of ``per_run`` at each slot's run).

    No search per slot: the slots take the run that holds ``start``, and
    each run that starts later in the range adds its difference from the
    run before it at its first slot, which a prefix sum carries along.
    Zero-count runs add theirs at the next run's start and hold no slot.
    Integer values only (unsigned ones wrap and come back whole)."""
    starts = ends - cnt
    pos = starts - start
    held = jnp.sum(pos <= 0) - 1              # the run that holds ``start``
    at = jnp.where((pos > 0) & (pos < length), pos, length)

    def spread(v):
        prev = jnp.concatenate([jnp.zeros(1, v.dtype), v[:-1]])
        base = jnp.where(held >= 0, v[jnp.maximum(held, 0)],
                         jnp.zeros((), v.dtype))
        return base + jnp.cumsum(jnp.zeros(length, v.dtype).at[at].add(
            v - prev, mode="drop"))

    slot = start + jnp.arange(length, dtype=jnp.int32)
    pidx = slot + spread(lo - starts)
    valid = (slot >= 0) & (slot < ends[-1])
    first = jnp.zeros(length, bool).at[jnp.where(
        (pos >= 0) & (pos < length) & (cnt > 0), pos, length)].set(
        True, mode="drop")
    return pidx, valid, first, tuple(spread(v) for v in per_run)


def sorted_member(sorted_keys, queries):
    """Batched membership: sorted_keys [B, M] row-sorted, queries [B, C] ->
    bool [B, C] (the MC validation join primitive)."""
    loc = jnp.clip(jax.vmap(jnp.searchsorted)(sorted_keys, queries),
                   0, sorted_keys.shape[1] - 1)
    return jnp.take_along_axis(sorted_keys, loc, axis=1) == queries


@dataclass(frozen=True)
class EngineConfig:
    """Static (hashable) part of a MatchEngine — the jit cache key.

    ``seg_bounds`` / ``num_bounds`` are per-segment ``(start, length,
    n_real)`` triples into the concatenated device arrays: ``start`` is the
    segment's offset, ``length`` its padded extent (the slice shape the trace
    specializes on), ``n_real`` the live-posting count within it."""
    backend: str
    interpret: bool
    bucket_bits: int
    bucket_widths: tuple          # per segment; () on the sorted backend
    seg_bounds: tuple             # ((start, length, n_real), ...)
    num_bounds: tuple             # ((start, length, n_real), ...)
    n_tables: int
    max_cols: int
    row_stride: int


class MatchEngine:
    """See module docstring.  Build with ``MatchEngine.from_index`` (one
    static segment) or ``MatchEngine.from_store`` (LiveLake segments)."""

    def __init__(self, dev: dict, bucket_hashes, bucket_payload,
                 config: EngineConfig, alive=None):
        self.dev = dev                      # concatenated per-segment arrays
        self.bucket_hashes = bucket_hashes  # tuple of [2^bits, W_i] per seg
        self.bucket_payload = bucket_payload
        self.alive = alive                  # bool [n_tables] tombstone mask
        self.config = config

    # ------------------------------------------------------------- building
    @classmethod
    def from_index(cls, index, *, backend: str = "sorted",
                   interpret: bool = False, bucket_width: int | None = None):
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, "
                             f"got {backend!r}")
        dev = index.device_arrays()
        bh = bp = None
        widths = ()
        if backend == "bucket":
            # the layout must be lossless: a truncated bucket would drop
            # matches without any overflow accounting (the probe can only
            # count what the layout kept)
            need = max(index.max_bucket_count(), 1)
            if bucket_width is None:
                bucket_width = need
            elif bucket_width < need:
                raise ValueError(
                    f"bucket_width={bucket_width} is smaller than the "
                    f"fullest bucket ({need}): probing would silently drop "
                    f"matches; raise bucket_width or bucket_bits")
            width = ((bucket_width + 127) // 128) * 128   # TPU lane padding
            bh_np, bp_np, layout_overflow = index.padded_buckets(width)
            assert layout_overflow == 0
            bh, bp = (jnp.asarray(bh_np),), (jnp.asarray(bp_np),)
            widths = (width,)
        n = index.n_postings
        m = len(index.num_rowkey)
        cfg = EngineConfig(backend=backend, interpret=interpret,
                           bucket_bits=index.bucket_bits,
                           bucket_widths=widths,
                           seg_bounds=((0, n, n),),
                           num_bounds=((0, m, m),),
                           n_tables=index.n_tables, max_cols=index.max_cols,
                           row_stride=index.row_stride)
        return cls(dev, bh, bp, cfg)

    @classmethod
    def from_store(cls, store, *, backend: str = "sorted",
                   interpret: bool = False, device=None):
        """Engine over a LiveLake SegmentStore: per-segment device arrays are
        concatenated *on device* (host->device transfer is only ever the new
        segment — segment uploads are memoized on the immutable segments),
        and the per-segment bounds become static aux data.  ``device`` pins
        every array to one mesh device (sharded lakes build one engine per
        shard on its own device)."""
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, "
                             f"got {backend!r}")
        segs = store.segments
        seg_devs = [s.device_arrays(device) for s in segs]
        # each segment's rows are as wide as its widest table: widen them
        # all by repeating a cell of the same row, which changes no answer
        width = max(d["row_cells"].shape[1] for d in seg_devs)
        seg_devs = [dict(d, row_cells=jnp.concatenate(
            [d["row_cells"]] + [d["row_cells"][:, :1]] *
            (width - d["row_cells"].shape[1]), axis=1)) for d in seg_devs]
        dev = {k: jnp.concatenate([d[k] for d in seg_devs])
               for k in seg_devs[0]}
        seg_bounds, num_bounds = [], []
        off = noff = 0
        for s in segs:
            seg_bounds.append((off, s.n_padded, s.n_real))
            num_bounds.append((noff, s.n_num_padded, s.n_num))
            off += s.n_padded
            noff += s.n_num_padded
        bh = bp = None
        widths = ()
        if backend == "bucket":
            bhs, bps, ws = [], [], []
            for (start, _, _), s in zip(seg_bounds, segs):
                width = ((max(s.max_bucket_count(), 1) + 127) // 128) * 128
                bh_i, bp_i = s.device_buckets(width, payload_offset=start,
                                              device=device)
                bhs.append(bh_i)
                bps.append(bp_i)
                ws.append(width)
            bh, bp, widths = tuple(bhs), tuple(bps), tuple(ws)
        cfg = EngineConfig(backend=backend, interpret=interpret,
                           bucket_bits=store.bucket_bits,
                           bucket_widths=widths,
                           seg_bounds=tuple(seg_bounds),
                           num_bounds=tuple(num_bounds),
                           n_tables=store.n_tables, max_cols=store.max_cols,
                           row_stride=store.row_stride)
        alive = jnp.asarray(store.alive) if device is None else \
            jax.device_put(np.asarray(store.alive), device)
        return cls(dev, bh, bp, cfg, alive=alive)

    @property
    def backend(self) -> str:
        return self.config.backend

    # ------------------------------------------------------------ primitives
    def probe(self, q_hash, q_mask, m_cap: int):
        """Postings window per query hash: (pidx, valid, overflow), fanned
        out over the segments ([nq, n_segments * m_cap]) with tombstoned
        tables masked out of ``valid`` before any group-by stage.

        One uniform ``m_cap`` (sized from cross-segment total counts) is
        deliberate: per-segment caps would shrink the window when matches
        spread across segments, but each data-dependent cap combination
        would be its own jit-cache entry — fragmenting the capacity-ladder
        buckets that make mutation serving retrace-free.  Compaction, not
        cap tuning, is the mechanism that bounds the fan-out cost."""
        lo, cnt = self.runs(q_hash, q_mask)                 # [n_seg, nq]
        lane = jnp.arange(m_cap)
        nq = q_hash.shape[0]
        pidx = (lo[:, :, None] + lane).transpose(1, 0, 2).reshape(nq, -1)
        valid = (lane < cnt[:, :, None]).transpose(1, 0, 2).reshape(nq, -1)
        pidx = jnp.clip(pidx, 0, self.dev["hash"].shape[0] - 1)
        ovf = jnp.sum(jnp.maximum(cnt - m_cap, 0))
        if self.alive is not None:
            valid &= self.alive[self.dev["table"][pidx]]
        return pidx, valid, ovf

    def runs(self, q_hash, q_mask):
        """Each query's run of postings in each segment: (lo, cnt), i32
        ``[n_segments, nq]``; ``lo`` indexes the concatenated arrays and
        ``cnt`` is 0 for a masked query."""
        los, cnts = [], []
        for i, (start, length, n_real) in enumerate(self.config.seg_bounds):
            if self.config.backend == "sorted":
                keys = self.dev["hash"][start:start + length]
                lo = jnp.minimum(jnp.searchsorted(keys, q_hash, side="left"),
                                 n_real)
                hi = jnp.minimum(jnp.searchsorted(keys, q_hash,
                                                  side="right"), n_real)
                lo, cnt = lo + start, hi - lo
            else:
                hits = bucket_ops.probe(
                    self.bucket_hashes[i], self.bucket_payload[i], q_hash,
                    self.config.bucket_bits, use_kernel=True,
                    interpret=self.config.interpret,
                    q_block=min(256, q_hash.shape[0]))
                hit = hits >= 0
                # payloads are global and a hash's postings contiguous:
                # the run starts at the least payload
                cnt = jnp.sum(hit, axis=1)
                lo = jnp.min(jnp.where(hit, hits, self.dev["hash"].shape[0]),
                             axis=1)
            los.append(lo.astype(jnp.int32))
            cnts.append(jnp.where(q_mask, cnt, 0).astype(jnp.int32))
        return jnp.stack(los), jnp.stack(cnts)

    def window(self, q_hash, q_mask) -> Window:
        """The flat probe window: every posting of every (segment, query)
        run, segment after segment.  Callers walk its slots in chunks
        (``slots``), as many as the postings need, so a hot value holds as
        many slots as it has postings and nothing is cut.  Within a run the
        postings keep the index order (table, col, row), so a group-by key
        repeats only in adjacent slots of one run."""
        lo, cnt = self.runs(q_hash, q_mask)
        cnt = cnt.reshape(-1)
        return Window(lo.reshape(-1), cnt, jnp.cumsum(cnt))

    def slots(self, win: Window, start, length: int, per_run=()):
        """Slots ``start .. start + length - 1`` of ``win`` (``Slots``) and
        each ``per_run`` array (``[R]``, see ``per_run``) at each slot's
        run.  Tombstoned tables stay in ``valid``: whole tables die, so
        their postings score only their own tables, which ``live`` zeroes
        once per launch instead of once per slot."""
        pidx, valid, first, vals = flat_slots(win.lo, win.cnt, win.ends,
                                              start, length, per_run)
        pidx = jnp.clip(pidx, 0, self.dev["hash"].shape[0] - 1)
        return Slots(pidx, valid, first), vals

    def live(self, scores):
        """``scores`` (tables on the last axis) with tombstoned tables
        zeroed."""
        if self.alive is None:
            return scores
        return jnp.where(self.alive, scores, 0.0)

    def per_run(self, per_query):
        """A per-query array as a per-run one of ``window``'s runs."""
        return jnp.tile(per_query, len(self.config.seg_bounds))

    def overflow(self, win: Window, n_slots):
        """Each query's postings past the window's first ``n_slots`` slots
        (i32 [nq]): nonzero only where a count the host sized the walk from
        was stale."""
        ovf = jnp.clip(win.ends - n_slots, 0, win.cnt)
        return ovf.reshape(len(self.config.seg_bounds), -1).sum(axis=0)

    def row_holds(self, pidx, q_hash):
        """Whether the row of the posting at ``pidx[i]`` holds a cell whose
        hash is ``q_hash[i]``: one gather of the row's cell hashes
        (``row_cells``, every column of the row) and a compare per column,
        whatever the count of the value.  Tombstoned rows answer too: a
        posting's row lies in its own table, so a dead row validates only
        for that (dead) table."""
        cells = self.dev["row_cells"][pidx]
        return jnp.any(cells == q_hash[:, None], axis=1)

    def rowjoin(self, rowkeys, mask, row_cap: int):
        """Numeric-postings window per candidate rowkey: (nidx, nvalid),
        fanned out over the per-segment (table, row)-sorted runs."""
        parts = []
        for start, length, n_real in self.config.num_bounds:
            keys = self.dev["num_rowkey"][start:start + length]
            nidx, nvalid, _ = probe_sorted_bounded(keys, n_real, rowkeys,
                                                   mask, row_cap)
            parts.append((nidx + start, nvalid))
        if len(parts) == 1:
            nidx, nvalid = parts[0]
        else:
            nidx = jnp.concatenate([p for p, _ in parts], axis=1)
            nvalid = jnp.concatenate([v for _, v in parts], axis=1)
        if self.alive is not None:
            nvalid &= self.alive[self.dev["num_table"][nidx]]
        return nidx, nvalid

    def bloom(self, pidx, qk_lo, qk_hi):
        """XASH superkey containment of query digests in the candidate rows
        at ``pidx`` [nt, cap]: (row_sk & q_sk) == q_sk, via the
        superkey_filter kernel package."""
        cand_lo = self.dev["sk_lo"][pidx]
        cand_hi = self.dev["sk_hi"][pidx]
        return sk_ops.filter_candidates(
            cand_lo, cand_hi, qk_lo, qk_hi,
            use_kernel=self.config.backend == "bucket",
            interpret=self.config.interpret)

    def qcr(self, n_agree, n_all, min_support: int = 3):
        """QCR epilogue |2a - n| / n with the support floor, via the
        qcr_score kernel package."""
        return qcr_ops.score_segments(
            n_agree, n_all, min_support=min_support,
            use_kernel=self.config.backend == "bucket",
            interpret=self.config.interpret)

    def member(self, sorted_keys, queries):
        return sorted_member(sorted_keys, queries)


def _engine_flatten(e: MatchEngine):
    return ((e.dev, e.bucket_hashes, e.bucket_payload, e.alive), e.config)


def _engine_unflatten(aux, children):
    dev, bh, bp, alive = children
    return MatchEngine(dev, bh, bp, aux, alive=alive)


jax.tree_util.register_pytree_node(MatchEngine, _engine_flatten,
                                   _engine_unflatten)
