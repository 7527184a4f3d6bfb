"""The four BLEND seekers as static-shaped, jittable scan programs.

Every seeker maps (MatchEngine, hashed query) -> dense per-table scores
[n_tables] (the TPU-native result-set representation; combiners are
elementwise set algebra over these vectors).  ``allowed`` is the optimizer's
threaded intermediate-result mask — the TPU analogue of the paper's
``WHERE TableId IN (...)`` query rewriting: postings from dead tables are
zeroed *before* the expensive group-by / validation stages.

All probing goes through ``MatchEngine.probe`` (core/match.py): the engine
owns the device index and selects the searchsorted or Pallas bucket-probe
backend; seekers never touch the raw hash array.  The unfused MC programs'
bloom stage and the correlation scoring epilogue likewise route through the
superkey_filter and qcr_score kernel packages via the engine.  The fused MC
validates each candidate row exactly from its cells' hashes and runs no
superkey filter.

Static capacities (``m_cap`` matches per value, ``row_cap`` numeric cells per
row) keep shapes jit-stable; overflows are counted and surfaced, never
silently dropped.  ``TRACE_COUNTS`` increments once per jit trace of each
seeker — the executor's retrace-free contract is asserted against it.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

from repro import obs

TRACE_COUNTS = collections.Counter()


def _mark_trace(kind: str):
    """Python-side effect: runs once per jit trace, never per call.  Also
    bridged into the metrics registry (``exec.retraces``), so a serving
    tier with observability enabled sees compile churn without reaching
    into this module's counter."""
    TRACE_COUNTS[kind] += 1
    reg = obs.registry()
    reg.counter("exec.retraces").inc()
    reg.counter(f"exec.retraces.{kind}").inc()


def _first_occurrence(*keys, valid=None):
    """Mask of first occurrence of a key combo along axis 1.

    Inputs are sorted within each valid run.  With a segment-fanned probe
    window ([nq, n_segments * m_cap]) a valid run can directly follow another
    segment's garbage tail whose clipped gather happens to repeat the same
    key — passing ``valid`` masks keys to a sentinel first so run boundaries
    always register as a change (a table's postings live in exactly one
    segment, so a key never spans two valid runs)."""
    first = None
    for k in keys:
        if valid is not None:
            k = jnp.where(valid, k, -1)
        prev = jnp.concatenate([jnp.full_like(k[:, :1], -1), k[:, :-1]], axis=1)
        f = k != prev
        first = f if first is None else (first | f)
    return first


# --------------------------------------------------------------------------
# SC seeker — single-column join discovery (Listing 1)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m_cap", "n_tables", "max_cols"))
def sc_seeker(engine, q_hash, q_mask, *, m_cap, n_tables, max_cols,
              allowed=None):
    """COUNT(DISTINCT CellValue) GROUP BY (TableId, ColumnId); table score =
    best column.  Returns (scores f32 [n_tables], overflow)."""
    _mark_trace("SC")
    idx = engine.dev
    pidx, valid, ovf = engine.probe(q_hash, q_mask, m_cap)
    t = idx["table"][pidx]
    c = idx["col"][pidx]
    contrib = valid & _first_occurrence(t, c, valid=valid)
    if allowed is not None:
        contrib &= allowed[t]
    flat = (t * max_cols + c).reshape(-1)
    scores_tc = jnp.zeros(n_tables * max_cols, jnp.float32).at[flat].add(
        contrib.reshape(-1).astype(jnp.float32), mode="drop")
    return scores_tc.reshape(n_tables, max_cols).max(axis=1), ovf


# --------------------------------------------------------------------------
# KW seeker — keyword search (SC without the ColumnId group key)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m_cap", "n_tables"))
def kw_seeker(engine, q_hash, q_mask, *, m_cap, n_tables, allowed=None):
    _mark_trace("KW")
    idx = engine.dev
    pidx, valid, ovf = engine.probe(q_hash, q_mask, m_cap)
    t = idx["table"][pidx]
    contrib = valid & _first_occurrence(t, valid=valid)
    if allowed is not None:
        contrib &= allowed[t]
    scores = jnp.zeros(n_tables, jnp.float32).at[t.reshape(-1)].add(
        contrib.reshape(-1).astype(jnp.float32), mode="drop")
    return scores, ovf


# --------------------------------------------------------------------------
# MC seeker — multi-column join discovery (MATE-style, Listing 2)
# --------------------------------------------------------------------------

def _tuple_mask_or_ones(tuple_mask, nt):
    return jnp.ones((nt,), bool) if tuple_mask is None else tuple_mask


@functools.partial(jax.jit, static_argnames=("m_cap", "n_tables", "n_cols",
                                             "use_superkey", "row_stride"))
def mc_seeker(engine, tuple_hashes, init_col, qk_lo, qk_hi, *, m_cap,
              n_tables, n_cols, row_stride=1 << 22, use_superkey=True,
              allowed=None, tuple_mask=None):
    """tuple_hashes: [nt, n_cols] hashed query tuples; init_col: [nt] index of
    the least-frequent (initiator) value; qk_lo/hi: [nt] query superkeys;
    tuple_mask: [nt] optional validity of (padded) tuples.

    Phase 1: probe the initiator value -> candidate rows.
    Phase 2: XASH superkey bloom filter  ((row_sk & q_sk) == q_sk).
    Phase 3: exact validation — every other column value must occur in the
             same (table, row).
    Returns (scores = matched-tuple count per table, row_counts = candidate
    rows that survive per table (Table V TP metric), overflow)."""
    _mark_trace("MC")
    idx = engine.dev
    nt = tuple_hashes.shape[0]
    h0 = jnp.take_along_axis(tuple_hashes, init_col[:, None], 1)[:, 0]
    q_mask = _tuple_mask_or_ones(tuple_mask, nt)
    pidx, valid, ovf = engine.probe(h0, q_mask, m_cap)
    t = idx["table"][pidx]
    r = idx["row"][pidx]
    if allowed is not None:
        valid &= allowed[t]
    if use_superkey:
        valid &= engine.bloom(pidx, qk_lo, qk_hi)
    rowkey = t.astype(jnp.int32) * row_stride + r.astype(jnp.int32)

    ok = valid
    for j in range(n_cols):                       # static, small
        # a truncated validation probe is flagged like the initiator's
        pj, vj, oj = engine.probe(tuple_hashes[:, j],
                                  q_mask & (init_col != j), m_cap)
        ovf += oj
        tj = idx["table"][pj]
        rj = idx["row"][pj]
        rkj = tj.astype(jnp.int32) * row_stride + rj.astype(jnp.int32)
        rkj = jnp.where(vj, rkj, -1)
        member = jnp.any(rowkey[:, :, None] == rkj[:, None, :], axis=-1)
        ok &= member | (init_col == j)[:, None]
    # matched-tuple count per table (dedupe: one tuple counts once per table)
    per_tt = jnp.zeros((nt * n_tables,), jnp.float32).at[
        (jnp.arange(nt)[:, None] * n_tables + t).reshape(-1)].max(
        ok.reshape(-1).astype(jnp.float32), mode="drop")
    scores = per_tt.reshape(nt, n_tables).sum(axis=0)
    row_counts = jnp.zeros(n_tables, jnp.float32).at[t.reshape(-1)].add(
        ok.reshape(-1).astype(jnp.float32), mode="drop")
    return scores, row_counts, ovf


# --------------------------------------------------------------------------
# Segmented (fused-batch) seeker variants — core/fused.py dispatches all
# same-kind seekers of a plan (or of a whole serve_many batch) as ONE device
# program: every posting of every query value lies in one flat window
# (``MatchEngine.window``), and one scatter produces a stacked
# [n_seekers, n_tables] score matrix.  SC and KW walk the batch's distinct
# values once each and add to every seeker that holds the value; MC and C
# concatenate their seekers' query arrays with per-row seeker ids
# (``seg_id``) and prefix the group-by keys with them.  The program walks
# the window in ``n_chunks`` chunks of ``chunk`` slots: ``n_chunks`` is an
# operand the caller sizes from the summed match counts, so no value is cut
# however hot and the program does not change with the count.  Every score is a sum of 0/1 contributions (or a QCR
# ratio of such sums) over exactly the postings a dedicated exact launch
# would see, so each row of the stack is bit-identical to the unfused
# seeker's output wherever that one does not overflow.  ``n_seekers`` and
# ``chunk`` are quantized to powers of two by the caller so batches stay
# retrace-free.
# --------------------------------------------------------------------------

def _starts_group(first, *keys):
    """Whether each slot starts a key group within its run: a run keeps the
    index order (table, col, row), so repeats of a key are adjacent.  Read
    at index ``k`` against ``k - 1``, so a chunk reads one slot of the
    chunk before it (index 0), and a group that spans two chunks counts
    once."""
    for k in keys:
        first = first | (k != jnp.roll(k, 1))
    return first


def _seeker_overflow(engine, win, n_slots, seg_id, n_seekers):
    ovf = engine.overflow(win, n_slots)
    return jnp.zeros(n_seekers, ovf.dtype).at[seg_id].add(ovf, mode="drop")


def _values_seg(engine, q_hash, q_mask, member, n_chunks, chunk, n_keys,
                key_of):
    """The group-by of the batched SC and KW seekers: the flat window over
    the batch's distinct values, each walked once however many seekers hold
    it.  The first slot of each group-by key (``key_of(pidx)``, < ``n_keys``)
    in a value's run adds 1 to that key for every seeker that holds the
    value (``member`` [nq, n_seekers] bool).  Returns (sums [n_keys,
    n_seekers], overflow [n_seekers])."""
    win = engine.window(q_hash, q_mask)
    # each value's seekers as bits of 32-bit words, carried to its slots
    # like any per-run value (no gather per slot)
    nq, n_seekers = member.shape
    n_words = -(-n_seekers // 32)
    bit = jnp.arange(32, dtype=jnp.uint32)
    words = jnp.sum(jnp.pad(member, ((0, 0), (0, 32 * n_words - n_seekers)))
                    .reshape(nq, n_words, 32).astype(jnp.uint32) << bit,
                    axis=2, dtype=jnp.uint32)
    per_run = [engine.per_run(words[:, w]) for w in range(n_words)]

    def chunk_sums(i, acc):
        sl, held = engine.slots(win, i * chunk - 1, chunk + 1, per_run)
        key = key_of(sl.pidx)
        contrib = (sl.valid & _starts_group(sl.first, key))[1:]
        add = jnp.concatenate([(w[1:, None] >> bit) & 1 for w in held],
                              axis=1)[:, :n_seekers]
        add = jnp.where(contrib[:, None], add, 0).astype(jnp.float32)
        return acc.at[key[1:]].add(add, mode="drop")

    sums = jax.lax.fori_loop(0, n_chunks, chunk_sums, jnp.zeros(
        (n_keys, member.shape[1]), jnp.float32))
    ovf = engine.overflow(win, n_chunks * chunk)
    return sums, jnp.sum(jnp.where(member, ovf[:, None], 0), axis=0)


@functools.partial(jax.jit, static_argnames=("chunk", "n_tables",
                                             "max_cols"))
def sc_seeker_seg(engine, q_hash, q_mask, member, n_chunks, *, chunk,
                  n_tables, max_cols):
    """Batched ``sc_seeker``: the batch's distinct values (``q_hash``) and
    which of its seekers hold each (``member`` [nq, n_seekers]), grouped-by
    into [n_seekers, n_tables].  Returns (scores, overflow [n_seekers])."""
    _mark_trace("SC_seg")
    idx = engine.dev
    sums, ovf = _values_seg(
        engine, q_hash, q_mask, member, n_chunks, chunk,
        n_tables * max_cols, lambda p: idx["table"][p] * max_cols +
        idx["col"][p])
    scores = sums.reshape(n_tables, max_cols, -1).max(axis=1).T
    return engine.live(scores), ovf


@functools.partial(jax.jit, static_argnames=("chunk", "n_tables"))
def kw_seeker_seg(engine, q_hash, q_mask, member, n_chunks, *, chunk,
                  n_tables):
    """Batched ``kw_seeker`` (SC without the ColumnId group key)."""
    _mark_trace("KW_seg")
    idx = engine.dev
    sums, ovf = _values_seg(engine, q_hash, q_mask, member, n_chunks, chunk,
                            n_tables, lambda p: idx["table"][p])
    return engine.live(sums.T), ovf


@functools.partial(jax.jit, static_argnames=("chunk", "n_seekers",
                                             "n_tables", "n_cols"))
def mc_seeker_seg(engine, tuple_hashes, init_col, seg_id, n_chunks, *,
                  chunk, n_seekers, n_tables, n_cols, tuple_mask=None):
    """Batched ``mc_seeker`` over the concatenated tuple blocks of all
    same-width (``n_cols``) MC seekers; ``seg_id`` is per tuple.

    The window holds every posting of each tuple's initiator value.  A
    candidate row survives if it holds each other value of the tuple: one
    gather of the row's cell hashes (``MatchEngine.row_holds``), exact at
    any count of the other value.  No XASH superkey test runs before it:
    every slot is computed on the device anyway, and the exact test costs
    less than the filter would.  A tuple counts once per table: the
    initiator run holds a table's postings in adjacent slots, so the run's
    (tuple, table) groups are contiguous, and a group counts at its last
    slot if any of its slots survived (a running maximum carries that
    across chunks)."""
    _mark_trace("MC_seg")
    idx = engine.dev
    nt = tuple_hashes.shape[0]
    h0 = jnp.take_along_axis(tuple_hashes, init_col[:, None], 1)[:, 0]
    q_mask = _tuple_mask_or_ones(tuple_mask, nt)
    win = engine.window(h0, q_mask)
    # each run's tuple: its seeker and its values other than the initiator
    per_run = [engine.per_run(seg_id)] + [
        engine.per_run(jnp.take_along_axis(
            tuple_hashes, (k + (init_col <= k))[:, None], 1)[:, 0])
        for k in range(n_cols - 1)]

    def chunk_scores(i, carry):
        acc, g_start, g_ok = carry
        sl, vals = engine.slots(win, i * chunk - 1, chunk + 2, per_run)
        t = idx["table"][sl.pidx]
        group = _starts_group(sl.first, t)
        ends_group = jnp.roll(group | ~sl.valid, -1)
        core = slice(1, chunk + 1)
        pidx, ok, t = sl.pidx[core], sl.valid[core], t[core]
        group, ends_group = group[core], ends_group[core]
        sid, *others = (v[core] for v in vals)
        for h in others:
            ok &= engine.row_holds(pidx, h)
        s = i * chunk + jnp.arange(chunk, dtype=jnp.int32)
        start = jnp.maximum(g_start, jax.lax.cummax(
            jnp.where(group, s, -1), axis=0))
        last_ok = jnp.maximum(g_ok, jax.lax.cummax(
            jnp.where(ok, s, -1), axis=0))
        counts = sl.valid[core] & ends_group & (last_ok >= start)
        acc = acc.at[sid * n_tables + t].add(counts.astype(jnp.float32),
                                             mode="drop")
        return acc, start[-1], last_ok[-1]

    none = jnp.int32(-1)
    scores, _, _ = jax.lax.fori_loop(0, n_chunks, chunk_scores, (jnp.zeros(
        n_seekers * n_tables, jnp.float32), none, none))
    return engine.live(scores.reshape(n_seekers, n_tables)), \
        _seeker_overflow(engine, win, n_chunks * chunk, seg_id, n_seekers)


@functools.partial(jax.jit, static_argnames=("chunk", "row_cap",
                                             "n_seekers", "n_tables",
                                             "max_cols", "h_sample",
                                             "sampling", "min_support",
                                             "row_stride"))
def c_seeker_seg(engine, qj_hash, q_mask, q_bit, seg_id, n_chunks, *, chunk,
                 row_cap, n_seekers, n_tables, max_cols, h_sample,
                 row_stride=1 << 22, sampling="conv", min_support=3):
    """Batched ``c_seeker``: the QCR group-by key is prefixed with the
    seeker id of the originating join posting, so the per-(table, join_col,
    num_col) segment sums — and hence every QCR ratio — are computed from
    exactly the contributions the dedicated launch would have seen."""
    _mark_trace("C_seg")
    idx = engine.dev
    win = engine.window(qj_hash, q_mask)
    per_run = (engine.per_run(seg_id),
               engine.per_run(q_bit.astype(jnp.int32)))
    dim = n_tables * max_cols * max_cols
    rank_of = idx["num_rank_conv" if sampling == "conv" else "num_rank_rand"]

    def chunk_sums(i, sums):
        n_all, n_agree = sums
        sl, (sid, qb) = engine.slots(win, i * chunk, chunk, per_run)
        t = idx["table"][sl.pidx]
        cj = idx["col"][sl.pidx]
        rowkey = t.astype(jnp.int32) * row_stride + \
            idx["row"][sl.pidx].astype(jnp.int32)
        nidx, nvalid = engine.rowjoin(rowkey, sl.valid, row_cap)
        ntab = idx["num_table"][nidx]
        ncol = idx["num_col"][nidx]
        nvalid &= rank_of[nidx] < h_sample
        agree = (idx["num_quadrant"][nidx] == qb[:, None]) & nvalid
        key = (sid[:, None] * dim +
               (ntab * max_cols + cj[:, None]) * max_cols + ncol).reshape(-1)
        return (n_all.at[key].add(nvalid.reshape(-1).astype(jnp.float32),
                                  mode="drop"),
                n_agree.at[key].add(agree.reshape(-1).astype(jnp.float32),
                                    mode="drop"))

    zeros = jnp.zeros(n_seekers * dim, jnp.float32)
    n_all, n_agree = jax.lax.fori_loop(0, n_chunks, chunk_sums,
                                       (zeros, zeros))
    qcr = engine.qcr(n_agree, n_all, min_support)
    scores = qcr.reshape(n_seekers, n_tables, max_cols * max_cols).max(axis=2)
    return engine.live(scores), \
        _seeker_overflow(engine, win, n_chunks * chunk, seg_id, n_seekers)


# --------------------------------------------------------------------------
# MC capacity compaction — the TPU analogue of the paper's query rewriting.
# The threaded predicate can't shrink a static-shape scan by itself; instead
# the executor measures the survivor count (stage 1) and re-launches the
# expensive validation with compacted candidate buffers (stage 2).  This is
# where "WHERE TableId IN (IR)" actually reduces work on a vector machine.
# --------------------------------------------------------------------------

def _mc_candidates(engine, tuple_hashes, init_col, qk_lo, qk_hi, m_cap,
                   use_superkey, allowed, tuple_mask):
    idx = engine.dev
    nt = tuple_hashes.shape[0]
    h0 = jnp.take_along_axis(tuple_hashes, init_col[:, None], 1)[:, 0]
    q_mask = _tuple_mask_or_ones(tuple_mask, nt)
    pidx, valid, ovf = engine.probe(h0, q_mask, m_cap)
    t = idx["table"][pidx]
    r = idx["row"][pidx]
    if allowed is not None:
        valid &= allowed[t]
    if use_superkey:
        valid &= engine.bloom(pidx, qk_lo, qk_hi)
    return t, r, valid, ovf, q_mask


@functools.partial(jax.jit, static_argnames=("m_cap", "use_superkey"))
def mc_survivor_counts(engine, tuple_hashes, init_col, qk_lo, qk_hi, *, m_cap,
                       use_superkey=True, allowed=None, tuple_mask=None):
    """Stage 1: candidates per tuple surviving the threaded predicate +
    bloom prune (the planner picks the stage-2 capacity from the max)."""
    _mark_trace("MC_stage1")
    _, _, valid, _, _ = _mc_candidates(engine, tuple_hashes, init_col, qk_lo,
                                       qk_hi, m_cap, use_superkey, allowed,
                                       tuple_mask)
    return jnp.sum(valid, axis=1)


@functools.partial(jax.jit, static_argnames=("m_cap", "m_cap2", "n_tables",
                                             "n_cols", "use_superkey",
                                             "row_stride"))
def mc_seeker_compact(engine, tuple_hashes, init_col, qk_lo, qk_hi, *, m_cap,
                      m_cap2, n_tables, n_cols, row_stride=1 << 22,
                      use_superkey=True, allowed=None, tuple_mask=None):
    """Stage 2: exact validation over compacted [nt, m_cap2] candidates
    (m_cap2 << m_cap when the predicate filters hard)."""
    _mark_trace("MC_stage2")
    idx = engine.dev
    nt = tuple_hashes.shape[0]
    t, r, valid, ovf, q_mask = _mc_candidates(engine, tuple_hashes, init_col,
                                              qk_lo, qk_hi, m_cap,
                                              use_superkey, allowed,
                                              tuple_mask)
    # compact: move surviving candidates to the front, take m_cap2
    order = jnp.argsort(~valid, axis=1, stable=True)[:, :m_cap2]
    t = jnp.take_along_axis(t, order, axis=1)
    r = jnp.take_along_axis(r, order, axis=1)
    valid = jnp.take_along_axis(valid, order, axis=1)
    rowkey = t.astype(jnp.int32) * row_stride + r.astype(jnp.int32)

    ok = valid
    for j in range(n_cols):
        # a truncated validation probe is flagged like the initiator's
        pj, vj, oj = engine.probe(tuple_hashes[:, j],
                                  q_mask & (init_col != j), m_cap)
        ovf += oj
        tj = idx["table"][pj]
        rj = idx["row"][pj]
        rkj = tj.astype(jnp.int32) * row_stride + rj.astype(jnp.int32)
        rkj = jnp.sort(jnp.where(vj, rkj, jnp.iinfo(jnp.int32).max), axis=1)
        member = engine.member(rkj, rowkey)
        ok &= member | (init_col == j)[:, None]
    per_tt = jnp.zeros((nt * n_tables,), jnp.float32).at[
        (jnp.arange(nt)[:, None] * n_tables + t).reshape(-1)].max(
        ok.reshape(-1).astype(jnp.float32), mode="drop")
    scores = per_tt.reshape(nt, n_tables).sum(axis=0)
    row_counts = jnp.zeros(n_tables, jnp.float32).at[t.reshape(-1)].add(
        ok.reshape(-1).astype(jnp.float32), mode="drop")
    return scores, row_counts, ovf


# --------------------------------------------------------------------------
# Correlation seeker — QCR in one pass (Listing 3)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m_cap", "row_cap", "n_tables",
                                             "max_cols", "h_sample", "sampling",
                                             "min_support", "row_stride"))
def c_seeker(engine, qj_hash, q_mask, q_bit, *, m_cap, row_cap, n_tables,
             max_cols, h_sample, row_stride=1 << 22, sampling="conv",
             min_support=3, allowed=None):
    """qj_hash: hashed join-key values; q_bit[i] = 1 iff the query target for
    key i is >= the target mean (the paper's k0/k1 split, done at parse time).

    QCR = (2*(n_I + n_III) - N) / N  computed per (table, join_col, num_col)
    triple via two segment-sums; table score = max |QCR| over triples with
    N >= min_support.  h-sampling filters the numeric side by the indexed
    convenience/random rank (sketch size chosen at query time)."""
    _mark_trace("C")
    idx = engine.dev
    pidx, valid, ovf = engine.probe(qj_hash, q_mask, m_cap)
    t = idx["table"][pidx]
    r = idx["row"][pidx]
    cj = idx["col"][pidx]
    rowkey = t.astype(jnp.int32) * row_stride + r.astype(jnp.int32)
    rk_flat = rowkey.reshape(-1)

    nidx, nvalid = engine.rowjoin(rk_flat, valid.reshape(-1), row_cap)

    ntab = idx["num_table"][nidx]
    ncol = idx["num_col"][nidx]
    nquad = idx["num_quadrant"][nidx]
    rank = idx["num_rank_conv" if sampling == "conv" else "num_rank_rand"][nidx]
    nvalid &= rank < h_sample
    if allowed is not None:
        nvalid &= allowed[ntab]

    qb = jnp.broadcast_to(q_bit[:, None], pidx.shape).reshape(-1)[:, None]
    agree = (nquad == qb) & nvalid

    key = ((ntab * max_cols + cj.reshape(-1)[:, None]) * max_cols + ncol)
    key = key.reshape(-1)
    dim = n_tables * max_cols * max_cols
    n_all = jnp.zeros(dim, jnp.float32).at[key].add(
        nvalid.reshape(-1).astype(jnp.float32), mode="drop")
    n_agree = jnp.zeros(dim, jnp.float32).at[key].add(
        agree.reshape(-1).astype(jnp.float32), mode="drop")
    qcr = engine.qcr(n_agree, n_all, min_support)
    return qcr.reshape(n_tables, -1).max(axis=1), ovf


@functools.partial(jax.jit, static_argnames=("m_cap",))
def c_survivor_counts(engine, qj_hash, q_mask, *, m_cap, allowed=None):
    """Stage 1 for the compacted correlation seeker: join-side matches that
    survive the threaded predicate."""
    _mark_trace("C_stage1")
    pidx, valid, _ = engine.probe(qj_hash, q_mask, m_cap)
    if allowed is not None:
        valid &= allowed[engine.dev["table"][pidx]]
    return jnp.sum(valid)


@functools.partial(jax.jit, static_argnames=("m_cap", "cap2", "row_cap",
                                             "n_tables", "max_cols",
                                             "h_sample", "sampling",
                                             "min_support", "row_stride"))
def c_seeker_compact(engine, qj_hash, q_mask, q_bit, *, m_cap, cap2, row_cap,
                     n_tables, max_cols, h_sample, row_stride=1 << 22,
                     sampling="conv", min_support=3, allowed=None):
    """Stage 2: the numeric row-join + QCR scoring runs over the compacted
    [cap2] surviving join-side postings instead of [nq*m_cap]."""
    _mark_trace("C_stage2")
    idx = engine.dev
    pidx, valid, ovf = engine.probe(qj_hash, q_mask, m_cap)
    t = idx["table"][pidx]
    if allowed is not None:
        valid &= allowed[t]
    rowkey = (t.astype(jnp.int32) * row_stride +
              idx["row"][pidx].astype(jnp.int32))
    cj = idx["col"][pidx]
    qb = jnp.broadcast_to(q_bit[:, None], pidx.shape)
    flat_valid = valid.reshape(-1)
    # fill_value must be out-of-band: filling with slot 0 would mark the pad
    # entries valid whenever slot 0 itself survives, double-counting its
    # postings cap2-surv times in the QCR segment sums
    (keep,) = jnp.nonzero(flat_valid, size=cap2, fill_value=-1)
    kv = keep >= 0
    keep = jnp.where(kv, keep, 0)
    rk = jnp.where(kv, rowkey.reshape(-1)[keep], -1)
    cjf = cj.reshape(-1)[keep]
    qbf = qb.reshape(-1)[keep]

    nidx, nvalid = engine.rowjoin(rk, kv & (rk >= 0), row_cap)
    ntab = idx["num_table"][nidx]
    ncol = idx["num_col"][nidx]
    nquad = idx["num_quadrant"][nidx]
    rank = idx["num_rank_conv" if sampling == "conv" else "num_rank_rand"][nidx]
    nvalid &= rank < h_sample
    agree = (nquad == qbf[:, None]) & nvalid
    key = ((ntab * max_cols + cjf[:, None]) * max_cols + ncol).reshape(-1)
    dim = n_tables * max_cols * max_cols
    n_all = jnp.zeros(dim, jnp.float32).at[key].add(
        nvalid.reshape(-1).astype(jnp.float32), mode="drop")
    n_agree = jnp.zeros(dim, jnp.float32).at[key].add(
        agree.reshape(-1).astype(jnp.float32), mode="drop")
    qcr = engine.qcr(n_agree, n_all, min_support)
    return qcr.reshape(n_tables, -1).max(axis=1), ovf
