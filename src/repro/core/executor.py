"""Plan executor: optimized (EG ordering + mask threading) and naive (B-NO).

The executor owns a ``MatchEngine`` (device index + probe backends), hashes
query values through a cross-query memo cache, and runs the plan DAG.
``optimize=False`` reproduces the paper's B-NO configuration: same seekers
and combiners, random/insertion seeker order, no intermediate-result
threading.

Serving is retrace-free: match capacities are quantized to a small fixed
ladder and query counts are padded to powers of two, so re-running any plan
shape with new values of the same capacity bucket hits the jit cache (zero
new traces — asserted against ``seekers.TRACE_COUNTS``).  ``sync=False``
dispatches seekers without host synchronization (no ``block_until_ready``,
no data-dependent compaction stages) for batched serving
(serve/engine.py ``serve_many``).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import combiners as comb
from repro.core import seekers as seek
from repro.core.cost_model import CostModel
from repro.core.hashing import MISSING, hash_value, row_superkey, split_u64
from repro.core.index import UnifiedIndex
from repro.core.match import MatchEngine
from repro.core.optimizer import optimize as optimize_plan
from repro.core.plan import Plan, SeekerSpec
from repro.core import sketch as sk

# the unfused seekers' match-capacity ladder: every such launch uses one of
# these static capacities, so the jit cache holds at most len(CAP_LADDER)
# variants per (seeker, query-pad) shape instead of one per observed match
# count — and a coarse ladder keeps the bucket stable across draws from the
# same workload.  ``m_cap_max`` tops it.  The fused path (core/fused.py)
# sizes one flat window from the summed counts instead and uses neither.
CAP_LADDER = (32, 128, 512, 1024)
PAD_SENTINEL = MISSING                    # reserved: never a real cell hash


@dataclass
class OverflowSlice:
    """A lazy view into a fused group's stacked overflow vector: ``rows``
    are this plan's seekers' rows in ``vec``.  Materializing the slice at
    dispatch time would cost one tiny device gather per seeker; deferring
    it to the ``ExecInfo.overflow`` read keeps the fused dispatch path free
    of per-node device ops.  On a sharded lake ``vec`` is a *tuple* of
    per-shard vectors (overflow sums across shards, like scores)."""
    vec: object                   # [n_seekers_p] device vector, or a tuple
    rows: list                    # this plan's row indices into vec


@dataclass
class ExecInfo:
    optimized: bool
    node_seconds: dict = field(default_factory=dict)
    order: list = field(default_factory=list)
    overflow_parts: list = field(default_factory=list)
    # query-cache accounting (serve/cache.py): seeker nodes served from the
    # subplan cache (``cached_nodes``) vs actually dispatched
    # (``seeker_runs``).  Telemetry only — ``serve_many`` excludes exact
    # result-cache hits (CacheInfo.status == 'hit') from its drain
    # denominator; a partial request dispatches combiner work even at zero
    # seeker runs, so it keeps its share.
    cached_nodes: list = field(default_factory=list)
    seeker_runs: int = 0
    #: memoized ``overflow`` total (None until first read / batch fetch)
    _overflow: int | None = None
    # device-program dispatch count: every jitted seeker call (compaction
    # stages included) and every combiner node counts one on the unfused
    # path; the fused path counts its group launches + the single DAG
    # program — ``n_groups + 1``, which is ``n_kinds + 1`` unless same-kind
    # seekers differ in static shape args (MC n_cols, C h/sampling)
    launches: int = 0
    # sharded graceful degradation: indices of shards whose fused probe
    # failed twice (initial + one retry on a rebuilt engine) and were
    # zero-substituted out of the merge — the response is flagged degraded
    # (serve/engine.py DiscoveryResponse) instead of erroring the batch
    failed_shards: list = field(default_factory=list)

    @property
    def total_seconds(self) -> float:
        return sum(self.node_seconds.values())

    @property
    def overflow(self) -> int:
        # reading this synchronizes on the dispatched seekers; all parts are
        # fetched in ONE device transfer (a part may be a per-seeker scalar
        # or a fused group's stacked OverflowSlice)
        if self._overflow is None:
            ExecInfo.materialize_overflow([self])
        return self._overflow

    @staticmethod
    def materialize_overflow(infos):
        """Resolve many infos' overflow totals in ONE device transfer,
        deduping shared vectors (a fused group's stacked overflow vector is
        shared by every plan in a serve_many batch).  Per-response fetches
        are a measurable share of the warm batched serving path."""
        todo = [i for i in infos if i._overflow is None]
        vecs: dict = {}
        for i in todo:
            for p in i.overflow_parts:
                v = p.vec if isinstance(p, OverflowSlice) else p
                vecs.setdefault(id(v), v)
        raw = jax.device_get(list(vecs.values())) if vecs else []
        host = {k: np.asarray(a) for k, a in zip(vecs, raw)}
        for i in todo:
            total = 0
            for p in i.overflow_parts:
                if isinstance(p, OverflowSlice):
                    # sharded slice: vec is [n_shards, n_seekers_p]
                    total += int(host[id(p.vec)][..., p.rows].sum())
                else:
                    total += int(host[id(p)].sum())
            i._overflow = total


def _pow2_at_least(n: int, lo: int = 8, hi: int = 1024) -> int:
    m = lo
    while m < min(n, hi):
        m *= 2
    return m


class Executor:
    """Runs plans over a ``UnifiedIndex`` or a LiveLake ``SegmentStore``.

    A store carries an ``epoch`` counter that every mutation bumps; the
    executor compares it lazily at query entry and rebuilds its MatchEngine
    when stale — so a Session over a live lake always observes a consistent
    epoch without any mutation hook into the executor.  (The value-hash
    memo survives refreshes: it is a pure function of cell values, not of
    the index.)"""

    def __init__(self, index: UnifiedIndex, m_cap_max: int = 1024,
                 row_cap: int = 8, backend: str = "sorted",
                 interpret: bool = False, bucket_width: int | None = None):
        self.index = index
        self.backend = backend
        self.interpret = interpret
        self.bucket_width = bucket_width
        self._engine_epoch = None
        self._build_engine()
        self.m_cap_max = m_cap_max
        self.row_cap = row_cap
        rungs = {min(c, m_cap_max) for c in CAP_LADDER}
        if m_cap_max > max(CAP_LADDER):
            rungs.add(m_cap_max)        # honor caps above the default ladder
        self.cap_ladder = tuple(sorted(rungs))
        self._hash_cache: dict = {}
        self._hash_cache_max = 1 << 20
        #: running totals that the flight recorder's ``hash`` and
        #: ``optimize`` spans read as differences: values looked up in the
        #: hash memo, those not in it (``hash_value`` ran), and planner
        #: statistics ``host_counts`` calls
        self.n_hashed = 0
        self.n_hash_misses = 0
        self.n_stat_scans = 0
        self._in_plan = False
        #: approximate tier: dense sketch packs, memoized per (epoch,
        #: geometry) — rebuilt lazily like the MatchEngine, never mid-query
        self._sketch_views_memo = None

    # ---------------------------------------------------------- live engine
    def _build_engine(self):
        idx = self.index
        if hasattr(idx, "segments"):       # LiveLake SegmentStore
            if self.bucket_width is not None:
                raise ValueError(
                    "bucket_width is not configurable on a live store: "
                    "each segment sizes its own lossless bucket layout")
            self.engine = MatchEngine.from_store(idx, backend=self.backend,
                                                 interpret=self.interpret)
            self._engine_epoch = idx.epoch
        else:
            self.engine = MatchEngine.from_index(
                idx, backend=self.backend, interpret=self.interpret,
                bucket_width=self.bucket_width)
        self.dev = self.engine.dev          # back-compat alias
        self.n_tables = idx.n_tables
        self.max_cols = idx.max_cols

    def refresh(self):
        """Pick up index mutations: rebuild the engine iff the store epoch
        moved (no-op for a static UnifiedIndex and for unchanged epochs).
        The value-hash memo survives: ``hash_value`` is a pure function of
        the cell value, independent of index epoch."""
        ep = getattr(self.index, "epoch", None)
        if ep is not None and ep != self._engine_epoch:
            self._build_engine()

    # ------------------------------------------------------------------ util
    def _hash_many(self, values) -> np.ndarray:
        """Memoized value hashing (shared across queries / plans).  The memo
        is bounded: a long-lived serving executor seeing an unbounded stream
        of distinct values evicts the oldest half (dict insertion order)
        instead of wiping everything — a full clear stampedes every hot
        value through a re-hash on the next request."""
        vals = list(values)
        out = np.empty(len(vals), np.uint32)
        cache = self._hash_cache
        if len(cache) > self._hash_cache_max:
            for k in list(cache)[:len(cache) // 2]:
                del cache[k]
        for i, v in enumerate(vals):
            h = cache.get(v)
            if h is None:
                h = hash_value(v)
                cache[v] = h
                self.n_hash_misses += 1
            out[i] = h
        self.n_hashed += len(vals)
        return out

    def _hashed(self, values) -> np.ndarray:
        """Hash + dedupe (SQL IN (...) set semantics)."""
        return np.unique(self._hash_many(values))

    @staticmethod
    def _pad_queries(h: np.ndarray, lo: int = 16):
        """Pad a hashed query array to the power-of-two shape ladder so any
        query set of the same capacity bucket reuses the compiled seeker."""
        n = len(h)
        width = _pow2_at_least(max(n, 1), lo=lo, hi=1 << 30)
        hp = np.full(width, PAD_SENTINEL, np.uint32)
        hp[:n] = h
        mask = np.zeros(width, bool)
        mask[:n] = True
        return jnp.asarray(hp), jnp.asarray(mask)

    def _stat_counts(self, h: np.ndarray) -> np.ndarray:
        """Planner-statistics counts: on a live store, tombstoned postings
        are excluded (they contribute no results, only probe-window slots),
        so seeker ranking reflects the live lake."""
        self.n_stat_scans += 1
        if hasattr(self.index, "segments"):
            return self.index.host_counts(h, live_only=True)
        return self.index.host_counts(h)

    def seeker_stats(self, spec: SeekerSpec):
        """(cardinality, n_cols, avg value frequency) — the cost features."""
        if spec.kind == "MC":
            freqs = []
            for c in range(spec.n_cols):
                h = self._hashed([t[c] for t in spec.values])
                freqs.append(self._stat_counts(h).mean())
            avg = float(np.prod(freqs))
            return (float(len(spec.values)), float(spec.n_cols), avg)
        h = self._hashed(spec.values)
        avg = float(self._stat_counts(h).mean()) if len(h) else 0.0
        return (float(len(spec.values)), float(spec.n_cols), avg)

    def _quantize_cap(self, need: int) -> int:
        for c in self.cap_ladder:
            if need <= c:
                return c
        return self.cap_ladder[-1]

    def _mcap_for(self, hashes: np.ndarray) -> int:
        counts = self.index.host_counts(hashes)
        return self._quantize_cap(int(counts.max(initial=1)))

    # ----------------------------------------------------------- sketch tier
    def _sketch_sources(self):
        """[(sketch_map, alive_mask, device)] — one entry per device pack.
        The sharded executor overrides this with one entry per shard; the
        base executor serves one pack on the default device."""
        idx = self.index
        if hasattr(idx, "sketch_map"):            # LiveLake SegmentStore
            return [(idx.sketch_map(), None, None)]
        return [(getattr(idx, "sketches", None) or {}, None, None)]

    def sketch_views(self):
        """Sorted sketch-posting views (core/sketch.py ``SketchView``),
        memoized per (epoch, geometry): probe cost is O(|Q| log + matches)
        — independent of posting count AND of table count — and the view
        only rebuilds when the index epoch or capacity changes, so probes
        never re-sort across repeated queries."""
        key = (getattr(self.index, "epoch", None), self.n_tables,
               self.max_cols)
        memo = self._sketch_views_memo
        if memo is None or memo[0] != key:
            cfg = getattr(self.index, "sketch_config", None) \
                or sk.SketchConfig()
            views = [sk.build_view(m, self.n_tables, self.max_cols, cfg,
                                   alive=alive)
                     for m, alive, _dev in self._sketch_sources()]
            self._sketch_views_memo = (key, views)
        return self._sketch_views_memo[1]

    def sketch_probe(self, spec: SeekerSpec,
                     confidence: float = 0.95) -> sk.SketchProbeResult:
        """Estimate one seeker's per-table scores from the sketch tier.

        Runs the host probe on every view (per shard on a sharded lake) and
        merges with one elementwise sum — each table's slots are nonzero on
        exactly one view, so the merge is exact and the 1-vs-N shard
        results are bit-identical.  MC has no sketch estimator (raises
        ValueError; the session falls back to the exact path)."""
        if not self._in_plan:
            self.refresh()
        t0 = time.perf_counter()
        from repro.obs import trace as otrace
        rec = otrace.current()
        views = self.sketch_views()

        def dispatch(make):
            outs = []
            for i, view in enumerate(views):
                with rec.span("sketch.probe.pack", kind=spec.kind, pack=i):
                    outs.append(make(view))
            return [sum(parts) for parts in zip(*outs)]

        if spec.kind in ("SC", "KW"):
            # distinct query hashes: the exact seekers are COUNT(DISTINCT)
            h = np.unique(self._hashed(spec.values))
            # a table score is a max over per-column intervals: Bonferroni
            # the per-column confidence so the max's interval holds jointly
            comparisons = self.max_cols if spec.kind == "SC" else 1
            z = sk.z_for(confidence, comparisons)
            level = "col" if spec.kind == "SC" else "tbl"
            lo, hi, est, ci_lo, ci_hi = dispatch(
                lambda v: v.containment(h, z, level=level))
            out = sk.SketchProbeResult(
                kind=spec.kind, estimator="kmv-bottomk", est=est,
                bound_lo=lo, bound_hi=hi, ci_lo=ci_lo, ci_hi=ci_hi,
                sound=True)
        elif spec.kind == "C":
            pairs = list(dict.fromkeys(zip(spec.values, spec.target)))
            h = self._hash_many([p[0] for p in pairs])
            tgt = np.array([float(p[1]) for p in pairs])
            qbit = (tgt >= tgt.mean()).astype(np.int8)
            # dedupe join hashes keeping the first pair's quadrant bit (the
            # exact seeker probes in first-occurrence order too)
            hu, first = np.unique(h, return_index=True)
            qb = qbit[first]
            # the score is a max over (join col, numeric col) pairs
            z = sk.z_for(confidence, self.max_cols ** 2)

            def make(view):
                est, lo, hi, support = view.correlation(
                    hu, qb, z, min_support=sk.SAMPLE_MIN_SUPPORT)
                # sound join gate: zero containment upper bound over the
                # join values => the table cannot join => exact score is 0
                _, cont_hi, _, _, _ = view.containment(hu, 0.0, level="col")
                return est, lo, hi, support, cont_hi

            est, ci_lo, ci_hi, support, cont_hi = dispatch(make)
            impossible = cont_hi <= 0
            # joinable but unseen in the sample: report the uninformative
            # interval instead of a falsely tight one
            no_est = (support <= 0) & ~impossible
            est = np.where(support > 0, est, 0.0).astype(np.float32)
            ci_lo = np.where(support > 0, ci_lo, 0.0).astype(np.float32)
            ci_hi = np.where(impossible, 0.0,
                             np.where(no_est, 1.0, ci_hi)).astype(np.float32)
            out = sk.SketchProbeResult(
                kind="C", estimator="sample-qcr", est=est, bound_lo=ci_lo,
                bound_hi=ci_hi, ci_lo=ci_lo, ci_hi=ci_hi, sound=False,
                impossible=impossible)
        else:
            raise ValueError(
                f"no sketch estimator for seeker kind {spec.kind!r}")
        out.seconds = time.perf_counter() - t0
        out.launches = 0                 # host-side probe: no device programs
        reg = obs.registry()
        reg.counter("approx.sketch_probes").inc()
        reg.histogram("approx.probe_seconds").observe(out.seconds)
        return out

    # --------------------------------------------------------------- seekers
    def run_seeker(self, spec: SeekerSpec, allowed=None,
                   sync: bool = True) -> comb.ResultSet:
        if not self._in_plan:   # a running plan already pinned its epoch
            self.refresh()
        self._last_launches = 1
        if spec.kind in ("SC", "KW"):
            h = self._hashed(spec.values)
            m_cap = self._mcap_for(h)
            qh, qm = self._pad_queries(h)
            fn = seek.sc_seeker if spec.kind == "SC" else seek.kw_seeker
            kw = dict(m_cap=m_cap, n_tables=self.n_tables)
            if spec.kind == "SC":
                kw["max_cols"] = self.max_cols
            scores, ovf = fn(self.engine, qh, qm, allowed=allowed, **kw)
        elif spec.kind == "MC":
            values = list(dict.fromkeys(spec.values))   # dedupe tuples
            nt = len(values)
            n_cols = spec.n_cols
            th = np.stack([self._hash_many([t[c] for t in values])
                           for c in range(n_cols)], axis=1)       # [nt, n_cols]
            counts = np.stack([self.index.host_counts(th[:, c])
                               for c in range(n_cols)], axis=1)
            init_col = np.argmin(counts, axis=1).astype(np.int32)
            qks = np.array([row_superkey(th[i], np.zeros(n_cols, np.int64))
                            for i in range(nt)], np.uint64)
            qk_lo, qk_hi = split_u64(qks)
            m_cap = self._quantize_cap(int(counts.max(initial=1)))
            # pad the tuple batch onto the shape ladder
            ntp = _pow2_at_least(max(nt, 1), lo=8, hi=1 << 30)
            pad = ntp - nt
            th = np.pad(th, ((0, pad), (0, 0)))
            init_col = np.pad(init_col, (0, pad))
            qk_lo, qk_hi = np.pad(qk_lo, (0, pad)), np.pad(qk_hi, (0, pad))
            tmask = np.zeros(ntp, bool)
            tmask[:nt] = True
            args = (self.engine, jnp.asarray(th), jnp.asarray(init_col),
                    jnp.asarray(qk_lo), jnp.asarray(qk_hi))
            if sync:
                # stage 1: survivor counts after predicate + bloom -> the
                # stage-2 validation runs with compacted candidate buffers
                # (this is where the threaded 'WHERE TableId IN (IR)'
                # actually shrinks work)
                self._last_launches = 2
                surv = seek.mc_survivor_counts(*args, m_cap=m_cap,
                                               allowed=allowed,
                                               tuple_mask=jnp.asarray(tmask))
                m_cap2 = self._quantize_cap(int(jnp.max(surv)))
                scores, _rows, ovf = seek.mc_seeker_compact(
                    *args, m_cap=m_cap, m_cap2=min(m_cap2, m_cap),
                    n_tables=self.n_tables, n_cols=n_cols,
                    row_stride=self.index.row_stride, allowed=allowed,
                    tuple_mask=jnp.asarray(tmask))
            else:
                # async dispatch: skip the data-dependent compaction stage
                # (its capacity pick is a host sync); validate at full m_cap
                scores, _rows, ovf = seek.mc_seeker(
                    *args, m_cap=m_cap, n_tables=self.n_tables,
                    n_cols=n_cols, row_stride=self.index.row_stride,
                    allowed=allowed, tuple_mask=jnp.asarray(tmask))
        elif spec.kind == "C":
            pairs = list(dict.fromkeys(zip(spec.values, spec.target)))
            h = self._hash_many([p[0] for p in pairs])
            tgt = np.array([float(p[1]) for p in pairs])
            qbit = (tgt >= tgt.mean()).astype(np.int8)            # k0/k1 split
            m_cap = self._mcap_for(h)
            qh, qm = self._pad_queries(h)
            qbit = np.pad(qbit, (0, qh.shape[0] - len(qbit)))
            kw = dict(m_cap=m_cap, row_cap=self.row_cap,
                      n_tables=self.n_tables, max_cols=self.max_cols,
                      h_sample=spec.h, sampling=spec.sampling,
                      row_stride=self.index.row_stride, allowed=allowed)
            if allowed is not None and sync:
                # two-stage: compact the join side to the surviving postings
                self._last_launches = 2
                surv = int(seek.c_survivor_counts(self.engine, qh, qm,
                                                  m_cap=m_cap,
                                                  allowed=allowed))
                cap2 = _pow2_at_least(max(surv, 1),
                                      hi=int(qh.shape[0]) * m_cap)
                scores, ovf = seek.c_seeker_compact(self.engine, qh, qm,
                                                    jnp.asarray(qbit),
                                                    cap2=cap2, **kw)
            else:
                scores, ovf = seek.c_seeker(self.engine, qh, qm,
                                            jnp.asarray(qbit), **kw)
        else:
            raise ValueError(spec.kind)
        if sync:
            scores.block_until_ready()
        self._last_overflow = ovf
        return comb.topk_result(scores, spec.k)

    # ------------------------------------------------------------------ plan
    def run(self, plan: Plan, optimize: bool = True,
            cost_model: CostModel | None = None, sync: bool = True,
            cache=None, fused: bool = False):
        """Execute ``plan``.  ``cache`` is an optional query-cache handle
        (duck-typed ``seeker_key``/``get_seeker``/``put_seeker`` — see
        serve/cache.py): unrestricted seeker runs are served from and stored
        into its subplan level, short-circuiting ``run_seeker``.  Seekers
        that would run under a threaded optimizer mask still execute, so a
        partially-cached plan is bit-identical to a cold run.

        ``fused=True`` routes through core/fused.py: all same-kind seekers
        dispatch as one batched device program and the combiner DAG compiles
        to a single jitted program, so the plan executes in
        ``~n_kinds + 1`` launches (``ExecInfo.launches``) instead of one
        per node — bit-identical to the unfused walk."""
        self.refresh()          # one consistent epoch for the whole plan
        self._in_plan = True    # nested run_seeker calls must not re-refresh
        try:
            if fused:
                from repro.core.fused import run_fused
                rs, info = run_fused(self, [plan], optimize=optimize,
                                     cost_model=cost_model, cache=cache)[0]
                if sync:
                    rs.scores.block_until_ready()
                return rs, info
            return self._run(plan, optimize, cost_model, sync, cache)
        finally:
            self._in_plan = False

    def run_many(self, plans, optimize: bool = True,
                 cost_model: CostModel | None = None, sync: bool = True,
                 cache=None):
        """Fused batch execution: same-kind seekers are batched *across all
        plans* into shared device launches (serve/engine.py ``serve_many``'s
        fused mode).  Returns [(ResultSet, ExecInfo)] aligned with
        ``plans``; with ``sync=False`` nothing synchronizes — the caller
        drains the device once."""
        from repro.core.fused import run_fused
        self.refresh()
        self._in_plan = True
        try:
            out = run_fused(self, list(plans), optimize=optimize,
                            cost_model=cost_model, cache=cache)
        finally:
            self._in_plan = False
        if sync:
            jax.block_until_ready([rs.scores for rs, _ in out])
        return out

    def _run(self, plan: Plan, optimize: bool, cost_model, sync: bool,
             cache=None):
        info = ExecInfo(optimized=optimize)
        ep = optimize_plan(plan, self.seeker_stats, cost_model) if optimize \
            else None
        memo: dict[str, comb.ResultSet] = {}

        def timed_seeker(name, spec, allowed=None):
            t0 = time.perf_counter()
            hit = None
            key = None
            if cache is not None and allowed is None:
                key = cache.seeker_key(spec)
                hit = cache.get_seeker(key)
            if hit is not None:
                rs = hit.result
                info.overflow_parts.append(hit.overflow)
                info.cached_nodes.append(name)
            else:
                rs = self.run_seeker(spec, allowed=allowed, sync=sync)
                info.seeker_runs += 1
                info.launches += self._last_launches
                info.overflow_parts.append(self._last_overflow)
                if key is not None:
                    cache.put_seeker(key, rs, self._last_overflow,
                                     self.n_tables)
            info.node_seconds[name] = time.perf_counter() - t0
            info.order.append(name)
            return rs

        def eval_node(name: str) -> comb.ResultSet:
            if name in memo:
                return memo[name]
            node = plan.nodes[name]
            if node.is_seeker:
                rs = timed_seeker(name, node.spec)
            else:
                kind = node.spec.kind
                k = node.spec.k
                if optimize and ep is not None and name in ep.groups:
                    rs = self._run_group(plan, ep.groups[name], node, info,
                                         timed_seeker, eval_node, memo)
                elif kind == "difference":
                    a = eval_node(node.deps[0])
                    b_node = plan.nodes[node.deps[1]]
                    if optimize and b_node.is_seeker and \
                            len(plan.consumers(b_node.name)) == 1 and \
                            b_node.name not in memo:
                        # rewriting: restrict the subtrahend to the minuend's
                        # tables (WHERE TableId IN (IR_a))
                        b = timed_seeker(b_node.name, b_node.spec,
                                         allowed=a.mask)
                        memo[b_node.name] = b
                    else:
                        b = eval_node(node.deps[1])
                    t0 = time.perf_counter()
                    rs = comb.difference(a, b, k)
                    info.node_seconds[name] = time.perf_counter() - t0
                    info.order.append(name)
                    info.launches += 1
                else:
                    deps = [eval_node(d) for d in node.deps]
                    t0 = time.perf_counter()
                    if kind == "intersect":
                        rs = comb.intersect(deps, k)
                    elif kind == "union":
                        rs = comb.union(deps, k)
                    elif kind == "counter":
                        rs = comb.counter(deps, k)
                    else:
                        raise ValueError(kind)
                    info.node_seconds[name] = time.perf_counter() - t0
                    info.order.append(name)
                    info.launches += 1
            memo[name] = rs
            return rs

        result = eval_node(plan.output)
        reg = obs.registry()
        reg.counter("exec.plans").inc()
        reg.counter("exec.launches").inc(info.launches)
        reg.counter("exec.seeker_runs").inc(info.seeker_runs)
        reg.histogram("exec.plan_seconds").observe(info.total_seconds)
        return result, info

    def _run_group(self, plan, eg, combiner_node, info, timed_seeker,
                   eval_node, memo):
        """Ranked execution-group run with mask threading (Intersection)."""
        results = []
        allowed = None
        for sname in eg.seekers:
            if sname in memo:
                # shared seeker (>= 2 consumers, hash-consed subtree): it was
                # executed unrestricted once already — reuse, don't re-probe
                rs = memo[sname]
            else:
                exclusive = len(plan.consumers(sname)) == 1
                rs = timed_seeker(sname, plan.nodes[sname].spec,
                                  allowed=allowed if exclusive else None)
                memo[sname] = rs
            results.append(rs)
            allowed = rs.mask if allowed is None else (allowed & rs.mask)
        # non-seeker deps of the combiner are evaluated normally
        for dep in combiner_node.deps:
            if dep not in eg.seekers:
                results.append(eval_node(dep))
        t0 = time.perf_counter()
        rs = comb.intersect(results, combiner_node.spec.k)
        info.node_seconds[combiner_node.name] = time.perf_counter() - t0
        info.order.append(combiner_node.name)
        info.launches += 1
        return rs
