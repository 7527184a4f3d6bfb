"""Fused plan execution: batched same-kind seeker dispatch + whole-DAG
device compilation.

The unfused executor pays one device program per seeker node (two for the
compaction stages) plus a Python re-entry between every combiner — on deep
discovery DAGs launch overhead, not probe work, dominates warm-path latency.
The fused path collapses a plan (or a whole ``serve_many`` batch) to
``n_kinds + 1`` launches:

1. **Batched seeker dispatch** — all same-kind seekers, across every plan in
   the batch, are concatenated into one padded query array with per-row
   seeker ids, probed once into one flat window that holds every posting of
   every query value (``MatchEngine.window``) and grouped-by into a stacked
   ``[n_seekers, n_tables]`` score matrix (seekers.py ``*_seeker_seg``).
   The program walks the window in chunks of slots; how many comes from
   ONE ``host_counts`` call over every seeker's hashes (the group's summed
   match counts), so no value is cut however many postings it has.
2. **Whole-DAG device compilation** — the post-seeker combiner DAG
   (top-k / intersect / union / difference / counter / optimizer mask
   threading) is elementwise over ``[n_tables]`` vectors, so the entire DAG
   lowers to one jitted program keyed on the (static, hashable) instruction
   list derived from the plan topology.  Zero intermediate host syncs.

Bit-identity with the unfused executor rests on two invariants:

* the flat window holds every posting of each seeker's query values, as an
  exact dedicated launch would, and every seeker score is a sum / max of
  0-or-1 float contributions (or a QCR ratio of such sums), so the stacked
  rows equal the dedicated launches bit-for-bit;
* a seeker run under the optimizer's threaded ``allowed`` mask equals
  ``where(allowed, unrestricted_scores, 0)`` followed by the same top-k —
  the mask is constant per table and is ANDed into contributions *before* a
  per-table group-by — so mask threading moves into the DAG program, where
  the masks live on device, and the batched seekers all run unrestricted.

Query-cache composition: seekers served from the subplan cache drop out of
the batch entirely — their cached (scores, mask) vectors are fed to the DAG
program as extra inputs.  As in the unfused path, only unrestricted runs are
served from or stored into the cache, so partial hits stay bit-identical to
a cold run.

Retrace-freedom: the batch query width, the tuple-block width, the seeker
count and the window's chunk are all quantized onto power-of-two ladders
(the number of chunks is an operand), and the DAG program is keyed on plan
topology — re-running any plan shape with new values of the same buckets
is zero-trace
(``seekers.TRACE_COUNTS``-asserted in tests/test_fused.py).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro import faults, obs
from repro.core import seekers as seek
from repro.core.combiners import ResultSet
from repro.obs import trace as otrace
from repro.core.executor import (ExecInfo, OverflowSlice, PAD_SENTINEL,
                                 _pow2_at_least)
from repro.core.optimizer import optimize as optimize_plan


@dataclass
class _Task:
    """One pending (unrestricted) seeker dispatch in the fused batch."""
    plan_idx: int
    name: str
    spec: object
    instr_idx: int                    # its placeholder slot in the plan prog
    # hashed query payload (filled by _hash_tasks)
    h: np.ndarray | None = None      # SC/KW/C: hashed values
    qbit: np.ndarray | None = None   # C: k0/k1 split bits
    th: np.ndarray | None = None     # MC: [nt, n_cols] hashed tuples
    init_col: np.ndarray | None = None
    nt: int = 0                      # MC: deduped tuple count
    #: postings of each probed value (MC: each tuple's initiator value);
    #: ``[n_shards, n]`` on a sharded lake, where a shard probes only its
    #: own
    counts: np.ndarray | None = None
    group_key: tuple = ()
    row: int = -1                    # row in the group's stacked output
    head: object = None              # canonical task for this spec: dupes
    #                                  share its hashes, batch row and scores


@dataclass
class _PlanProg:
    """A plan compiled to a linear DAG program + its pending seeker batch."""
    instrs: list = field(default_factory=list)
    order: list = field(default_factory=list)        # ExecInfo.order parity
    tasks: list = field(default_factory=list)        # _Task, traversal order
    cached: list = field(default_factory=list)       # CachedSeeker hits
    cached_names: list = field(default_factory=list)
    cache_puts: list = field(default_factory=list)   # (key, reg, task)
    out_reg: int = 0


def _group_key(spec) -> tuple:
    """Seekers sharing a key share one device program: the kind plus every
    per-seeker *static* argument of its segmented kernel."""
    if spec.kind == "MC":
        return ("MC", spec.n_cols)
    if spec.kind == "C":
        return ("C", spec.h, spec.sampling)
    return (spec.kind,)


# --------------------------------------------------------------------------
# plan -> linear DAG program (mirrors Executor._run's traversal exactly,
# including the memoization, EG mask threading, the difference-subtrahend
# rewrite and the subplan-cache consultation order)
# --------------------------------------------------------------------------

def _compile_plan(plan, optimize, ep, cache, plan_idx) -> _PlanProg:
    pr = _PlanProg()
    reg_of: dict[str, int] = {}

    def emit(ins) -> int:
        pr.instrs.append(ins)
        return len(pr.instrs) - 1

    def seeker_node(name, spec, allowed_reg) -> int:
        # mirrors timed_seeker: cache serves/stores unrestricted runs only
        key = cache.seeker_key(spec) \
            if cache is not None and allowed_reg is None else None
        if key is not None:
            hit = cache.get_seeker(key)
            if hit is not None:
                reg = emit(("cached", len(pr.cached)))
                pr.cached.append(hit)
                pr.cached_names.append(name)
                pr.order.append(name)
                return reg
        task = _Task(plan_idx=plan_idx, name=name, spec=spec,
                     instr_idx=len(pr.instrs))
        # the task's ordinal within the plan is stable across batch
        # compositions; its batch row is resolved through the traced
        # ``rows`` vector at run time, so reshuffled batches reuse the
        # compiled DAG program
        reg = emit(("seeker", None, len(pr.tasks), spec.k,
                    -1 if allowed_reg is None else allowed_reg))
        pr.tasks.append(task)
        if key is not None:
            pr.cache_puts.append((key, reg, task))
        pr.order.append(name)
        return reg

    def run_group(eg, combiner_node) -> int:
        results = []
        allowed = None
        for sname in eg.seekers:
            if sname in reg_of:
                r = reg_of[sname]
            else:
                exclusive = len(plan.consumers(sname)) == 1
                r = seeker_node(sname, plan.nodes[sname].spec,
                                allowed if exclusive else None)
                reg_of[sname] = r
            results.append(r)
            allowed = r if allowed is None else emit(("maskand", allowed, r))
        for dep in combiner_node.deps:
            if dep not in eg.seekers:
                results.append(eval_node(dep))
        reg = emit(("intersect", tuple(results), combiner_node.spec.k))
        pr.order.append(combiner_node.name)
        return reg

    def eval_node(name: str) -> int:
        if name in reg_of:
            return reg_of[name]
        node = plan.nodes[name]
        if node.is_seeker:
            reg = seeker_node(name, node.spec, None)
        else:
            kind = node.spec.kind
            k = node.spec.k
            if optimize and ep is not None and name in ep.groups:
                reg = run_group(ep.groups[name], node)
            elif kind == "difference":
                a = eval_node(node.deps[0])
                b_node = plan.nodes[node.deps[1]]
                if optimize and b_node.is_seeker and \
                        len(plan.consumers(b_node.name)) == 1 and \
                        b_node.name not in reg_of:
                    b = seeker_node(b_node.name, b_node.spec, a)
                    reg_of[b_node.name] = b
                else:
                    b = eval_node(node.deps[1])
                reg = emit(("difference", a, b, k))
                pr.order.append(name)
            else:
                deps = tuple(eval_node(d) for d in node.deps)
                reg = emit((kind, deps, k))
                pr.order.append(name)
        reg_of[name] = reg
        return reg

    pr.out_reg = eval_node(plan.output)
    return pr


# --------------------------------------------------------------------------
# batched hashing + ONE host_counts call for every window size
# --------------------------------------------------------------------------

#: a seeker program walks its flat window in chunks of a power of two of
#: slots between these two: a small batch takes one chunk the size of its
#: postings, a large one as many of the largest as its postings need.  The
#: chunk, not the count, is the program's static shape, so a serving mix
#: compiles a few programs, not one per window size (a v5e compiles one in
#: 1-15 s)
MIN_CHUNK, MAX_CHUNK = 1 << 10, 1 << 14
#: the smallest padded query array (values, or MC tuples) and seeker count
#: of a launch, each size being a program of its own.  The device searches
#: every padded value's run (~22 gathers each), so the width floor stays
#: low; 16 seekers hold one of each kind for a whole batch of the server's
#: default policy
MIN_WIDTH, MIN_SEEKERS = 256, 16


def _walk(matched: int) -> tuple:
    """(chunk, n_chunks) of a flat window that holds ``matched`` postings."""
    chunk = min(_pow2(matched, lo=MIN_CHUNK), MAX_CHUNK)
    return chunk, -(-matched // chunk)


def _hash_tasks(ex, groups) -> dict:
    """Hash every pending seeker's query values (through the executor's
    memoized value-hash cache) and size every group's flat window from one
    batched ``host_counts`` lookup over the concatenated hash arrays.
    Returns each group's walk of its window, ``(chunk, n_chunks)``, keyed
    like ``groups`` (a tuple of per-shard walks on a sharded lake)."""
    tasks = [t for g in groups.values() for t in g]
    if not tasks:
        return {}
    rec = otrace.current()
    reqs = []
    with rec.span("hash") as sp:
        hashed0, misses0 = ex.n_hashed, ex.n_hash_misses
        for t in tasks:
            spec = t.spec
            if spec.kind in ("SC", "KW"):
                t.h = ex._hashed(spec.values)
                reqs.append(t.h)
            elif spec.kind == "C":
                pairs = list(dict.fromkeys(zip(spec.values, spec.target)))
                t.h = ex._hash_many([p[0] for p in pairs])
                tgt = np.array([float(p[1]) for p in pairs])
                t.qbit = (tgt >= tgt.mean()).astype(np.int8) if len(tgt) \
                    else np.zeros(0, np.int8)
                reqs.append(t.h)
            else:                                       # MC
                values = list(dict.fromkeys(spec.values))
                t.nt = len(values)
                n_cols = spec.n_cols
                t.th = np.stack([ex._hash_many([v[c] for v in values])
                                 for c in range(n_cols)], axis=1) \
                    if values else np.zeros((0, n_cols), np.uint32)
                reqs.append(t.th.reshape(-1))
        if rec.enabled:
            sp.set("values", ex.n_hashed - hashed0)
            sp.set("misses", ex.n_hash_misses - misses0)
    with rec.span("capacity") as sp:
        lens = np.array([len(r) for r in reqs], np.int64)
        offs = np.concatenate([[0], np.cumsum(lens)])
        all_h = np.concatenate(reqs) if offs[-1] else np.zeros(0, np.uint32)
        n_shards = getattr(ex, "n_shards", 0)
        if n_shards:
            # per-shard counts in the same ONE batched lookup: the MC
            # initiator-column pick comes from the summed counts —
            # identical to a 1-shard run — while each shard's window sizes
            # to its own counts (a shard only holds its own tables'
            # postings)
            per = ex.index.host_counts(all_h, per_shard=True)
            counts = per.sum(axis=0)
        else:
            counts = ex.index.host_counts(all_h)
        for i, t in enumerate(tasks):
            cut = slice(offs[i], offs[i + 1])
            pick = slice(None)
            if t.spec.kind == "MC":
                cm = counts[cut].reshape(t.nt, t.spec.n_cols)
                t.init_col = np.argmin(cm, axis=1).astype(np.int32)
                # the window holds each tuple's initiator postings
                pick = np.arange(t.nt) * t.spec.n_cols + t.init_col
            t.counts = (per[:, cut] if n_shards else counts[cut])[..., pick]
        walks, matched = {}, 0
        for key, g in groups.items():
            c = np.concatenate([t.counts for t in g], axis=-1)
            if key[0] in ("SC", "KW"):
                # the window holds each distinct value of the group once
                c = c[..., _distinct(g)[2]]
            c = c.sum(axis=-1)
            matched += int(c.sum())
            walks[key] = tuple(_walk(int(m)) for m in c) if n_shards \
                else _walk(int(c))
        if rec.enabled:
            sp.set("hashes", len(all_h))
            sp.set("slots", sum(_slots(w) for w in walks.values()))
            sp.set("matched", matched)
    return walks


def _distinct(tasks) -> tuple:
    """The distinct values of a group of SC or KW seekers: (their hashes,
    ``[n, len(tasks)]`` which seekers hold each, and each one's first place
    in the seekers' values laid end to end)."""
    h = np.concatenate([t.h for t in tasks])
    u, first, inv = np.unique(h, return_index=True, return_inverse=True)
    member = np.zeros((len(u), len(tasks)), bool)
    member[inv.reshape(-1), np.repeat(np.arange(len(tasks)),
                                      [len(t.h) for t in tasks])] = True
    return u, member, first


def _slots(walk) -> int:
    """Slots a walk (or a tuple of per-shard walks) covers."""
    if isinstance(walk[0], tuple):
        return sum(_slots(w) for w in walk)
    return walk[0] * walk[1]


# --------------------------------------------------------------------------
# group batch assembly + launch
# --------------------------------------------------------------------------

def _pow2(n: int, lo: int) -> int:
    return _pow2_at_least(max(n, 1), lo=lo, hi=1 << 30)


def _launch_group(ex, key, tasks, walk, failed=None):
    """Dispatch one seeker group as a single device program.  Returns
    (scores [n_seekers_p, n_tables], overflow [n_seekers_p]) — both lazy.
    ``tasks`` are the deduped head tasks of the group (run_fused collapses
    identical specs before hashing).

    ``walk`` is the window's ``(chunk, n_chunks)`` (``_hash_tasks``; a tuple
    of per-shard walks on a sharded lake).

    Sharded executors (``ex.engines``) dispatch the same batched program
    once per shard — same query operands, per-shard windows — and
    return *tuples* of per-shard (scores, overflow).  Each shard holds
    whole tables, so summing the per-shard matrices (inside ``_run_dag``)
    is exact: every table slot is nonzero on exactly one shard.  The whole
    per-shard fan-out is ONE logical launch (ExecInfo.launches).

    Graceful degradation: a shard probe that raises is retried once on a
    freshly rebuilt shard engine (``ex.reset_shard``); a second failure
    drops the shard from this launch — its (scores, overflow) are
    zero-substituted, which the exact merge treats as "no tables here" —
    and its index lands in ``failed`` so the response is flagged degraded
    rather than silently partial."""
    for i, t in enumerate(tasks):
        t.row = i
    kind = key[0]
    # lo=8: a serving batch's per-kind seeker count varies with every batch
    # composition; padding the stacked output to at least 8 rows collapses
    # nsp (and with it the DAG program's group-matrix input shapes) onto a
    # couple of buckets, so reshuffled batches stop retracing.  The padding
    # itself is dead rows in a [nsp, n_tables] matrix — negligible next to
    # the probe work, and single-plan latency is unaffected (measured).
    nsp = _pow2(len(tasks), lo=MIN_SEEKERS)

    if kind == "MC":
        n_cols = key[1]
        total = sum(t.nt for t in tasks)
        width = _pow2(total, lo=MIN_WIDTH)
        th = np.zeros((width, n_cols), np.uint32)
        init = np.zeros(width, np.int32)
        seg = np.zeros(width, np.int32)
        tmask = np.zeros(width, bool)
        off = 0
        for i, t in enumerate(tasks):
            n = t.nt
            th[off:off + n] = t.th
            init[off:off + n] = t.init_col
            seg[off:off + n] = i
            tmask[off:off + n] = True
            off += n

        # numpy operands go straight into the jitted call: jit's own
        # device_put of the whole operand list is much cheaper than
        # per-array jnp.asarray round-trips on the hot path (and, being
        # uncommitted, they follow each shard engine to its device)
        def dispatch(eng, walk):
            return seek.mc_seeker_seg(
                eng, th, init, seg, np.int32(walk[1]), chunk=walk[0],
                n_seekers=nsp, n_tables=ex.n_tables, n_cols=n_cols,
                tuple_mask=tmask)
    elif kind in ("SC", "KW"):
        h, member, _ = _distinct(tasks)
        width = _pow2(len(h), lo=MIN_WIDTH)
        qh = np.full(width, PAD_SENTINEL, np.uint32)
        qh[:len(h)] = h
        qm = np.arange(width) < len(h)
        mem = np.zeros((width, nsp), bool)
        mem[:len(h), :len(tasks)] = member
        fn, opts = (seek.sc_seeker_seg, dict(max_cols=ex.max_cols)) \
            if kind == "SC" else (seek.kw_seeker_seg, {})

        def dispatch(eng, walk):
            return fn(eng, qh, qm, mem, np.int32(walk[1]), chunk=walk[0],
                      n_tables=ex.n_tables, **opts)
    else:
        total = sum(len(t.h) for t in tasks)
        width = _pow2(total, lo=MIN_WIDTH)
        qh = np.full(width, PAD_SENTINEL, np.uint32)
        qm = np.zeros(width, bool)
        seg = np.zeros(width, np.int32)
        qb = np.zeros(width, np.int8)
        off = 0
        for i, t in enumerate(tasks):
            n = len(t.h)
            qh[off:off + n] = t.h
            qm[off:off + n] = True
            seg[off:off + n] = i
            qb[off:off + n] = t.qbit
            off += n

        def dispatch(eng, walk):
            chunk, n_chunks = walk[0], np.int32(walk[1])
            return seek.c_seeker_seg(eng, qh, qm, qb, seg, n_chunks,
                                     chunk=chunk, row_cap=ex.row_cap,
                                     n_seekers=nsp, n_tables=ex.n_tables,
                                     max_cols=ex.max_cols, h_sample=key[1],
                                     sampling=key[2],
                                     row_stride=ex.index.row_stride)

    engines = getattr(ex, "engines", None)
    rec = otrace.current()
    mreg = obs.registry()
    if engines is None:
        with rec.span("shard:0", slots=_slots(walk), chunk=walk[0],
                      seekers=len(tasks)):
            return dispatch(ex.engine, walk)
    scores, ovf = [], []
    for s, eng in enumerate(engines):
        with rec.span(f"shard:{s}", slots=_slots(walk[s]),
                      chunk=walk[s][0], seekers=len(tasks)):
            try:
                faults.checkpoint(f"shard.probe.{s}")
                sc, ov = dispatch(eng, walk[s])
            except Exception:                        # noqa: BLE001
                # InjectedCrash (BaseException) deliberately passes through:
                # a simulated kill -9 must not be absorbed as a shard retry
                mreg.counter("shard.failures").inc()
                try:
                    eng = ex.reset_shard(s)
                    faults.checkpoint(f"shard.probe.{s}")
                    sc, ov = dispatch(eng, walk[s])
                    mreg.counter("shard.retries").inc()
                except Exception:                    # noqa: BLE001
                    # rebuilt engine failed too: drop the shard from the
                    # merge — zeros are exactly "no tables live here"
                    mreg.counter("shard.dropped").inc()
                    if failed is not None:
                        failed.add(s)
                    sc = jnp.zeros((nsp, ex.n_tables), jnp.float32)
                    ov = jnp.zeros(nsp, jnp.int32)
        # stage results on the merge device so the single DAG program
        # consumes them without implicit cross-device transfers
        scores.append(jax.device_put(sc, ex.merge_device))
        ovf.append(jax.device_put(ov, ex.merge_device))
    return tuple(scores), tuple(ovf)


# --------------------------------------------------------------------------
# the whole-DAG device program
# --------------------------------------------------------------------------

def _topk(scores, k: int):
    """Mirrors combiners.topk_result on raw (scores, mask) pairs."""
    k = min(k, scores.shape[0])
    vals, ids = jax.lax.top_k(scores, k)
    keep = vals > 0
    mask = jnp.zeros(scores.shape[0], bool).at[ids].set(keep)
    return jnp.where(mask, scores, 0.0), mask


def _maybe_topk(scores, mask, k):
    """Mirrors combiners._maybe_topk: ``k=None`` keeps the combiner's own
    mask (no cut) — the same contract legacy cut-free plans rely on."""
    scores = jnp.where(mask, scores, 0.0)
    if k is None:
        return scores, mask
    return _topk(scores, k)


@functools.partial(jax.jit, static_argnames=("prog",))
def _run_dag(group_scores, rows, cached_scores, cached_masks, *, prog):
    """Execute one plan's compiled instruction list in a single device
    program.  ``group_scores`` is the tuple of stacked seeker score matrices
    this plan consumes and ``rows`` the traced vector mapping each seeker
    ordinal to its batch row — traced so a reshuffled serve_many batch of
    the same plan shapes reuses the compiled program.  Every op mirrors its
    combiners.py counterpart exactly (same op order, same top-k), so
    outputs are bit-identical to the node-at-a-time walk."""
    seek._mark_trace("DAG")
    regs = []
    for ins in prog:
        op = ins[0]
        if op == "seeker":
            _, gi, j, k, allowed = ins
            gs = group_scores[gi]
            if isinstance(gs, tuple):
                # sharded group: sum the per-shard score matrices' rows —
                # exact in f32 (each table slot is nonzero on exactly one
                # shard; the rest contribute literal zeros).  This is the
                # whole cross-shard merge epilogue: it fuses into the one
                # DAG program, costing no extra launch.
                s = gs[0][rows[j]]
                for m in gs[1:]:
                    s = s + m[rows[j]]
            else:
                s = gs[rows[j]]
            if allowed >= 0:
                s = jnp.where(regs[allowed][1], s, 0.0)
            regs.append(_topk(s, k))
        elif op == "cached":
            regs.append((cached_scores[ins[1]], cached_masks[ins[1]]))
        elif op == "maskand":
            regs.append((regs[ins[1]][0], regs[ins[1]][1] & regs[ins[2]][1]))
        elif op == "intersect":
            _, deps, k = ins
            scores, mask = regs[deps[0]]
            for d in deps[1:]:
                mask = mask & regs[d][1]
                scores = scores + regs[d][0]
            regs.append(_maybe_topk(scores, mask, k))
        elif op == "union":
            _, deps, k = ins
            scores, mask = regs[deps[0]]
            for d in deps[1:]:
                mask = mask | regs[d][1]
                scores = jnp.maximum(scores, regs[d][0])
            regs.append(_maybe_topk(scores, mask, k))
        elif op == "difference":
            _, a, b, k = ins
            mask = regs[a][1] & ~regs[b][1]
            regs.append(_maybe_topk(regs[a][0], mask, k))
        elif op == "counter":
            _, deps, k = ins
            counts = jnp.zeros_like(regs[deps[0]][0])
            for d in deps:
                counts = counts + regs[d][1].astype(jnp.float32)
            regs.append(_maybe_topk(counts, counts > 0, k))
        else:
            raise ValueError(op)
    return tuple(regs)


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _empty_cached(n_tables: int):
    """Shared zero-width placeholder inputs for plans with no cached
    seekers — built eagerly once per table count instead of dispatching two
    ``jnp.zeros`` device programs per plan per batch (a measurable share of
    the warm serve_many hot path)."""
    return (jnp.zeros((0, n_tables), jnp.float32),
            jnp.zeros((0, n_tables), bool))


def run_fused(ex, plans, optimize=True, cost_model=None, cache=None):
    """Execute ``plans`` (one or a whole serve_many batch) on the fused
    path; returns [(ResultSet, ExecInfo)] aligned with ``plans``.  The
    caller (Executor.run / Executor.run_many) owns engine refresh and the
    final drain."""
    rec = otrace.current()
    mreg = obs.registry()
    with rec.span("optimize") as sp:
        stats0 = (ex.n_stat_scans, ex.n_hashed, ex.n_hash_misses,
                  getattr(ex.index, "n_stat_postings", 0))
        eps = [optimize_plan(p, ex.seeker_stats, cost_model) if optimize
               else None for p in plans]
        if rec.enabled:
            scans = ex.n_stat_scans - stats0[0]
            sp.set("seekers", sum(len(g.seekers) for e in eps if e is not None
                                  for g in e.groups.values()))
            sp.set("stats_scans", scans)
            # postings whose alive flag the scans gathered: 0 unless a
            # segment holds a set of dead tables not seen before
            sp.set("stats_postings",
                   getattr(ex.index, "n_stat_postings", 0) - stats0[3])
            # seeker_stats hashes through the same memo as the ``hash``
            # span, and before it: its lookups and misses are counted here
            sp.set("hash_values", ex.n_hashed - stats0[1])
            sp.set("hash_misses", ex.n_hash_misses - stats0[2])
    with rec.span("lower", plans=len(plans)):
        progs = [_compile_plan(p, optimize, e, cache, i)
                 for i, (p, e) in enumerate(zip(plans, eps))]

    tasks = [t for pr in progs for t in pr.tasks]
    # identical seekers (same frozen spec — e.g. a hot subtree shared
    # across a serve_many batch, where per-request cache lookups all happen
    # before any put) collapse onto one head task BEFORE hashing: same spec
    # means same hashes, capacity rung and scores, so dupes share the
    # head's batch row and pay no host work
    heads: dict = {}
    for t in tasks:
        t.head = heads.setdefault(t.spec, t)
    groups: dict[tuple, list] = {}
    for h in heads.values():
        h.group_key = _group_key(h.spec)
        groups.setdefault(h.group_key, []).append(h)
    walks = _hash_tasks(ex, groups)
    group_out: dict[tuple, tuple] = {}
    launch_seconds: dict[tuple, float] = {}
    failed_shards: set = set()
    for key in sorted(groups):
        kind_name = "/".join(str(p) for p in key)
        # a launch that bumped TRACE_COUNTS paid a jit trace+compile:
        # exec.compiles and the span's ``compiled`` show which step did
        tr0 = sum(seek.TRACE_COUNTS.values())
        t0 = time.perf_counter()
        with rec.span("probe:" + kind_name, seekers=len(groups[key]),
                      slots=_slots(walks[key])) as sp:
            group_out[key] = _launch_group(ex, key, groups[key], walks[key],
                                           failed=failed_shards)
        launch_seconds[key] = time.perf_counter() - t0
        if sum(seek.TRACE_COUNTS.values()) > tr0:
            sp.set("compiled", True)
            mreg.counter("exec.compiles").inc()
    group_plans: dict[tuple, set] = {}
    for t in tasks:                    # dupes adopt their head's placement
        t.group_key = t.head.group_key
        t.row = t.head.row
        group_plans.setdefault(t.group_key, set()).add(t.plan_idx)

    out = []
    for pr, plan in zip(progs, plans):
        plan_keys = sorted({t.group_key for t in pr.tasks})
        key_idx = {k: i for i, k in enumerate(plan_keys)}
        for t in pr.tasks:
            ins = pr.instrs[t.instr_idx]
            pr.instrs[t.instr_idx] = ("seeker", key_idx[t.group_key],
                                      ins[2], ins[3], ins[4])
        rows = np.array([t.row for t in pr.tasks], np.int32)
        gs = tuple(group_out[k][0] for k in plan_keys)
        if pr.cached:
            cs = jnp.stack([c.result.scores for c in pr.cached])
            cm = jnp.stack([c.result.mask for c in pr.cached])
        else:
            cs, cm = _empty_cached(ex.n_tables)
        # the DAG program is the cross-shard merge + the whole combiner tree
        tr0 = sum(seek.TRACE_COUNTS.values())
        t0 = time.perf_counter()
        with rec.span("merge", instrs=len(pr.instrs)) as sp:
            regs = _run_dag(gs, rows, cs, cm, prog=tuple(pr.instrs))
        dag_s = time.perf_counter() - t0
        if sum(seek.TRACE_COUNTS.values()) > tr0:
            sp.set("compiled", True)
            mreg.counter("exec.compiles").inc()

        info = ExecInfo(optimized=optimize)
        info.order = pr.order
        info.cached_nodes = pr.cached_names
        info.seeker_runs = len(pr.tasks)
        # every plan in the batch shares the group launches, so a dropped
        # shard degrades every response formed from them
        info.failed_shards = sorted(failed_shards)
        # one launch per seeker group + the DAG program; groups == kinds
        # unless same-kind seekers differ in static shape args (MC n_cols,
        # C h/sampling), each of which is its own device program
        info.launches = len(plan_keys) + 1
        info.node_seconds["fused:dag"] = dag_s
        for key in plan_keys:
            # a serve_many group launch is shared across plans; attribute an
            # equal share so per-request node_seconds stay additive (+= so
            # two same-kind groups, e.g. MC n_cols=2 and n_cols=3, don't
            # overwrite each other)
            name = "fused:" + "/".join(str(p) for p in key)
            info.node_seconds[name] = info.node_seconds.get(name, 0.0) + \
                launch_seconds[key] / len(group_plans[key])
        info.overflow_parts.extend(c.overflow for c in pr.cached)
        for key in plan_keys:
            rows = [t.row for t in pr.tasks if t.group_key == key]
            info.overflow_parts.append(OverflowSlice(group_out[key][1],
                                                     rows))
        if cache is not None:
            for ckey, reg, task in pr.cache_puts:
                cache.put_seeker(ckey, ResultSet(scores=regs[reg][0],
                                                 mask=regs[reg][1]),
                                 OverflowSlice(group_out[task.group_key][1],
                                               [task.row]),
                                 ex.n_tables)
        out.append((ResultSet(scores=regs[pr.out_reg][0],
                              mask=regs[pr.out_reg][1]), info))
    mreg.counter("exec.plans").inc(len(out))
    # physical device programs this call: one per group + one DAG per plan
    # (per-plan ExecInfo.launches attributes shared group launches to every
    # consumer, so summing those would overcount)
    mreg.counter("exec.launches").inc(len(groups) + len(progs))
    mreg.counter("exec.seeker_runs").inc(len(tasks))
    return out
