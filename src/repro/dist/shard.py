"""Sharded lakes: the SegmentStore partitioned across a device mesh along
the table axis, with fused per-shard probes and a single cross-shard merge.

Layout.  A ``ShardedStore`` is a coordinator over ``n_shards`` ordinary
per-shard ``SegmentStore``s, each pinned to its own mesh device and each
holding a *subset of whole tables* under the store's global geometry
(table-slot capacity, row stride, padded max-cols are imposed identically on
every shard, and table ids are global).  Because a table's postings live
wholly inside exactly one segment — the LiveLake invariant — table-axis
partitioning makes **every** seeker fully shard-local: SC/KW distinct
counts, MC superkey validation and the correlation row-join all group by
table, so a shard computes exact scores for its own tables and literal
zeros everywhere else.  The only cross-shard operation left is summing the
per-shard ``[n_seekers, n_tables]`` score matrices — exact in f32 (one
nonzero contributor per slot) and fused into the single whole-DAG program
(core/fused.py), so a whole plan still costs ``~n_kinds + 1`` logical
launches and results are bit-identical to a 1-shard run on the same data
(as long as no probe window overflows; parity tests assert overflow == 0).

Mutations stay shard-local: ``add_table`` allocates a global id at the
coordinator and routes the new L0 delta to the least-loaded shard;
``drop_table`` tombstones in place on the owner.  Global geometry changes
(slot-capacity growth, row-stride widening, max-cols growth) are the one
coordinated path — they change the static shapes every shard's seekers
compile against, so they land on *every* shard and bump its epoch.  The
store's ``epoch`` is the tuple of shard epochs; it flows through the
ordinary ``index_epoch_key`` fingerprint, so the QueryCache can never serve
results staled by any shard's mutation.

``ShardedExecutor`` builds one ``MatchEngine`` per shard (arrays committed
to the shard's device via ``MatchEngine.from_store(device=...)``), rebuilds
only the shards whose epoch moved, and executes exclusively on the fused
path: ``core/fused.py`` dispatches each seeker group once per shard with
*per-shard* capacity windows (a shard only holds its own postings, so its
window is ~``1/n_shards`` of the global rung — the scale-out win) and sums
the staged score matrices on the merge device inside the DAG program.

Validated on CPU via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(tests/test_distributed.py); when ``n_shards`` exceeds the visible device
count, shards wrap onto devices round-robin so the MPMD layout (and its
bit-identity) is testable on a single device.

``dryrun_discovery()`` lowers the per-shard fused seeker programs over a
Gittables-scale shard on the production mesh — the blend-discovery dry-run
cell (launch/dryrun.py).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import seekers as seek
from repro.core.executor import Executor
from repro.core.fused import MAX_CHUNK, SETS_PER_SEEKER
from repro.core.index import _ceil_pow2, validate_row_stride
from repro.core.match import EngineConfig, MatchEngine
from repro.store.compact import (CompactionPolicy, compact_store,
                                 maybe_compact as _maybe_compact)
from repro.store.segments import SegmentStore


def shard_devices(n_shards: int) -> list:
    """One device per shard, wrapping round-robin when the host exposes
    fewer devices than shards (single-device test fallback: the MPMD
    layout, capacities and merge are identical, only the parallelism is
    lost)."""
    devs = jax.devices()
    return [devs[i % len(devs)] for i in range(n_shards)]


def make_shard_mesh(n_shards: int):
    """A 1-axis ``('shard',)`` jax.sharding mesh over the first ``n_shards``
    devices, or None when the host exposes fewer devices (round-robin
    fallback — no true mesh exists)."""
    devs = jax.devices()
    if n_shards > len(devs):
        return None
    return jax.sharding.Mesh(np.array(devs[:n_shards]), ("shard",))


class ShardedStore:
    """Coordinator over per-shard ``SegmentStore``s (see module docstring).

    Duck-types the executor/planner surface of a single ``SegmentStore``
    (``n_tables`` / ``max_cols`` / ``row_stride`` / ``host_counts`` /
    ``segments`` / ``epoch`` / ``shape`` / mutation API), so sessions,
    caches and cost models treat a sharded lake like any live store."""

    def __init__(self, lake=None, *, n_shards: int = 2, bucket_bits: int = 12,
                 seed: int = 0, with_quadrants: bool = True, devices=None):
        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        tables = list(lake.tables) if lake is not None else []
        n = len(tables)
        # global geometry, imposed identically on every shard
        max_rows = max([t.n_rows for t in tables], default=1)
        row_stride = _ceil_pow2(max(max_rows, 1))
        table_cap = _ceil_pow2(max(n + SegmentStore.MIN_HEADROOM, 16))
        max_cols = max([t.n_cols for t in tables], default=1)
        validate_row_stride(table_cap, row_stride, max_rows)
        self.n_shards = n_shards
        self.devices = list(devices) if devices is not None \
            else shard_devices(n_shards)
        self.mesh = make_shard_mesh(n_shards) if devices is None else None
        # round-robin initial placement: global id g -> shard g % n_shards
        # (matches enumerate order, so LiveLake's id bookkeeping is exact)
        self.shards = []
        for s in range(n_shards):
            entries = [(g, t) for g, t in enumerate(tables)
                       if g % n_shards == s]
            names = [t.name if g % n_shards == s else None
                     for g, t in enumerate(tables)]
            self.shards.append(SegmentStore(
                bucket_bits=bucket_bits, seed=seed,
                with_quadrants=with_quadrants, entries=entries,
                table_names=names, table_cap=table_cap,
                row_stride=row_stride, max_cols=max_cols))

    # -------------------------------------------------------------- geometry
    @property
    def n_tables(self) -> int:
        return self.shards[0].n_tables

    @property
    def n_slots(self) -> int:
        return max(s.n_slots for s in self.shards)

    @property
    def max_cols(self) -> int:
        return max(s.max_cols for s in self.shards)

    @property
    def row_stride(self) -> int:
        return self.shards[0].row_stride

    @property
    def bucket_bits(self) -> int:
        return self.shards[0].bucket_bits

    @property
    def n_postings(self) -> int:
        return sum(s.n_postings for s in self.shards)

    @property
    def n_stat_postings(self) -> int:
        return sum(s.n_stat_postings for s in self.shards)

    @property
    def epoch(self) -> tuple:
        """Global epoch vector: one counter per shard.  Hashable, compares
        by value — the QueryCache fingerprint and ``Executor.refresh`` use
        it exactly like the scalar epoch of a single store."""
        return tuple(s.epoch for s in self.shards)

    @property
    def segments(self) -> list:
        """All shards' segments (read-only concatenation: statistics and
        duck-type checks — mutations go through the shard owning a run)."""
        return [seg for s in self.shards for seg in s.segments]

    @property
    def alive(self) -> np.ndarray:
        out = self.shards[0].alive.copy()
        for s in self.shards[1:]:
            out |= s.alive
        return out

    @property
    def table_names(self) -> list:
        names = [None] * self.n_slots
        for s in self.shards:
            for i in range(s.n_slots):
                if s.alive[i] and s.table_names[i] is not None:
                    names[i] = s.table_names[i]
        return names

    @property
    def pending_dead(self) -> set:
        return set().union(*(s.pending_dead for s in self.shards))

    @property
    def quadrant(self):
        # cost_model only truth-tests this attribute (store duck type)
        return self.shards[0].quadrant

    @property
    def sketch_config(self):
        return self.shards[0].sketch_config

    def live_ids(self) -> list:
        return sorted(t for s in self.shards for t in s.live_ids())

    def storage_bytes(self) -> int:
        return sum(s.storage_bytes() for s in self.shards)

    # ------------------------------------------------------------ statistics
    def host_counts(self, q_hashes, live_only: bool = False,
                    per_shard: bool = False) -> np.ndarray:
        """Match counts per query hash.  ``per_shard=True`` returns the
        ``[n_shards, nq]`` matrix the fused dispatcher sizes per-shard probe
        windows from; the default sums it — identical to a 1-shard store's
        counts on the same data."""
        per = np.stack([s.host_counts(q_hashes, live_only=live_only)
                        for s in self.shards])
        return per if per_shard else per.sum(axis=0)

    def shape(self) -> dict:
        """Observable index shape (Session.explain): mesh layout plus
        per-shard segment/posting/tombstone counts."""
        tomb = sorted(str(s.table_names[t])
                      for s in self.shards for t in s.pending_dead)
        per = [{"shard": i, "device": str(d), "epoch": s.epoch,
                "segments": len(s.segments), "postings": s.n_postings,
                "live_tables": int(s.alive.sum()),
                "tombstones": len(s.pending_dead)}
               for i, (s, d) in enumerate(zip(self.shards, self.devices))]
        return {
            "mode": "sharded",
            "shards": self.n_shards,
            "mesh_shape": (self.n_shards,),
            "mesh_axes": ("shard",),
            "epoch": self.epoch,
            "segments": sum(len(s.segments) for s in self.shards),
            "postings": self.n_postings,
            "live_tables": int(self.alive.sum()),
            "tombstoned": tomb,
            "table_slots": self.n_tables,
            "row_stride": self.row_stride,
            "per_shard": per,
        }

    # ------------------------------------------------------------- mutations
    def resolve(self, ref) -> int:
        for s in self.shards:
            try:
                return s.resolve(ref)
            except KeyError:
                pass
        raise KeyError(f"no live table matching {ref!r}")

    def owner_of(self, ref) -> int:
        """Shard index owning a live table reference."""
        for i, s in enumerate(self.shards):
            try:
                s.resolve(ref)
                return i
            except KeyError:
                pass
        raise KeyError(f"no live table matching {ref!r}")

    def least_loaded(self) -> int:
        return min(range(self.n_shards),
                   key=lambda i: self.shards[i].n_postings)

    def _alloc_gid(self) -> int:
        # reuse a freed global id if any shard relinquished one; the new
        # owner may be a different shard — the old owner's slot is dead
        # everywhere, so ownership transfers cleanly
        for s in self.shards:
            if s.free_ids:
                return s.free_ids.pop()
        return self.n_slots

    def _sync_max_cols(self):
        """Propagate padded max-cols growth to every shard: it is a static
        seeker shape, so a grown shard and a stale shard must never serve
        the same query with different paddings."""
        mc = max(s._max_cols_real for s in self.shards)
        for s in self.shards:
            if s._max_cols_real != mc:
                before = s.max_cols
                s._max_cols_real = mc
                if s.max_cols != before:
                    s.bump_epoch()

    def add_table(self, table, name: str | None = None,
                  tid: int | None = None, shard: int | None = None) -> int:
        """Route one new table to the least-loaded shard under a
        coordinator-allocated global id.  Only that shard re-indexes (one L0
        delta); global geometry changes — stride widening, capacity growth,
        max-cols growth — are the exception and land on every shard.

        ``tid`` / ``shard`` pin the global id and destination shard — WAL
        replay (store/wal.py) uses both so a recovered lake reproduces the
        uninterrupted run's placement (and therefore its per-shard epochs,
        probe windows and future least-loaded routing) exactly."""
        name = table.name if name is None else name
        if table.n_rows > self.row_stride:
            for s in self.shards:
                s._widen_stride(table.n_rows)
                s.bump_epoch()
        if tid is None:
            gid = self._alloc_gid()
        else:
            gid = int(tid)
            for s in self.shards:
                if gid in s.free_ids:
                    s.free_ids.remove(gid)
        if gid >= self.n_tables:
            cap = self.n_tables
            while gid >= cap:
                cap *= 2
            for s in self.shards:
                s.grow_capacity(cap)      # bumps every shard's epoch
        dest = self.least_loaded() if shard is None else int(shard)
        self.shards[dest].add_table(table, name, tid=gid)
        self._sync_max_cols()
        return gid

    def drop_table(self, ref) -> int:
        """Tombstone on the owner shard (single-table L0 runs are removed
        outright, exactly like the single-store path)."""
        for s in self.shards:
            try:
                gid = s.resolve(ref)
            except KeyError:
                continue
            return s.drop_table(gid)
        raise KeyError(f"no live table matching {ref!r}")

    # ------------------------------------------------------------ compaction
    def maybe_compact(self, policy: CompactionPolicy | None = None) -> bool:
        ran = False
        for s in self.shards:
            ran |= _maybe_compact(s, policy)
        return ran

    def compact(self, policy: CompactionPolicy | None = None,
                full: bool = False, reclaim_ids: bool = False):
        if reclaim_ids:
            raise ValueError(
                "reclaim_ids is unsupported on a sharded lake: table ids "
                "are global across shards and results would be renumbered "
                "per shard")
        for s in self.shards:
            compact_store(s, policy, full=full)
        return None


class ShardedExecutor(Executor):
    """Executor over a ``ShardedStore``: one committed MatchEngine per shard,
    fused-path-only execution, per-shard epoch tracking (a shard-local
    mutation rebuilds exactly one engine)."""

    def __init__(self, store, m_cap_max: int = 1024, row_cap: int = 8,
                 backend: str = "sorted", interpret: bool = False,
                 bucket_width: int | None = None):
        if not hasattr(store, "shards"):
            raise TypeError("ShardedExecutor needs a ShardedStore; use "
                            "Executor for single-device lakes")
        self.n_shards = store.n_shards
        self.devices = list(store.devices)
        # the DAG program (and its cached-result inputs) live on the default
        # device, which is also shard 0's device — staged per-shard scores
        # meet the cache-fed vectors there with no extra hop
        self.merge_device = jax.devices()[0]
        self._shard_epochs = [None] * store.n_shards
        self.engines = [None] * store.n_shards
        super().__init__(store, m_cap_max=m_cap_max, row_cap=row_cap,
                         backend=backend, interpret=interpret,
                         bucket_width=bucket_width)

    def _build_engine(self):
        store = self.index
        if self.bucket_width is not None:
            raise ValueError(
                "bucket_width is not configurable on a live store: "
                "each segment sizes its own lossless bucket layout")
        for s, shard in enumerate(store.shards):
            if self._shard_epochs[s] != shard.epoch:
                self.engines[s] = MatchEngine.from_store(
                    shard, backend=self.backend, interpret=self.interpret,
                    device=self.devices[s])
                self._shard_epochs[s] = shard.epoch
        self.engine = self.engines[0]       # stats/back-compat surface
        self.dev = self.engine.dev
        self._engine_epoch = store.epoch
        self.n_tables = store.n_tables
        self.max_cols = store.max_cols

    def reset_shard(self, s: int):
        """Throw away shard ``s``'s MatchEngine and rebuild it from the
        store — the recovery lever for a failed shard probe (core/fused.py
        retries exactly once on the rebuilt engine before dropping the
        shard from the merge).  Returns the fresh engine."""
        self.engines[s] = None
        self._shard_epochs[s] = None
        self._build_engine()
        return self.engines[s]

    def run(self, plan, optimize: bool = True, cost_model=None,
            sync: bool = True, cache=None, fused: bool = True):
        # sharded plans execute on the fused path only: the per-shard
        # dispatch + merge epilogue IS the execution model (the unfused
        # node-at-a-time walk has no cross-shard merge)
        return super().run(plan, optimize=optimize, cost_model=cost_model,
                           sync=sync, cache=cache, fused=True)

    def run_seeker(self, spec, allowed=None, sync: bool = True):
        raise NotImplementedError(
            "single-seeker dispatch is not defined on a sharded lake; "
            "run a plan (fused path) instead")

    def _sketch_sources(self):
        # one pack per shard, committed to the shard's device like its
        # MatchEngine; table-axis partitioning makes the probe shard-local
        # (a shard's pack is all-zero outside its own tables) so the
        # cross-shard merge in sketch_probe is an exact elementwise sum
        return [(shard.sketch_map(), None, dev)
                for shard, dev in zip(self.index.shards, self.devices)]


# --------------------------------------------------------------------------
# the blend-discovery dry-run cell (lake scale, production mesh)
# --------------------------------------------------------------------------

GITTABLES_SCALE = dict(n_postings=1_400_000_000, n_numeric=350_000_000,
                       n_tables=1_500_000, max_cols=8, row_stride=1 << 8)


def dryrun_discovery(multi_pod: bool = False, nq: int = 1024,
                     chunk: int = MAX_CHUNK, n_tuples: int = 256,
                     n_cols: int = 2, row_cap: int = 8):
    """Lower + compile the per-shard fused seeker programs over a
    Gittables-scale shard (ShapeDtypeStructs, no allocation) sized for the
    production mesh.  Under table-axis MPMD sharding every device runs the
    same shard-local program on ``1/chips`` of the postings, so the
    per-shard lowering IS the per-device serving cost; the cross-shard
    merge is one dense ``[n_seekers, n_tables]`` sum fused into the DAG
    program (negligible next to the probes at this scale)."""
    from repro.launch import hlo_analysis
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=multi_pod)
    sc = GITTABLES_SCALE
    n_dev = mesh.size
    npad = _ceil_pow2(max((sc["n_postings"] + n_dev - 1) // n_dev, 1))
    nnum = _ceil_pow2(max((sc["n_numeric"] + n_dev - 1) // n_dev, 1))
    sds = jax.ShapeDtypeStruct
    dev = {"hash": sds((npad,), jnp.uint32),
           "table": sds((npad,), jnp.int32),
           "col": sds((npad,), jnp.int32),
           "row": sds((npad,), jnp.int32),
           "row_cells": sds((npad, sc["max_cols"]), jnp.uint32),
           "sk_lo": sds((npad,), jnp.uint32),
           "sk_hi": sds((npad,), jnp.uint32),
           "quadrant": sds((npad,), jnp.int8),
           "rank_conv": sds((npad,), jnp.int32),
           "rank_rand": sds((npad,), jnp.int32),
           "num_rowkey": sds((nnum,), jnp.int32),
           "num_table": sds((nnum,), jnp.int32),
           "num_col": sds((nnum,), jnp.int32),
           "num_quadrant": sds((nnum,), jnp.int8),
           "num_rank_conv": sds((nnum,), jnp.int32),
           "num_rank_rand": sds((nnum,), jnp.int32)}
    cfg = EngineConfig(backend="sorted", interpret=False, bucket_bits=12,
                       bucket_widths=(), seg_bounds=((0, npad, npad),),
                       num_bounds=((0, nnum, nnum),),
                       n_tables=sc["n_tables"], max_cols=sc["max_cols"],
                       row_stride=sc["row_stride"])
    eng = MatchEngine(dev, None, None, cfg)
    nsp = 4                        # one fused group of 4 batched seekers
    n_chunks = sds((), jnp.int32)  # the window's chunks: an operand
    holder_sets = sds((SETS_PER_SEEKER * nsp, nsp), jnp.bool_)
    fns = {
        "sc": (seek.sc_seeker_seg,
               (eng, sds((nq,), jnp.uint32), sds((nq,), jnp.bool_),
                sds((nq,), jnp.int32), holder_sets, n_chunks),
               dict(chunk=chunk, n_tables=sc["n_tables"],
                    max_cols=sc["max_cols"])),
        "kw": (seek.kw_seeker_seg,
               (eng, sds((nq,), jnp.uint32), sds((nq,), jnp.bool_),
                sds((nq,), jnp.int32), holder_sets, n_chunks),
               dict(chunk=chunk, n_tables=sc["n_tables"])),
        "mc": (seek.mc_seeker_seg,
               (eng, sds((n_tuples, n_cols), jnp.uint32),
                sds((n_tuples,), jnp.int32), sds((n_tuples,), jnp.int32),
                n_chunks),
               dict(chunk=chunk, n_seekers=nsp,
                    n_tables=sc["n_tables"], n_cols=n_cols)),
        "c": (seek.c_seeker_seg,
              (eng, sds((nq,), jnp.uint32), sds((nq,), jnp.bool_),
               sds((nq,), jnp.int8), sds((nq,), jnp.int32), n_chunks),
              dict(chunk=chunk, row_cap=row_cap, n_seekers=nsp,
                   n_tables=sc["n_tables"], max_cols=sc["max_cols"],
                   h_sample=256, row_stride=sc["row_stride"])),
    }
    rec = {"arch": "blend-discovery",
           "mesh": "pod2x16x16" if multi_pod else "pod16x16",
           "chips": mesh.size, "scale": sc, "status": "ok", "seekers": {}}
    for name, (fn, args, kw) in fns.items():
        t0 = time.time()
        compiled = fn.lower(*args, **kw).compile()
        text = compiled.as_text()
        analysis = hlo_analysis.analyze(text)
        mem = compiled.memory_analysis()
        terms = hlo_analysis.roofline_terms(analysis, chips=mesh.size)
        rec["seekers"][name] = {
            "compile_s": round(time.time() - t0, 2),
            "memory_gb_per_device": round(
                (mem.argument_size_in_bytes + mem.temp_size_in_bytes +
                 mem.output_size_in_bytes) / 1e9, 3),
            "hlo": analysis, "roofline": terms,
        }
    return rec
