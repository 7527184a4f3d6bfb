"""Durability and fault-tolerance benchmark (BENCH_10).

Measures the cost and the guarantees of the WAL-backed durability tier
(store/wal.py, store/snapshot.py, repro/faults.py) plus the serving-side
graceful degradation (deadlines, shard-failure retry/degraded responses):

* ``wal_overhead`` — acknowledged-mutation throughput with the WAL off vs
  on (durable fdatasync-in-preallocated-extents, group commit, plain fsync,
  no-fsync), on the same add_table workload.  Acceptance: the best *fully
  durable* mode stays within ~15% of WAL-off — per-record fsync latency on
  a journaling fs is noisy, and group commit (``LiveLake.add_tables``: one
  barrier per batch, acks wait for it) is the standard way a WAL meets a
  throughput budget without giving up durability.
* ``recovery`` — crash-recovery wall time vs WAL length (snapshot load +
  replay of n in {8, 32, 128} logged mutations), and the recovered state's
  bit-identity to the uninterrupted run (ids AND scores, same epoch).
* ``fault_serving`` — a query sweep on a 4-shard lake with injected shard
  failures: single failures must be absorbed by the retry (bit-identical),
  double failures must degrade (correct surviving scores, ``degraded``
  flagged) — **zero wrong results**; plus the deadline path: requests whose
  budget passes while queued resolve to typed ``DeadlineExceeded``, never
  a late dispatch.
* ``replay_with_faults`` — the trace-driven loadgen with per-query
  deadlines and client retries against an admission-controlled server:
  offered == completed + shed + expired, with retry accounting.

    PYTHONPATH=src python benchmarks/fault_bench.py [--out PATH]
        [--mutations N]
"""
from __future__ import annotations

import argparse
import json
import platform
import shutil
import sys
import tempfile
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]
for p in (REPO_ROOT, REPO_ROOT / "src"):       # runnable as a plain script
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np

import blend
from repro import faults
from repro.core.lake import Table, synthetic_lake
from repro.errors import DeadlineExceeded
from repro.faults import FaultInjector
from repro.serve.engine import DiscoveryEngine
from repro.serve.loadgen import make_trace, replay
from repro.serve.server import DiscoveryServer
from repro.store.live import LiveLake
from repro.store.wal import WriteAheadLog


def mk_lake(seed=11, n_tables=24):
    return synthetic_lake(n_tables=n_tables, rows=16, cols=4, vocab=300,
                          seed=seed)


def extra_table(i, rows=120, vocab=300):
    rng = np.random.default_rng(9000 + i)
    return Table(f"bench_extra{i}",
                 [[f"tok_{int(x)}" for x in rng.integers(0, vocab, rows)],
                  [f"tok_{int(x)}" for x in rng.integers(0, vocab, rows)],
                  [float(x) for x in np.round(rng.normal(0, 5, rows), 3)]])


def query_pool(lake, n=6, k=24):
    out = []
    for i in range(n):
        t = lake.tables[i % len(lake.tables)]
        sc = blend.sc(list(t.columns[0][:8]), k=k)
        kw = blend.kw([t.columns[1][0], t.columns[1][2]], k=k)
        out.append(((sc & kw) | blend.kw(list(t.columns[0][:4]),
                                         k=k)).top(12))
    return out


# --------------------------------------------------------------------------
# 1. WAL overhead on acknowledged mutations
# --------------------------------------------------------------------------

def _mutation_rate(tmp: Path, n_ops: int, use_wal: bool,
                   fsync=True, preallocate=0, group=0) -> float:
    ll = LiveLake(mk_lake(),
                  wal=WriteAheadLog(tmp / "bench.wal", fsync=fsync,
                                    preallocate=preallocate)
                  if use_wal else None)
    tables = [extra_table(i) for i in range(n_ops)]
    t0 = time.perf_counter()
    if group:
        for i in range(0, n_ops, group):
            ll.add_tables(tables[i:i + group])
    else:
        for t in tables:
            ll.add_table(t)
    dt = time.perf_counter() - t0
    if ll.wal is not None:
        ll.wal.close()
    return n_ops / dt


#: the WAL's durable default for serving workloads: per-append fdatasync
#: inside preallocated extents (see store/wal.py ``preallocate=``)
PREALLOC = 1 << 20

MODES = {
    "wal_off": dict(use_wal=False, fsync=False),
    "wal_on_durable": dict(use_wal=True, fsync=True, preallocate=PREALLOC),
    "wal_on_grouped": dict(use_wal=True, fsync=True, preallocate=PREALLOC,
                           group=8),
    "wal_on_fsync_noprealloc": dict(use_wal=True, fsync=True),
    "wal_on_nofsync": dict(use_wal=True, fsync=False),
}


def wal_overhead(n_ops: int) -> dict:
    rates = {}
    for name, kw in MODES.items():
        tmp = Path(tempfile.mkdtemp(prefix="blend-walbench-"))
        try:
            # warmup + best-of-3: fsync latency on a journaling fs is noisy
            rs = [_mutation_rate(Path(tempfile.mkdtemp(dir=tmp)), n_ops,
                                 **kw) for _ in range(3)]
            rates[name] = max(rs)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    off = rates["wal_off"]
    out = {"ops": n_ops, "wal_off_ops_s": round(off, 1)}
    for name in list(MODES)[1:]:
        out[f"{name}_ops_s"] = round(rates[name], 1)
        out[f"{name}_overhead_pct"] = \
            round(100.0 * (1.0 - rates[name] / off), 2)
    return out


# --------------------------------------------------------------------------
# 2. recovery time vs WAL length
# --------------------------------------------------------------------------

def recovery_curve(lengths=(8, 32, 128)) -> dict:
    out = {}
    for n in lengths:
        tmp = Path(tempfile.mkdtemp(prefix="blend-recbench-"))
        try:
            sp, wp = str(tmp / "lake.snap"), str(tmp / "lake.wal")
            session = blend.connect(mk_lake(), live=True, wal=wp)
            session.snapshot(sp)
            for i in range(n):
                if i % 5 == 4:
                    session.drop_table(f"bench_extra{i - 1}")
                else:
                    session.add_table(extra_table(i))
            q = query_pool(mk_lake(), n=1)[0]
            res = session.query(q, fused=True)
            want = (tuple(res.ids), np.asarray(res.scores).copy(),
                    session.live.store.epoch)
            t0 = time.perf_counter()
            rec = blend.recover(sp, wal=wp)
            recover_s = time.perf_counter() - t0
            got = rec.query(q, fused=True)
            identical = (tuple(got.ids) == want[0]
                         and np.array_equal(np.asarray(got.scores), want[1])
                         and rec.live.store.epoch == want[2])
            out[str(n)] = {
                "records_replayed": n,
                "recover_s": round(recover_s, 4),
                "recover_ms_per_record": round(1e3 * recover_s / n, 3),
                "bit_identical": bool(identical),
            }
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    return out


# --------------------------------------------------------------------------
# 3. serving under injected faults: degradation + deadlines
# --------------------------------------------------------------------------

def fault_serving() -> dict:
    lake = mk_lake(n_tables=20)
    engine = DiscoveryEngine(lake, shards=4, live=True)
    pool = query_pool(lake, n=6)
    clean = [engine.serve(q) for q in pool]      # also warms the jit cache

    wrong = degraded_flagged = absorbed = 0
    n_sweep = 30
    for i in range(n_sweep):
        q = pool[i % len(pool)]
        ref = clean[i % len(pool)]
        if i % 3 == 2:      # double failure: shard dropped, degraded
            inj = FaultInjector(fail={f"shard.probe.{i % 4}": 2})
        elif i % 3 == 1:    # single failure: absorbed by the retry
            inj = FaultInjector(fail={f"shard.probe.{i % 4}": 1})
        else:
            inj = FaultInjector()
        with faults.inject(inj):
            resp = engine.serve(q)
        if resp.degraded:
            degraded_flagged += 1
            store = engine.session.live.store
            dead = set(resp.failed_shards)
            ref_sc = np.asarray(ref.scores)
            got_sc = np.asarray(resp.scores)
            for tid in resp.table_ids:
                # a degraded answer may only omit, never corrupt
                if store.owner_of(tid) in dead or (
                        tid in ref.table_ids
                        and got_sc[tid] != ref_sc[tid]):
                    wrong += 1
        else:
            if list(resp.table_ids) != list(ref.table_ids) or \
                    not np.array_equal(np.asarray(resp.scores),
                                       np.asarray(ref.scores)):
                wrong += 1
            elif i % 3 == 1:
                absorbed += 1

    # deadline path: a parked dispatcher makes the budgets pass while
    # queued — every future must resolve to a typed DeadlineExceeded
    server = DiscoveryServer(engine, max_batch=8, start=False)
    futs = [server.submit(q, deadline_s=0.02) for q in pool]
    time.sleep(0.06)
    with server:
        answers = [f.result(timeout=30.0) for f in futs]
        late_dispatches = sum(
            0 if isinstance(a, DeadlineExceeded) else 1 for a in answers)
        post = server.serve(pool[0])             # server healthy afterwards
        stats = server.stats()
    return {
        "sweep_queries": n_sweep,
        "single_failures_absorbed": absorbed,
        "degraded_flagged": degraded_flagged,
        "wrong_results": wrong,
        "deadline": {
            "submitted": len(futs),
            "deadline_exceeded": stats["deadline_exceeded"],
            "late_dispatches": late_dispatches,
            "healthy_after": not isinstance(post, DeadlineExceeded),
        },
    }


# --------------------------------------------------------------------------
# 4. trace replay with deadlines + client retries
# --------------------------------------------------------------------------

def replay_with_faults() -> dict:
    lake = mk_lake(seed=17, n_tables=16)
    engine = DiscoveryEngine(lake, live=True)
    for q in query_pool(lake, n=4):
        engine.serve(q)                           # warm the jit cache
    trace = make_trace(lake, seed=7, duration_s=1.0, rate_rps=120.0,
                       n_distinct=6, k=16, p_mutation=0.05)
    server = DiscoveryServer(engine, max_batch=8, rate=60.0, burst=8.0)
    with server:
        rep = replay(server, trace, deadline_s=0.5, max_retries=3,
                     base_backoff_s=0.005, max_backoff_s=0.05)
    d = rep.as_dict()
    d["conservation"] = rep.offered == rep.completed + rep.shed + rep.expired
    return d


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(REPO_ROOT / "BENCH_10.json"))
    ap.add_argument("--mutations", type=int, default=40,
                    help="ops per WAL-overhead measurement")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    wal = wal_overhead(args.mutations)
    rec = recovery_curve()
    srv = fault_serving()
    rep = replay_with_faults()

    payload = {
        "bench": "BENCH_10",
        "platform": platform.platform(),
        "python": platform.python_version(),
        "wal_overhead": wal,
        "recovery": rec,
        "fault_serving": srv,
        "replay_with_faults": rep,
        "acceptance": {
            "wal_overhead_within_15pct":
                min(wal["wal_on_durable_overhead_pct"],
                    wal["wal_on_grouped_overhead_pct"]) <= 15.0,
            "recovery_bit_identical":
                all(v["bit_identical"] for v in rec.values()),
            "zero_wrong_results": srv["wrong_results"] == 0,
            "degraded_all_flagged": srv["degraded_flagged"] == 10,
            "deadlines_enforced":
                srv["deadline"]["late_dispatches"] == 0
                and srv["deadline"]["deadline_exceeded"]
                >= srv["deadline"]["submitted"],
            "replay_conservation": rep["conservation"],
        },
    }
    out = Path(args.out)
    out.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    print(f"wrote {out}")
    for k, v in payload["acceptance"].items():
        print(f"  {k}: {v}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
