"""Smoke run of the served discovery path on a TPU.

Drives ``DiscoveryServer`` -> ``Session`` -> fused DAG over a live segment
store once, at a lake of ~5.4M postings, and checks every answer:

* one chip (default): requests covering SC, KW, MC, C and a combiner DAG
  (``&``, ``|``, ``-``) plus one ``add_table`` barrier, on the ``sorted``
  backend against the brute-force oracle (``tests/oracle.py``, exact ids
  and scores), then the same requests on the ``bucket`` backend (its Pallas
  kernels compiled for the chip), bit-identical to ``sorted``;
* ``--chips 4``: the same lake sharded over four chips
  (``connect(lake, shards=4, live=True)``), bit-identical to a one-chip
  session in the same process.  Only that phase runs.

Every response must be a real, undegraded ``DiscoveryResponse`` with zero
probe-window overflow.  Earlier lines report host-clock set-up and request
timings (each ends in a device fetch; they are not device metrics), traces
during the warm requests and device peak memory.  The last line is one JSON
object naming the device; it is printed only when every check passed.  With
no TPU the script exits non-zero before it builds anything.

    python chip_smoke.py [--seed N] [--chips 1|4]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# GitTables-shaped rows (3 categorical + 1 numeric column, <= 60 rows) at
# ~5.4M postings.  A GitTables quarter per chip (dist/shard.py
# GITTABLES_SCALE) is ~350M postings; the index build hashes every cell in
# host Python, so that scale waits for a vectorised build.
N_TABLES, ROWS, COLS, VOCAB = 30_000, 60, 4, 50_000
K = 10


class SmokeFailure(AssertionError):
    pass


def require(cond, what: str):
    if not cond:
        raise SmokeFailure(what)


def log(msg: str):
    print(f"chip_smoke: {msg}", flush=True)


def make_requests(lake, rng):
    """Named BlendQL requests whose values are drawn from the lake, so every
    seeker kind has real matches (plus one value that is never in it)."""
    import blend

    def table():
        return lake.tables[int(rng.integers(0, lake.n_tables))]

    def rows(t, k):
        return [int(r) for r in rng.choice(t.n_rows, k, replace=False)]

    a, b, c, d, e = (table() for _ in range(5))
    ra, rb, rc, rd, re_ = (rows(a, 8), rows(b, 6), rows(c, 6), rows(d, 12),
                           rows(e, 8))
    sc = blend.sc([a.columns[0][r] for r in ra] + ["never_in_lake"], k=K)
    kw = blend.kw([b.columns[i % 3][r] for i, r in enumerate(rb)], k=K)
    mc = blend.mc([(c.columns[0][r], c.columns[1][r]) for r in rc], k=K)
    corr = blend.corr([d.columns[0][r] for r in rd],
                      [float(x) for x in rng.normal(0, 1, len(rd)).round(3)],
                      k=K)
    dag = ((blend.sc([e.columns[0][r] for r in re_], k=K)
            & blend.kw([e.columns[1][r] for r in re_], k=K))
           | blend.mc([(e.columns[0][r], e.columns[2][r]) for r in re_[:4]],
                      k=K)
           - blend.kw([a.columns[1][r] for r in ra[:2]], k=K)).top(K)
    return {"sc": sc, "kw": kw, "mc": mc, "c": corr, "dag": dag}


def added_table(lake, rng):
    """The table the ``add_table`` barrier inserts: a column of tokens found
    nowhere else, so the query after the barrier can only rank it first."""
    from repro.core.lake import Table

    n = 40
    src = lake.tables[int(rng.integers(0, lake.n_tables))]
    return Table("smoke_added", [
        [f"smoke_new_{i}" for i in range(n)],
        [src.columns[1][i % src.n_rows] for i in range(n)],
        [src.columns[2][i % src.n_rows] for i in range(n)],
        [float(x) for x in rng.normal(0, 10, n).round(3)]])


def query_values(expr) -> set:
    """Every cell value a request probes for."""
    from repro.query.logical import Seek, walk

    out = set()
    for node in walk(expr):
        if isinstance(node, Seek):
            for v in node.values:
                out.update(v if isinstance(v, tuple) else (v,))
    return out


def oracle_lake(tables, values: set):
    """The lake the oracle scores against: tables holding none of the probed
    values are replaced by empty tables of the same name.  Every seeker
    scores such a table 0, so the oracle's scores are those of the whole
    lake, at the cost of a scan over only the tables that can match."""
    from oracle import canon
    from repro.core.lake import DataLake, Table

    want = {canon(v) for v in values}
    return DataLake([t if any(canon(v) in want for col in t.columns
                              for v in col) else Table(t.name, [])
                     for t in tables])


def expected(session, lake, expr):
    from oracle import oracle_ids, oracle_run

    scores, mask = oracle_run(lake, session.compile(expr).plan)
    return oracle_ids(scores, mask), scores


def check_response(name, resp, n_tables, want=None):
    """A real, complete answer; with ``want`` it also equals the oracle's
    (ids, scores), scores exactly, and zero on every empty table slot."""
    import numpy as np

    from repro.serve.engine import DiscoveryResponse

    require(isinstance(resp, DiscoveryResponse),
            f"{name}: not a DiscoveryResponse: {resp!r}")
    require(not resp.degraded and not resp.failed_shards,
            f"{name}: degraded, failed shards {resp.failed_shards}")
    require(resp.overflow == 0, f"{name}: overflow {resp.overflow}")
    require(len(resp.table_ids) > 0, f"{name}: no table matched")
    if want is not None:
        ids, scores = want
        got = np.asarray(resp.scores)
        require(resp.table_ids == ids,
                f"{name}: ids {resp.table_ids} != oracle {ids}")
        require(np.array_equal(got[:n_tables], scores)
                and not got[n_tables:].any(),
                f"{name}: scores differ from the oracle")


def same_answer(name, a, b):
    import numpy as np

    require(a.table_ids == b.table_ids,
            f"{name}: ids {a.table_ids} != {b.table_ids}")
    require(np.array_equal(np.asarray(a.scores), np.asarray(b.scores)),
            f"{name}: scores are not bit-identical")


def serve(server, expr):
    return server.submit(expr).result(timeout=900)


def peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", -1))


def build_lake(seed: int):
    from repro.core.lake import synthetic_lake

    t0 = time.perf_counter()
    lake = synthetic_lake(n_tables=N_TABLES, rows=ROWS, cols=COLS,
                          vocab=VOCAB, seed=seed)
    cells = sum(t.n_rows * t.n_cols for t in lake.tables)
    log(f"lake: {lake.n_tables} tables x <= {ROWS} rows x {COLS} cols "
        f"(3 categorical + 1 numeric), vocab {VOCAB}, seed {seed}: {cells} "
        f"postings, generated in {time.perf_counter() - t0:.3f} s")
    log("cut: a GitTables quarter per chip is ~350M postings; this lake is "
        "~5.4M because the index build hashes every cell in host Python")
    return lake


def open_server(lake, **opts):
    """Host build, device upload, and a server over the live session."""
    import jax

    import blend
    from repro.serve.engine import DiscoveryEngine
    from repro.serve.server import DiscoveryServer
    from repro.store.live import LiveLake

    t0 = time.perf_counter()
    live = LiveLake(lake) if not opts.get("shards") else lake
    t1 = time.perf_counter()
    session = blend.connect(live, live=True, **opts)
    jax.block_until_ready(
        [e.dev for e in getattr(session.executor, "engines",
                                [session.executor.engine])])
    t2 = time.perf_counter()
    # optimize=False: plans run in the order the oracle evaluates them
    server = DiscoveryServer(DiscoveryEngine(None, session=session),
                             optimize=False, interactive_window_s=0.0)
    return server, t1 - t0, t2 - t1


def run_requests(label, server, requests):
    """Serve every request twice (cold, then warm) one at a time, so each
    warm request runs the very programs its cold run compiled."""
    from repro.core.seekers import TRACE_COUNTS

    cold, warm = {}, {}
    for rnd, out in (("first", cold), ("warm", warm)):
        traces = sum(TRACE_COUNTS.values())
        for name, expr in requests.items():
            t0 = time.perf_counter()
            out[name] = (serve(server, expr), time.perf_counter() - t0)
        new = sum(TRACE_COUNTS.values()) - traces
        log(f"{label}: {rnd}-request seconds " + " ".join(
            f"{n}={s:.6f}" for n, (_, s) in out.items())
            + f"; new traces {new}")
    require(new == 0, f"{label}: {new} new traces across warm requests")
    for name in requests:
        same_answer(f"{label}/{name} warm", warm[name][0], cold[name][0])
    return {n: r for n, (r, _) in cold.items()}


def one_chip(seed: int, lake=None):
    import jax
    import numpy as np

    import blend

    rng = np.random.default_rng(seed)
    lake = lake if lake is not None else build_lake(seed)
    requests = make_requests(lake, rng)
    new_table = added_table(lake, rng)
    after = {"added": blend.sc([f"smoke_new_{i}" for i in range(10)], k=K),
             "dag": requests["dag"]}
    values = set().union(*map(query_values,
                              [*requests.values(), *after.values()]))
    t0 = time.perf_counter()
    before_lake = oracle_lake(lake.tables, values)
    after_lake = oracle_lake([*lake.tables, new_table], values)
    log(f"oracle: {sum(bool(t.columns) for t in before_lake.tables)} "
        f"candidate tables of {lake.n_tables} ({time.perf_counter() - t0:.3f}"
        f" s scan)")
    answers = {}
    for backend in ("sorted", "bucket"):
        server, build_s, upload_s = open_server(lake, backend=backend)
        try:
            idx = server.session.index
            log(f"{backend}: host build {build_s:.6f} s, upload "
                f"{upload_s:.6f} s, {idx.n_postings} postings in "
                f"{len(idx.segments)} segment(s)")
            got = run_requests(backend, server, requests)
            tid = server.add_table(new_table).result(timeout=900)
            require(tid == lake.n_tables,
                    f"{backend}: add_table gave id {tid}")
            for name, expr in after.items():
                got[f"{name}+1"] = serve(server, expr)
            require(got["added+1"].table_ids[0] == tid,
                    f"{backend}: the added table is not ranked first")
        finally:
            server.stop()
        for name, resp in got.items():
            barrier = name.endswith("+1")
            olake = after_lake if barrier else before_lake
            if backend == "sorted":
                want = expected(server.session, olake,
                                (after[name[:-2]] if barrier
                                 else requests[name]))
                check_response(f"{backend}/{name}", resp, olake.n_tables,
                               want)
            else:
                check_response(f"{backend}/{name}", resp, olake.n_tables)
                same_answer(f"bucket/{name} vs sorted", resp,
                            answers["sorted"][name])
        answers[backend] = got
        log(f"{backend}: " + " ".join(
            f"{n}:{len(r.table_ids)}ids" for n, r in got.items())
            + (" oracle-exact" if backend == "sorted"
               else " bit-identical to sorted")
            + ", overflow 0, none degraded or shed")
    log(f"device peak_bytes_in_use {peak_bytes(jax.devices()[0])}")


def four_chips(seed: int, lake=None):
    import jax
    import numpy as np

    devices = jax.devices()
    require(len(devices) >= 4, f"--chips 4 needs 4 devices, got {devices}")
    rng = np.random.default_rng(seed)
    lake = lake if lake is not None else build_lake(seed)
    requests = make_requests(lake, rng)
    sharded, build_s, upload_s = open_server(lake, shards=4)
    single, build1_s, upload1_s = open_server(lake)
    try:
        store = sharded.session.index
        require(len({d.id for d in store.devices}) == 4
                and {d.platform for d in store.devices}
                == {devices[0].platform}
                and store.mesh is not None,
                f"shards are not on 4 distinct devices: {store.devices}")
        log(f"sharded: build + upload {build_s + upload_s:.6f} s on "
            f"{[str(d) for d in store.devices]}; postings per shard "
            f"{[s.n_postings for s in store.shards]}")
        log(f"one-chip: host build {build1_s:.6f} s, upload "
            f"{upload1_s:.6f} s")
        got4 = run_requests("sharded", sharded, requests)
        got1 = run_requests("one-chip", single, requests)
    finally:
        sharded.stop()
        single.stop()
    for name in requests:
        check_response(f"sharded/{name}", got4[name], lake.n_tables)
        same_answer(f"sharded/{name} vs one-chip", got4[name], got1[name])
    log("sharded: " + " ".join(f"{n}:{len(r.table_ids)}ids"
                               for n, r in got4.items())
        + " bit-identical to one chip, overflow 0, none degraded or shed")
    log("peak_bytes_in_use per device "
        + str([peak_bytes(d) for d in devices[:4]]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices}",
              file=sys.stderr)
        return 2
    for p in (ROOT / "src", ROOT / "tests"):
        sys.path.insert(0, str(p))
    from repro.compile_cache import enable_compile_cache

    log(f"compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args.seed)
    log(f"done in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
