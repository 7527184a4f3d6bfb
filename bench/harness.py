"""One benchmark run of one cell, end to end.

``bench/run.py`` calls :func:`main`.  A run:

1. generates the cell's lake from ``--seed`` (``bench/lakegen.py``);
2. builds it with ``blend.connect(lake, live=True)`` on the default
   ``sorted`` backend -- the host build and the upload users pay;
3. wraps the session in a ``DiscoveryServer`` with its default policy;
4. warms up on traffic of the cell's own shape drawn from a separate seed
   stream: first a walk over batch sizes and query sizes
   (:func:`ladder_walk`), then rounds of the cell's traffic until
   ``QUIET_ROUNDS`` rounds in a row compile nothing new;
5. drives the measured window through ``DiscoveryServer.submit``; a window
   in which a program was traced, compiled or loaded is taken as warm-up
   and driven again, while the run has time (``checks`` reports
   ``compiles_in_window`` of the window measured, limit 0);
6. reads the device's peak memory, stops the server, and checks every
   answer of the window against ``bench/reference.py``;
7. prints the result line.

Everything cell-specific is data: the workload entry in ``BENCHMARK.json``
names a configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); ``bench/cells/<workload>.json`` holds the
cell's offered rate or client count and its latency limit; each per-layer
metric is read by ``bench/metrics/<metric>.py``.  ``--spec`` reads the
workload from another file of the same form: ``bench/held_out.json`` holds
cells kept out of the benchmark, with the reason in ``PERF.md``.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from bench import lakegen, reference, tracereduce
from bench.traffic import loadgen

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CELL_DIR = BENCH / "cells"
METRIC_DIR = BENCH / "metrics"
#: seconds a request may still take after the window closes
LATE_S = 60.0
#: warm-up stops after this many rounds in a row compile nothing new
QUIET_ROUNDS = 3
MAX_WARM_ROUNDS = 16
#: seconds of the cell's own traffic in each warm-up round
WARM_TRAFFIC_S = 3.0
#: the ladder walk sends batches at this many places along each ordering
WALK_PLACES = 4
#: largest batch the server forms (``DiscoveryServer``'s default policy)
MAX_BATCH = 16
#: a window that compiled is driven again only while the run can still
#: end this many seconds after its start (a run has 360)
RUN_LIMIT_S = 300.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str):
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ cells
def load_cell(name: str, root: Path = ROOT,
              spec_file: str = "BENCHMARK.json") -> dict:
    """The workload entry of ``BENCHMARK.json`` (or ``spec_file``, relative
    to ``root``) merged with its cell file, its configuration and its
    traffic mix."""
    with open(root / spec_file) as f:
        spec = json.load(f)
    entry = [w for w in spec["workloads"] if w["name"] == name]
    if not entry:
        raise SystemExit(f"no workload {name!r} in {spec_file}")
    entry = entry[0]
    with open(CELL_DIR / f"{name}.json") as f:
        params = json.load(f)
    config = [c for c in spec["configs"] if c["name"] == entry["config"]][0]
    return {"name": name, "chips": entry["chips"],
            "config": lakegen.load_config(root / config["file"]),
            "traffic": loadgen.load_traffic(entry["traffic"]),
            "params": params,
            "end_to_end": [m for m in spec["end_to_end"]
                           if name in m.get("workloads", [name])],
            "per_layer": [m for m in spec["per_layer"]
                          if name in m.get("workloads", [name])]}


def require_chips(n: int):
    # the TPU runtime logs under /tmp unless told otherwise; keep a run's
    # files in its own TMPDIR
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        raise NoChip(f"needs {n} TPU chip(s), JAX found {devices}")
    return devices


def load_metric(name: str, metric_dir: Path = METRIC_DIR):
    """The reader module ``<metric_dir>/<name>.py``: ``read(ctx)`` returns
    the metric's value, or None where the run has nothing to read."""
    path = metric_dir / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------- compiles
class CompileCounter:
    """Counts persistent-cache hits and misses of JAX compiles, and the
    seeker / DAG programs the program traced (``TRACE_COUNTS``)."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._event)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @staticmethod
    def traces() -> int:
        from repro.core.seekers import TRACE_COUNTS

        return sum(TRACE_COUNTS.values())

    def snapshot(self):
        return (self.traces(), self.hits, self.misses)


# ----------------------------------------------------------------- checks
def check(records, answer_of, n_tables: int):
    """Judge every answer of the window: returns per-record ok flags and
    the counts of each kind of failure.  A served answer counts as right
    only if its ranked ids equal the reference's and its whole float32
    score vector equals the reference's bit for bit."""
    from repro.serve.engine import DiscoveryResponse

    counts = dict(wrong_answers=0, overflowed=0, degraded=0,
                  shed_or_expired=0, errors=0, unanswered=0)
    ok = []
    for rec in records:
        good = False
        fut = rec.fut
        if not fut.done():
            counts["unanswered"] += 1
        elif fut.cancelled() or fut.exception() is not None:
            counts["errors"] += 1
        else:
            resp = fut.result()
            if not isinstance(resp, DiscoveryResponse):
                counts["shed_or_expired"] += 1
            elif resp.degraded or resp.failed_shards:
                counts["degraded"] += 1
            elif resp.overflow:
                counts["overflowed"] += 1
            else:
                ids, scores = answer_of(rec.key)
                got = np.asarray(resp.scores)
                if resp.table_ids == ids and \
                        np.array_equal(got[:n_tables], scores) and \
                        not got[n_tables:].any():
                    good = True
                else:
                    counts["wrong_answers"] += 1
        ok.append(good)
    return ok, counts


def percentile(values, q: float) -> float:
    """Nearest-rank percentile; ``inf`` counts as the slowest value."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return math.inf
    return float(v[max(int(math.ceil(q / 100.0 * len(v))) - 1, 0)])


# ------------------------------------------------------------------ setup
def build_server(cfg, seed: int, trace: bool):
    import jax

    import blend
    from repro.serve.engine import DiscoveryEngine
    from repro.serve.server import DiscoveryServer
    from repro.store.live import LiveLake

    t0 = time.perf_counter()
    lake = lakegen.generate(cfg, seed)
    tables = lake.tables()
    t1 = time.perf_counter()
    live = LiveLake(tables)
    t2 = time.perf_counter()
    session = blend.connect(live, live=True)
    jax.block_until_ready(session.executor.engine.dev)
    t3 = time.perf_counter()
    log(f"lake: {lake.n_tables} tables, {lake.n_postings} postings, "
        f"generated in {t1 - t0:.6f} s; host build {t2 - t1:.6f} s; "
        f"upload {t3 - t2:.6f} s")
    server = DiscoveryServer(DiscoveryEngine(None, session=session),
                             trace=trace)
    return lake, server


def query_features(queries, lake) -> np.ndarray:
    """Per query tree: distinct values (MC: tuples) over its seekers, the
    postings of its hottest value, and its seekers -- counted from the
    generated lake, not read from the program."""
    counts = np.bincount(lake.cat, minlength=lake.vocab)
    out = np.zeros((len(queries), 3), np.int64)
    for i, q in enumerate(queries):
        for s in reference.seekers(q):
            uniq = set(s[2])
            toks = [v for t in uniq for v in t] if s[1] == "MC" else \
                list(uniq)
            out[i, 0] += len(uniq)
            out[i, 1] = max(out[i, 1], int(counts[toks].max(initial=0)))
            out[i, 2] += 1
    return out


def ladder_walk(server, requests, features, rng) -> int:
    """Batches of every size the server forms, each made of neighbours
    along one ordering of the warm-up queries (by distinct values, by the
    hottest value's postings, by seekers) at ``WALK_PLACES`` places from
    the smallest to the largest: the batch widths, capacity rungs and
    seeker counts the window can meet.  Returns the requests sent."""
    n = len(requests)
    recs: list = []
    tie = rng.permutation(n)
    for k in range(features.shape[1]):
        order = tie[np.argsort(features[tie, k], kind="stable")]
        for b in range(1, min(MAX_BATCH, n) + 1):
            for at in np.unique(np.linspace(0, n - b, WALK_PLACES)
                                .astype(np.int64)):
                for i in order[at:at + b]:
                    loadgen.submit(server, requests[i], int(i), 0.0, recs)
                loadgen.wait_all(recs, time.perf_counter() + LATE_S)
    return len(recs)


def warm_up(drive_round, counter, walk=None, budget_s: float = 900.0):
    """``walk()`` once, then rounds of the cell's own traffic from the
    warm-up seed stream until ``QUIET_ROUNDS`` rounds in a row trace and
    compile nothing new."""
    t0 = time.perf_counter()
    first = counter.snapshot()
    sent = walk() if walk is not None else 0
    walked = counter.snapshot()
    quiet = rounds = 0
    while quiet < QUIET_ROUNDS and rounds < MAX_WARM_ROUNDS and \
            time.perf_counter() - t0 < budget_s:
        before = counter.snapshot()
        drive_round(rounds)
        rounds += 1
        quiet = quiet + 1 if counter.snapshot() == before else 0
    last = counter.snapshot()
    log(f"warm-up: walk of {sent} requests traced {walked[0] - first[0]} "
        f"programs; then {rounds} rounds traced {last[0] - walked[0]}; in "
        f"{time.perf_counter() - t0:.6f} s; {last[1] - first[1]} loaded "
        f"from the compile cache, {last[2] - first[2]} compiled"
        + ("" if quiet >= QUIET_ROUNDS else "; NOT QUIET"))


def open_warm_round(server, traffic, exprs, seed: int, rate: float):
    """One warm-up round of an open-loop cell: every batch size the server
    can form (1 to 16 requests sent at once), then ``WARM_TRAFFIC_S`` of the
    cell's own arrivals at its own rate, all from the warm-up query pool
    (``exprs``, the requests made from it)."""

    def warm_round(i):
        recs: list = []
        sched = loadgen.open_loop(traffic, rate, WARM_TRAFFIC_S, seed,
                                  offset=loadgen.WARM_OFFSET + i)
        # batch compositions the Zipf head makes rare: pool ranks drawn
        # uniformly, so every template meets every other
        rng = np.random.default_rng([seed, loadgen.WARM_OFFSET, i])
        for b in range(1, MAX_BATCH + 1):
            for q in rng.choice(len(exprs), b, replace=False):
                loadgen.submit(server, exprs[q], int(q), 0.0, recs)
            loadgen.wait_all(recs, time.perf_counter() + LATE_S)
        recs += loadgen.drive_open(server, exprs, sched,
                                   time.perf_counter())
        loadgen.wait_all(recs, time.perf_counter() + LATE_S)

    return warm_round


def warm_pool(traffic, lake, seed: int) -> list:
    """The open-loop warm-up's query pool: other tables, other rows."""
    return loadgen.query_pool(traffic, lake, np.random.default_rng(
        [seed, loadgen.STREAM_POOL + loadgen.WARM_OFFSET]),
        offset=loadgen.WARM_OFFSET)


# ---------------------------------------------------------------- tracing
class Profile:
    """A ``jax.profiler`` trace of part of the window, taken on a helper
    thread so the pacing thread keeps its schedule.  One TraceAnnotation at
    a known ``time.monotonic()`` puts the server's spans on its clock."""

    def __init__(self, start_at: float, length: float):
        self.dir = tempfile.mkdtemp(prefix="bench_profile_")
        self.window = None
        self.marker = None
        self.error = None
        self._thread = threading.Thread(target=self._run,
                                        args=(start_at, length), daemon=True)
        self._thread.start()

    def _run(self, start_at, length):
        import jax

        try:
            while time.perf_counter() < start_at:
                time.sleep(min(start_at - time.perf_counter(), 0.05))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(tracereduce.MARKER):
                mark = time.monotonic()
            w0 = time.monotonic()
            time.sleep(length)
            w1 = time.monotonic()
            jax.profiler.stop_trace()
            self.marker, self.window = mark, (w0, w1)
        except Exception as e:                           # noqa: BLE001
            self.error = repr(e)

    def result(self, timeout: float = 120.0):
        self._thread.join(timeout)
        try:
            if self.error or self.window is None:
                return None
            return tracereduce.reduce_dir(self.dir, self.marker, self.window)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def batch_spans(records):
    """The flight recorder's batch span trees of the window, once each."""
    seen, out = set(), []
    for rec in records:
        if not rec.fut.done() or rec.fut.exception() is not None:
            continue
        resp = rec.fut.result()
        root = getattr(resp, "trace", None)
        if root is None or len(root.children) < 2:
            continue
        b = root.children[1]
        if id(b) not in seen:
            seen.add(id(b))
            out.append(b)
    return out


# -------------------------------------------------------------------- run
def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             t_process: float, device=None, serve=None) -> dict:
    """One run; returns the result line as a dict (``checks`` last).

    ``serve(cfg, seed, trace)`` makes ``(lake, server, to_request)``, the
    system under test and how a query tree becomes its request; the default
    is the program's :func:`build_server` and ``loadgen.to_expr``.  The
    control (``bench/control.py``) puts the reference in its place."""
    cfg, traffic, params = cell["config"], cell["traffic"], cell["params"]
    counter = CompileCounter()
    if serve is None:
        lake, server = build_server(cfg, seed, trace)
        to_request = loadgen.to_expr
    else:
        lake, server, to_request = serve(cfg, seed, trace)
    closed = traffic["loop"] == "closed"
    walk_rng = np.random.default_rng([seed, loadgen.WARM_OFFSET, 1 << 20])
    try:
        if closed:
            cache: dict = {}

            def query_of(t, _cache=cache):
                if t not in _cache:
                    q = loadgen.make_query(traffic["query"], lake, t,
                                           np.random.default_rng([seed, t]),
                                           6)
                    _cache[t] = (q, to_request(q))
                return _cache[t]

            clients = int(params["clients"])
            sched_seed = int(traffic["schedule_seed"])
            big = 400 * int(seconds * 100 + 100)

            def warm_round(i):
                # every batch size the former can form, then the closed
                # loop itself, from the warm-up table stream
                tabs = loadgen.closed_loop_tables(
                    lake, sched_seed, big, offset=loadgen.WARM_OFFSET + i)
                recs: list = []
                for b in range(1, MAX_BATCH + 1):
                    for t in tabs[b * 17:b * 18]:
                        loadgen.submit(server, query_of(int(t))[1], int(t),
                                       0.0, recs)
                    loadgen.wait_all(recs, time.perf_counter() + LATE_S)
                recs += loadgen.drive_closed(
                    server, query_of, tabs[400:], clients,
                    time.perf_counter(), WARM_TRAFFIC_S)
                loadgen.wait_all(recs, time.perf_counter() + LATE_S)

            def walk():
                tabs = [int(t) for t in loadgen.closed_loop_tables(
                    lake, sched_seed, min(lake.n_tables, 1024),
                    offset=loadgen.WARM_OFFSET - 1)]
                return ladder_walk(
                    server, [query_of(t)[1] for t in tabs],
                    query_features([query_of(t)[0] for t in tabs], lake),
                    walk_rng)
        else:
            pool = loadgen.query_pool(traffic, lake, np.random.default_rng(
                [seed, loadgen.STREAM_POOL]))
            exprs = [to_request(q) for q in pool]
            rate = float(params["rate_rps"])
            wpool = warm_pool(traffic, lake, seed)
            wexprs = [to_request(q) for q in wpool]
            warm_round = open_warm_round(server, traffic, wexprs, seed,
                                         rate)

            def walk():
                return ladder_walk(server, wexprs,
                                   query_features(wpool, lake), walk_rng)

        warm_up(warm_round, counter, walk)
        attempt = 0
        while True:
            # a window that traced, compiled or loaded a program is warm-up
            # too: drive another (other arrivals or tables, same sizes)
            before = counter.snapshot()
            t_start = time.perf_counter() + 0.05
            profile = Profile(t_start + min(1.0, seconds / 4),
                              min(4.0, seconds / 2)) if trace else None
            if closed:
                tables = loadgen.closed_loop_tables(lake, sched_seed, big,
                                                    offset=attempt)
                records = loadgen.drive_closed(server, query_of, tables,
                                               clients, t_start, seconds)
            else:
                schedule = loadgen.open_loop(traffic, rate, seconds, seed,
                                             offset=attempt)
                records = loadgen.drive_open(server, exprs, schedule,
                                             t_start)
            t_close = t_start + seconds
            loadgen.wait_all(records, t_close + LATE_S)
            after = counter.snapshot()
            compiles = after[0] - before[0] + after[1] + after[2] \
                - before[1] - before[2]
            late = [r.sent - r.due for r in records] if not closed \
                else [0.0]
            log(f"window {attempt}: {len(records)} requests in {seconds} s; "
                f"{after[0] - before[0]} programs traced and "
                f"{after[1] + after[2] - before[1] - before[2]} compiles "
                f"inside the window; generator late by p95 "
                f"{percentile(late, 95) * 1e3:.6f} ms, max "
                f"{max(late) * 1e3:.6f} ms")
            prof = profile.result() if profile is not None else None
            if compiles == 0 or time.perf_counter() - t_process + \
                    seconds + LATE_S > RUN_LIMIT_S:
                break
            attempt += 1
        dev = device if device is not None else _device()
        peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))
        log(f"device peak_bytes_in_use {peak}")
    finally:
        server.stop()

    # ---- the reference, after the window and off the device
    t_ref = time.perf_counter()
    ref = reference.Reference(lake)
    answers: dict = {}
    least: dict = {}

    def tree(key):
        return query_of(key)[0] if closed else pool[key]

    def answer_of(key):
        if key not in answers:
            answers[key] = ref.answer(tree(key))
        return answers[key]

    ok, counts = check(records, answer_of, lake.n_tables)
    counts["compiles_in_window"] = compiles
    log(f"reference: {len(answers)} distinct queries checked in "
        f"{time.perf_counter() - t_ref:.6f} s")
    limit_s = float(params["limit_ms"]) / 1e3
    lat = [(r.done - r.due) if (good and r.done is not None) else math.inf
           for r, good in zip(records, ok)]
    correct = sum(counts.values()) == 0 and len(records) > 0
    metrics = {}
    if trace:
        def least_of(key):
            if key not in least:
                least[key] = ref.least_bytes(tree(key))
            return least[key]
        ctx = trace_context(records, ok, prof, least_of,
                            tracereduce.peaks(dev.device_kind))
        for m in cell["per_layer"]:
            v = load_metric(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = {
            "p50_ms": percentile(lat, 50) * 1e3,
            "p95_ms": percentile(lat, 95) * 1e3,
            "goodput_rps": sum(1 for x in lat if x <= limit_s) / seconds,
            "setup_s": t_start - t_process,
        }
        for m in cell["end_to_end"]:
            v = values[m["name"]]
            metrics[m["name"]] = {"value": v if math.isfinite(v) else None,
                                  "unit": m["unit"]}
    device_info = {"platform": dev.platform, "kind": dev.device_kind,
                   "count": _device_count(), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": len(records),
           "failed": sum(1 for g in ok if not g), "metrics": metrics,
           "device": device_info}
    if trace and prof is not None:
        device_info["busy_s"] = prof["busy_s"]
        device_info["window_s"] = prof["window_s"]
        out["breakdown"] = tracereduce.breakdown(prof, batch_spans(records))
    out["checks"] = {k: {"value": v, "limit": 0} for k, v in counts.items()}
    return out


def trace_context(records, ok, prof, least_of, peaks):
    """What the per-layer readers read: the window's responses, the flight
    recorder's batch spans, the reduced device trace and the chip's peaks."""
    reqs = []
    for rec, good in zip(records, ok):
        if not good:
            continue
        resp = rec.fut.result()
        b = resp.trace.children[1] if resp.trace is not None else None
        reqs.append(SimpleNamespace(queue_s=resp.queue_seconds,
                                    batch_size=resp.batch_size,
                                    batch_t0=b.t0 if b is not None else None,
                                    least_bytes=lambda k=rec.key:
                                    least_of(k)))
    return SimpleNamespace(requests=reqs, batches=batch_spans(records),
                           device=prof, peaks=peaks)


def _device():
    import jax

    return jax.devices()[0]


def _device_count() -> int:
    import jax

    return jax.device_count()


def main(argv=None, t_process: float | None = None) -> int:
    t_process = time.perf_counter() if t_process is None else t_process
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--spec", default="BENCHMARK.json",
                    help="file of the workload, relative to the checkout")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, spec_file=args.spec)
    try:
        devices = require_chips(cell["chips"])
    except NoChip as e:
        log(str(e))
        return 2
    from repro.compile_cache import enable_compile_cache

    log(f"compile cache {enable_compile_cache()}")
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_process, device=devices[0])
    for name, c in out["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(out), flush=True)
    return 0
