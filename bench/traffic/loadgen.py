"""The one traffic generator and its replay: a traffic file
(``bench/traffic/<name>.json``) plus ``--seed`` in, a deterministic list of
requests out, sent to a ``DiscoveryServer`` open loop (at due times) or
closed loop (one request in flight per client).

The arithmetic is copied from the program's ``serve/loadgen.py``
(``make_trace``, ``zipf_qids``, ``query_pool``, ``replay``): Markov-
modulated Poisson arrivals whose ON periods run at ``burst_factor`` times
the base rate, a bounded Zipf over a pool of distinct queries, a tenant and
lane mix, latency from each request's due time.  Two things differ, both
so that every seed gets the same work:

* the arrival times are drawn from the traffic file's own
  ``schedule_seed`` and then rotated by an offset drawn from ``--seed``, so
  every seed offers the same number of requests with the same gaps, with
  the bursts in other places;
* a query is a tree of plain tuples over token ids, built from the file's
  templates; ``to_expr`` turns it into the program's BlendQL expression and
  ``bench/reference.py`` evaluates the tuples themselves.

Query tree nodes::

    ("seek", kind, values, k, target)   kind SC | KW | MC | C; values are
                                        token ids (MC: tuples of them);
                                        target: C's numeric targets, else ()
    ("and" | "or" | "counter", k, (child, ...))
    ("sub", k, (left, right))           k None means no cut

Templates (the ``shapes`` of a traffic file) are the same trees in JSON::

    {"seek": "sc", "col": 0, "rows": "sample", "k": 24}
    {"seek": "kw", "col": 1, "rows": [0, 1], "k": 24}
    {"seek": "mc", "cols": [0, 1], "rows": {"first": 4}, "k": 24}
    {"seek": "corr", "col": 0, "rows": "sample", "k": 24}
    {"and": [...], "k": 10}, {"or": [...]}, {"sub": [a, b], "k": 10}
    {"counter": [...], "k": 10}
    {"counter": {"each_categorical_column": {"seek": "sc", ...}}, "k": 60}

``rows`` picks the rows of the query's lake table whose cells become the
values: ``"sample"`` the ``rows_per_query`` rows drawn for the query,
``"all"`` every row, a list indexes the sampled rows, ``{"first": n}`` the
first ``n`` sampled rows.  A correlation seeker's targets are the sampled
rows' positions (0, 1, 2, ...), as in the program's loadgen.
"""
from __future__ import annotations

import json
import queue
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent

#: independent random streams derived from --seed
STREAM_POOL, STREAM_ARRIVALS, STREAM_ROTATE, STREAM_CLOSED = 11, 12, 13, 14
#: the warm-up traffic uses --seed's streams shifted by this, so it never
#: replays the timed requests
WARM_OFFSET = 100

INTERACTIVE, BATCH = "interactive", "batch"
_SEEK = {"sc": "SC", "kw": "KW", "mc": "MC", "corr": "C"}
_COMB = ("and", "or", "sub", "counter")


def load_traffic(name_or_path) -> dict:
    p = Path(name_or_path)
    if not p.suffix:
        p = TRAFFIC_DIR / f"{name_or_path}.json"
    with open(p) as f:
        return json.load(f)


# ----------------------------------------------------------------- queries
def _rows(spec, sampled, n_rows):
    if spec == "sample":
        return sampled
    if spec == "all":
        return np.arange(n_rows)
    if isinstance(spec, dict):
        return sampled[:spec["first"]]
    return sampled[np.asarray(spec)]


def _build(tpl, lake, t, sampled):
    if "seek" in tpl:
        kind = _SEEK[tpl["seek"]]
        rows = _rows(tpl.get("rows", "sample"), sampled, int(lake.rows[t]))
        k = int(tpl["k"])
        if kind == "MC":
            cols = [lake.column(t, c) for c in tpl["cols"]]
            values = tuple(tuple(int(col[r]) for col in cols) for r in rows)
            return ("seek", kind, values, k, ())
        col = lake.column(t, tpl["col"])
        values = tuple(int(col[r]) for r in rows)
        target = tuple(float(j) for j in range(len(rows))) \
            if kind == "C" else ()
        return ("seek", kind, values, k, target)
    (op,) = [o for o in _COMB if o in tpl]
    body = tpl[op]
    if isinstance(body, dict):
        each = body["each_categorical_column"]
        kids = tuple(_build(dict(each, col=c), lake, t, sampled)
                     for c in range(int(lake.ncat[t])))
    else:
        kids = tuple(_build(b, lake, t, sampled) for b in body)
    return (op, tpl.get("k"), kids)


def make_query(tpl, lake, t: int, rng, rows_per_query: int):
    """One query over lake table ``t``; ``rng`` draws the sampled rows."""
    n = int(lake.rows[t])
    sampled = rng.choice(n, min(rows_per_query, n), replace=False)
    return _build(tpl, lake, t, sampled)


def query_pool(traffic: dict, lake, rng, offset: int = 0) -> list:
    """``n_distinct`` queries; query ``i`` takes template ``i % len(shapes)``
    over a lake table drawn uniformly (the program's loadgen ``query_pool``
    with the shapes read from the traffic file).  With the pool's
    ``table_seed`` the tables are drawn by shape from that seed (and
    ``offset``), so every ``--seed`` gives each pool rank a table of the
    same size; ``rng`` then draws only the sampled rows."""
    pool_cfg, shapes = traffic["pool"], traffic["shapes"]
    n = pool_cfg["n_distinct"]
    if pool_cfg.get("table_seed") is None:
        tabs = None
    else:
        tabs = lake.table_of_shape(np.random.default_rng(
            [pool_cfg["table_seed"], offset]).integers(0, lake.n_tables, n))
    pool = []
    for i in range(n):
        t = int(rng.integers(0, lake.n_tables)) if tabs is None \
            else int(tabs[i])
        pool.append(make_query(shapes[i % len(shapes)], lake, t, rng,
                               pool_cfg.get("rows_per_query", 6)))
    return pool


def zipf_qids(rng, n_distinct: int, size: int, a: float = 1.1) -> np.ndarray:
    """Bounded Zipf over pool ranks: P(rank r) ~ 1/r^a."""
    w = 1.0 / np.arange(1, n_distinct + 1, dtype=np.float64) ** a
    return rng.choice(n_distinct, size=size, p=w / w.sum())


def to_expr(q):
    """The program's BlendQL expression for a query tree."""
    import blend

    from bench.lakegen import token

    op = q[0]
    if op == "seek":
        _, kind, values, k, target = q
        if kind == "MC":
            return blend.mc([tuple(token(v) for v in tup) for tup in values],
                            k=k)
        words = [token(v) for v in values]
        if kind == "SC":
            return blend.sc(words, k=k)
        if kind == "KW":
            return blend.kw(words, k=k)
        return blend.corr(words, list(target), k=k)
    _, k, kids = q
    exprs = [to_expr(c) for c in kids]
    if op == "counter":
        return blend.counter(*exprs, k=k)
    if op == "sub":
        e = exprs[0] - exprs[1]
    else:
        e = exprs[0]
        for x in exprs[1:]:
            e = (e & x) if op == "and" else (e | x)
    return e.top(k) if k is not None else e


# ---------------------------------------------------------------- arrivals
def mmpp_times(rate_rps: float, duration_s: float, rng, *,
               burst_factor: float = 4.0, burst_fraction: float = 0.2,
               mean_burst_s: float = 0.05) -> np.ndarray:
    """Arrival times in ``[0, duration_s)`` of a Markov-modulated Poisson
    process with long-run mean rate ``rate_rps`` (the program's loadgen
    ``make_trace`` arithmetic)."""
    bf = min(max(burst_fraction, 0.0), 1.0)
    base = rate_rps / ((1.0 - bf) + bf * burst_factor)
    mean_off_s = mean_burst_s * (1.0 - bf) / bf if 0.0 < bf < 1.0 \
        else float("inf")
    out = []
    t = 0.0
    in_burst = bf >= 1.0
    state_end = (rng.exponential(mean_burst_s) if in_burst
                 else rng.exponential(mean_off_s)) if bf not in (0.0, 1.0) \
        else float("inf")
    while True:
        rate = base * (burst_factor if in_burst else 1.0)
        t += rng.exponential(1.0 / rate)
        while t > state_end:
            in_burst = not in_burst
            state_end += rng.exponential(
                mean_burst_s if in_burst else mean_off_s)
        if t >= duration_s:
            break
        out.append(t)
    return np.asarray(out)


@dataclass
class Request:
    due: float                   # seconds from the window's start
    qid: int                     # index into the query pool
    lane: str
    tenant: str


def open_loop(traffic: dict, rate_rps: float, duration_s: float, seed: int,
              offset: int = 0) -> list:
    """The timed (``offset=0``) or warm-up request schedule of one run.

    The arrival times, the Zipf draw of pool ranks, the lanes and the
    tenants come from the traffic file's ``schedule_seed``; ``--seed``
    rotates the times and permutes the rest, so every seed sends each pool
    rank, lane and tenant equally often, to other queries and in another
    order."""
    arr, pool_cfg = traffic["arrivals"], traffic["pool"]
    fixed = np.random.default_rng(arr["schedule_seed"])
    times = mmpp_times(rate_rps, duration_s, fixed,
                       burst_factor=arr["burst_factor"],
                       burst_fraction=arr["burst_fraction"],
                       mean_burst_s=arr["mean_burst_s"])
    n = len(times)
    qids = zipf_qids(fixed, pool_cfg["n_distinct"], n, a=pool_cfg["zipf_a"])
    lane = np.arange(n) < round(traffic["p_interactive"] * n)
    tenants = traffic["tenants"]
    ten = np.arange(n) % len(tenants)
    shift = np.random.default_rng(
        [seed, STREAM_ROTATE + offset]).uniform(0.0, duration_s)
    times = np.sort((times + shift) % duration_s)
    rng = np.random.default_rng([seed, STREAM_ARRIVALS + offset])
    qids, lane, ten = (a[rng.permutation(n)] for a in (qids, lane, ten))
    return [Request(float(t), int(q), INTERACTIVE if li else BATCH,
                    tenants[int(j)])
            for t, q, li, j in zip(times, qids, lane, ten)]


def closed_loop_tables(lake, schedule_seed: int, n: int, offset: int = 0):
    """The lake tables a closed loop's requests are made from, in order:
    passes over every table shape, each pass in an order drawn from the
    traffic file's ``schedule_seed``, mapped to the tables that have those
    shapes in this seed's lake; so every seed asks for the same sizes in
    the same order, over other values."""
    rng = np.random.default_rng([schedule_seed, STREAM_CLOSED + offset])
    passes = -(-n // lake.n_tables)
    shapes = np.concatenate([rng.permutation(lake.n_tables)
                             for _ in range(passes)])[:n]
    return lake.table_of_shape(shapes)


# ------------------------------------------------------------------ replay
class Record:
    __slots__ = ("key", "due", "sent", "done", "fut")

    def __init__(self, key, due, sent, fut):
        self.key, self.due, self.sent, self.fut = key, due, sent, fut
        self.done = None


def submit(server, expr, rec_key, due, records, lane=None, tenant=None,
           on_done=None):
    """Send one request; its ``Record`` notes when it was sent and when it
    was answered."""
    kw = {}
    if lane is not None:
        kw = {"lane": lane, "tenant": tenant}
    sent = time.perf_counter()
    fut = server.submit(expr, **kw)
    rec = Record(rec_key, due, sent, fut)

    def done(_f, rec=rec):
        rec.done = time.perf_counter()
        if on_done is not None:
            on_done(rec)

    records.append(rec)
    fut.add_done_callback(done)
    return rec


def drive_open(server, exprs, schedule, t_start: float) -> list:
    """Submit each request at its due time, whatever is outstanding."""
    records: list = []
    for req in schedule:
        due = t_start + req.due
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        submit(server, exprs[req.qid], req.qid, due, records, req.lane,
                req.tenant)
    return records


def drive_closed(server, query_of, tables, clients: int, t_start: float,
                 seconds: float) -> list:
    """``clients`` callers, each sending its next request as soon as its
    last one is answered, until the window closes."""
    records: list = []
    ready: queue.SimpleQueue = queue.SimpleQueue()
    it = iter(tables)

    def send():
        t = int(next(it))
        submit(server, query_of(t)[1], t, time.perf_counter(), records,
                on_done=lambda rec: ready.put(rec))

    while time.perf_counter() < t_start:
        time.sleep(min(t_start - time.perf_counter(), 0.01))
    for _ in range(clients):
        send()
    end = t_start + seconds
    while True:
        left = end - time.perf_counter()
        if left <= 0:
            break
        try:
            ready.get(timeout=left)
        except queue.Empty:
            break
        if time.perf_counter() < end:
            send()
    return records


def wait_all(records, deadline: float):
    for rec in records:
        try:
            rec.fut.result(timeout=max(deadline - time.perf_counter(), 0.0))
        except Exception:                                # noqa: BLE001
            pass
