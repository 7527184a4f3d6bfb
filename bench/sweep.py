"""Knee sweep of an open-loop cell: one process, one lake, rising rates.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 200,400,800

Builds and warms the cell as a run does, then offers the cell's traffic at
each rate in turn for ``--seconds`` and prints one line per rate: p50 and
p95 latency (from each request's due time, failures as infinite) and the
goodput at the cell's latency limit, every answer checked against the
reference.  The knee is the highest rate whose p95 stays within the limit
with goodput near the offered rate; a cell is offered 4/5 of it.  Runs only
on a TPU.
"""
import sys
import time

T_PROCESS = time.perf_counter()

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    import argparse
    import json

    import numpy as np

    from bench import harness, reference
    from bench.traffic import loadgen

    ap = argparse.ArgumentParser(description="knee sweep of an open-loop "
                                             "cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    cell = harness.load_cell(args.workload)
    if cell["traffic"]["loop"] != "open":
        raise SystemExit("the sweep offers a fixed rate: open-loop cells only")
    try:
        harness.require_chips(cell["chips"])
    except harness.NoChip as e:
        harness.log(str(e))
        return 2
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    traffic, seed = cell["traffic"], args.seed
    counter = harness.CompileCounter()
    lake, server = harness.build_server(cell["config"], seed, False)
    limit_s = float(cell["params"]["limit_ms"]) / 1e3
    pool = loadgen.query_pool(traffic, lake, np.random.default_rng(
        [seed, loadgen.STREAM_POOL]))
    exprs = [loadgen.to_expr(q) for q in pool]
    windows = []
    try:
        wpool = harness.warm_pool(traffic, lake, seed)
        wexprs = [loadgen.to_expr(q) for q in wpool]
        warm_round = harness.open_warm_round(server, traffic, wexprs, seed,
                                             rates[len(rates) // 2])
        harness.warm_up(warm_round, counter, lambda: harness.ladder_walk(
            server, wexprs, harness.query_features(wpool, lake),
            np.random.default_rng([seed, loadgen.WARM_OFFSET])))
        for i, rate in enumerate(rates):
            sched = loadgen.open_loop(traffic, rate, args.seconds, seed,
                                      offset=i + 1)
            t0 = time.perf_counter() + 0.05
            recs = loadgen.drive_open(server, exprs, sched, t0)
            loadgen.wait_all(recs, t0 + args.seconds + harness.LATE_S)
            windows.append((rate, recs))
    finally:
        server.stop()
    ref = reference.Reference(lake)
    answers: dict = {}

    def answer_of(key):
        if key not in answers:
            answers[key] = ref.answer(pool[key])
        return answers[key]

    for rate, recs in windows:
        ok, counts = harness.check(recs, answer_of, lake.n_tables)
        lat = [(r.done - r.due) if g else float("inf")
               for r, g in zip(recs, ok)]
        row = {"rate_rps": rate, "requests": len(recs),
               "p50_ms": harness.percentile(lat, 50) * 1e3,
               "p95_ms": harness.percentile(lat, 95) * 1e3,
               "goodput_rps": sum(1 for x in lat if x <= limit_s)
               / args.seconds,
               "failed": {k: v for k, v in counts.items() if v}}
        print("sweep " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
