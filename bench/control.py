"""The control of ``correct``: the reference put in the program's place with
one guarantee broken, driven and judged by the harness as a run is.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s> \
        [--cap 32] [--spec BENCHMARK.json]

The configurations guarantee exact answers from complete probes (no
probe-window overflow).  The control answers every request with a reference
that keeps only the first ``--cap`` postings of each query value -- the
smaller static window a faster probe would be tempted by.  It stands in
``harness.run_cell`` where the program's server stands: the same lake, the
same warm-up and window of the cell's traffic, the same comparison and the
same result line, whose ``correct`` has to come out false.  The benchmark's
own runs never run this.  It computes in NumPy on the host and needs no
chip.
"""
import sys
import time
from concurrent.futures import Future
from pathlib import Path

T_PROCESS = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


class ControlServer:
    """Answers each query tree at once with the capped reference, as a
    ``DiscoveryResponse``, in the interface the harness drives."""

    def __init__(self, lake, cap: int):
        from bench import reference

        self.ref = reference.Reference(lake, cap=cap)

    def submit(self, q, lane=None, tenant=None):
        from repro.serve.engine import DiscoveryResponse

        ids, scores = self.ref.answer(q)
        fut: Future = Future()
        fut.set_result(DiscoveryResponse(table_ids=ids, seconds=0.0,
                                         plan_nodes=0, scores=scores))
        return fut

    def stop(self):
        pass


def run(cell: dict, seed: int, seconds: float, cap: int) -> dict:
    """The result line of one control run of ``cell``."""
    from bench import harness, lakegen

    def serve(cfg, seed, _trace):
        lake = lakegen.generate(cfg, seed)
        return lake, ControlServer(lake, cap), lambda q: q

    return harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                            serve=serve)


def main(argv=None) -> int:
    import argparse
    import json

    from bench import harness

    ap = argparse.ArgumentParser(description="the control of correct")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--cap", type=int, default=32)
    ap.add_argument("--spec", default="BENCHMARK.json")
    args = ap.parse_args(argv)
    out = run(harness.load_cell(args.workload, spec_file=args.spec),
              args.seed, args.seconds, args.cap)
    for name, c in out["checks"].items():
        harness.log(f"check {name} {c['value']} limit {c['limit']}")
    out["control"] = {"workload": args.workload, "seed": args.seed,
                      "cap": args.cap,
                      "seconds": time.perf_counter() - T_PROCESS}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
