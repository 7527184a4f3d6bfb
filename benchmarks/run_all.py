"""Benchmark runner: every benchmark phase, each in its own process.

This process never imports JAX, so each phase owns the accelerator while it
runs (a parent holding the chip would leave its children none).  Phases run
in order; the runner exits non-zero if any of them failed.

* ``core`` — ``benchmarks/core_bench.py``: the Session-API workloads,
  ``BENCH_2.json`` to ``BENCH_5.json``;
* ``sharded`` — ``benchmarks/sharded_bench.py``: ``BENCH_6.json``, on the
  devices the process is given (``XLA_FLAGS=
  --xla_force_host_platform_device_count=8`` gives a CPU run eight);
* ``serving`` — ``benchmarks/serving_bench.py``: ``BENCH_7.json`` and
  ``BENCH_8.json`` (the CI-sized smoke sweep unless ``--full``);
* ``sketch`` — ``benchmarks/sketch_bench.py``: ``BENCH_9.json``;
* ``fault`` — ``benchmarks/fault_bench.py``: ``BENCH_10.json``.

``--full`` first runs the paper-table suites (``benchmarks/run.py``) and
folds their per-table JSON into ``BENCH_2.json``.

    python benchmarks/run_all.py [--out PATH] [--full] [--iters N]
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]
BENCH = REPO_ROOT / "benchmarks"


def phases(out_dir: Path, full: bool, iters: int) -> list:
    py = sys.executable

    def out(n):
        return str(out_dir / f"BENCH_{n}.json")

    return ([("paper_tables", [py, "-m", "benchmarks.run"])] if full else []) \
        + [("core", [py, str(BENCH / "core_bench.py"), "--out", out(2),
                     "--iters", str(iters)]
            + (["--paper-tables"] if full else [])),
           ("sharded", [py, str(BENCH / "sharded_bench.py"), "--out", out(6),
                        "--iters", str(iters)]),
           ("serving", [py, str(BENCH / "serving_bench.py"), "--out", out(7),
                        "--out8", out(8)] + ([] if full else ["--smoke"])),
           ("sketch", [py, str(BENCH / "sketch_bench.py"), "--out", out(9),
                       "--iters", str(iters), "--scales",
                       "1000,10000,100000" if full else "1000,10000"]),
           ("fault", [py, str(BENCH / "fault_bench.py"), "--out", out(10),
                      "--mutations", "40" if full else "24"])]


def main(out_path: Path, full: bool = False, iters: int = 10) -> int:
    path = os.pathsep.join([str(REPO_ROOT), str(REPO_ROOT / "src")]
                           + [p for p in [os.environ.get("PYTHONPATH")] if p])
    env = {**os.environ, "PYTHONPATH": path}
    failed = []
    for name, cmd in phases(out_path.parent, full, iters):
        print(f"[run_all] {name}: {' '.join(cmd)}", flush=True)
        rc = subprocess.run(cmd, cwd=REPO_ROOT, env=env).returncode
        if rc:
            print(f"[run_all] {name} FAILED (exit {rc})", flush=True)
            failed.append(name)
    if failed:
        print(f"[run_all] failed phases: {', '.join(failed)}")
        return 1
    print("[run_all] all phases passed")
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=Path, default=REPO_ROOT / "BENCH_2.json",
                    help="BENCH_2.json path; the other files go beside it")
    ap.add_argument("--full", action="store_true",
                    help="also run the paper-table suites (benchmarks/run.py)")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()
    sys.exit(main(args.out, full=args.full, iters=args.iters))
