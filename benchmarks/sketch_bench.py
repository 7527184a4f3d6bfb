"""Approximate discovery benchmark (BENCH_9).

Measures the sketch tier (core/sketch.py + ``Session.query(approx=...)``)
against the exact path on lakes of 1k / 10k / 100k total columns:

* ``approx/<scale>/<kind>`` — p50 latency approx vs exact and recall@k of
  the approx top-k against the exact top-k, per seeker kind (SC / KW / C);
* ``escalation_curve`` — escalation rate, recall@k and p50 vs epsilon at
  one scale: the knob's whole trade-off in one table.

Acceptance (ISSUE 9): on the 100k-column workload the approx path is
>= 3x faster at p50 than exact with <= 5% recall@10 loss; the payload's
``acceptance`` block records the measured numbers and the verdict.

The lake is window-skewed (each table draws its tokens from a random
window of the vocab, queries from a window likewise) so rankings have
realistic spread — on a uniform lake every table ties and no ranking,
exact or approximate, is meaningful.

    PYTHONPATH=src python benchmarks/sketch_bench.py [--out PATH]
        [--iters N] [--scales 1000,10000,100000]
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]
for p in (REPO_ROOT, REPO_ROOT / "src"):       # runnable as a plain script
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np

import blend
from repro.core.lake import DataLake, Table
from repro.core.plan import Plan, Seekers

COLS = 5            # 3 token columns + 2 numeric per table
ROWS = 120          # sketch K (128) covers most columns; exact pays per row
VOCAB = 4000
K_TOP = 10
N_QUERIES = 6
# A query value matches ~n_tables * 3 * ROWS / VOCAB postings; the exact
# path must gather them all or its scores undercount (surfaced as
# ``overflow`` but fatal for a ground-truth reference).  Provision for the
# 100k-column density plus tail.
M_CAP_MAX = 4096


def _stats(seconds: list) -> dict:
    a = np.asarray(seconds)
    return {
        "iters": int(a.size),
        "ops_per_sec": float(a.size / a.sum()) if a.sum() else 0.0,
        "mean_ms": float(a.mean() * 1e3),
        "p50_ms": float(np.percentile(a, 50) * 1e3),
        "p95_ms": float(np.percentile(a, 95) * 1e3),
    }


def bench_lake(n_tables: int, seed: int = 1) -> DataLake:
    rng = np.random.default_rng(seed)
    tables = []
    for i in range(n_tables):
        lo = int(rng.integers(0, VOCAB))
        width = int(rng.integers(60, 400))
        data = [[f"tok_{(lo + int(x)) % VOCAB}"
                 for x in rng.integers(0, width, ROWS)]
                for _ in range(COLS - 2)]
        data += [[float(x) for x in np.round(rng.normal(0, 5, ROWS), 3)]
                 for _ in range(2)]
        tables.append(Table(f"t{i}", data))
    return DataLake(tables)


def make_queries(rng, kind: str, n: int = N_QUERIES) -> list:
    out = []
    for _ in range(n):
        lo = int(rng.integers(0, VOCAB))
        vals = [f"tok_{(lo + int(x)) % VOCAB}"
                for x in rng.integers(0, 300, 300)]
        vals = list(dict.fromkeys(vals))
        if kind == "c":
            jv = vals[:24]
            spec = Seekers.Correlation(
                jv, [float(x) for x in rng.normal(0, 1, len(jv))], k=K_TOP)
        elif kind == "kw":
            spec = Seekers.KW(vals, k=K_TOP)
        else:
            spec = Seekers.SC(vals, k=K_TOP)
        p = Plan()
        p.add("out", spec)
        out.append(p)
    return out


def recall_at_k(approx_ids: list, exact_ids: list, k: int = K_TOP) -> float:
    if not exact_ids:
        return 1.0
    top = set(exact_ids[:k])
    return len(top & set(approx_ids[:k])) / len(top)


def scale_workloads(total_cols: int, iters: int, approx=True) -> dict:
    n_tables = total_cols // COLS
    t0 = time.perf_counter()
    lake = bench_lake(n_tables)
    session = blend.connect(lake, m_cap_max=M_CAP_MAX)
    session.query(blend.kw(["tok_1"], k=5))        # resident index
    build_s = time.perf_counter() - t0

    rng = np.random.default_rng(9)
    out = {"_index_build_s": build_s, "_tables": n_tables,
           "_columns": n_tables * COLS}
    for kind in ("sc", "kw", "c"):
        qs = make_queries(rng, kind)
        for q in qs[:2]:                           # warm jit both paths
            session.query(q).ids
            session.query(q, approx=True).ids
        exact_s, approx_s, recalls, esc = [], [], [], []
        for _ in range(max(iters // 2, 2)):
            for q in qs:
                t0 = time.perf_counter()
                eids = session.query(q).ids
                exact_s.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                res = session.query(q, approx=True)
                aids = res.ids
                approx_s.append(time.perf_counter() - t0)
                recalls.append(recall_at_k(aids, eids))
                esc.append(res.approx.escalated
                           / max(res.approx.candidates, 1))
        ex, ap = _stats(exact_s), _stats(approx_s)
        ap["recall_at_k"] = float(np.mean(recalls))
        ap["escalation_rate"] = float(np.mean(esc))
        ap["speedup_vs_exact"] = ex["p50_ms"] / ap["p50_ms"]
        out[f"{kind}/exact"] = ex
        out[f"{kind}/approx"] = ap
    return out


def escalation_curve(total_cols: int, iters: int) -> list:
    """Escalation rate / recall / latency vs epsilon (one scale, C + SC)."""
    lake = bench_lake(total_cols // COLS)
    session = blend.connect(lake, m_cap_max=M_CAP_MAX)
    rng = np.random.default_rng(13)
    qs = make_queries(rng, "sc", 4) + make_queries(rng, "c", 4)
    exact_ids = {}
    for i, q in enumerate(qs):                     # warm + exact reference
        exact_ids[i] = session.query(q).ids
        session.query(q, approx=True).ids
    curve = []
    for eps in (0.0, 0.01, 0.02, 0.05, 0.1, 0.2, 0.5):
        secs, recalls, esc = [], [], []
        for _ in range(max(iters // 2, 2)):
            for i, q in enumerate(qs):
                t0 = time.perf_counter()
                res = session.query(q, approx={"epsilon": eps})
                aids = res.ids
                secs.append(time.perf_counter() - t0)
                recalls.append(recall_at_k(aids, exact_ids[i]))
                esc.append(res.approx.escalated
                           / max(res.approx.candidates, 1))
        point = _stats(secs)
        point["epsilon"] = eps
        point["recall_at_k"] = float(np.mean(recalls))
        point["escalation_rate"] = float(np.mean(esc))
        curve.append(point)
    return curve


def main(out_path: Path, iters: int = 10, scales=None) -> dict:
    scales = scales or [1000, 10000, 100000]
    workloads = {}
    for total_cols in scales:
        tag = f"{total_cols // 1000}k"
        workloads[tag] = scale_workloads(total_cols, iters)
        s = workloads[tag]
        for kind in ("sc", "kw", "c"):
            ap = s[f"{kind}/approx"]
            print(f"approx/{tag}/{kind}: exact p50 "
                  f"{s[f'{kind}/exact']['p50_ms']:.2f}ms  approx p50 "
                  f"{ap['p50_ms']:.2f}ms  ({ap['speedup_vs_exact']:.1f}x, "
                  f"recall {ap['recall_at_k']:.3f}, "
                  f"esc {ap['escalation_rate']:.2f})")
    curve_scale = scales[min(1, len(scales) - 1)]
    curve = escalation_curve(curve_scale, iters)
    for pt in curve:
        print(f"eps={pt['epsilon']:<5} p50={pt['p50_ms']:8.2f}ms "
              f"recall={pt['recall_at_k']:.3f} esc={pt['escalation_rate']:.2f}")

    top_tag = f"{max(scales) // 1000}k"
    top = workloads[top_tag]
    best = max(("sc", "kw", "c"),
               key=lambda k: top[f"{k}/approx"]["speedup_vs_exact"])
    accept = {
        "scale": top_tag,
        "kind": best,
        "speedup_vs_exact": top[f"{best}/approx"]["speedup_vs_exact"],
        "recall_at_k": top[f"{best}/approx"]["recall_at_k"],
        "pass": bool(top[f"{best}/approx"]["speedup_vs_exact"] >= 3.0
                     and top[f"{best}/approx"]["recall_at_k"] >= 0.95),
    }
    print(f"acceptance[{top_tag}/{best}]: "
          f"{accept['speedup_vs_exact']:.1f}x at recall "
          f"{accept['recall_at_k']:.3f} -> "
          f"{'PASS' if accept['pass'] else 'FAIL'}")

    payload = {
        "bench": "BENCH_9",
        "platform": platform.platform(),
        "python": platform.python_version(),
        "config": {"rows": ROWS, "cols": COLS, "vocab": VOCAB,
                   "k_top": K_TOP, "scales": scales},
        "workloads": workloads,
        "escalation_curve": {"scale_cols": curve_scale, "points": curve},
        "acceptance": accept,
    }
    out_path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    print(f"wrote {out_path}")
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=Path, default=REPO_ROOT / "BENCH_9.json")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--scales", type=str, default="1000,10000,100000")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(args.out, iters=args.iters,
         scales=[int(s) for s in args.scales.split(",")])
