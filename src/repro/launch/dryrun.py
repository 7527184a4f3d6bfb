import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# NOTE: the lines above MUST stay first — jax locks the platform and the
# device count on first init.  The dry-run compiles on 512 host devices and
# never takes an accelerator; its per-cell children inherit both settings.
# (This also forces the docstring below to be a plain comment.)

# Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.
#
# For each cell this produces, per device: memory analysis (proves HBM fit),
# XLA cost analysis, and a trip-count-aware HLO analysis (FLOPs, HBM traffic,
# collective bytes) feeding EXPERIMENTS.md §Dry-run / §Roofline.
#
# Run one cell:   python -m repro.launch.dryrun --arch yi-6b --shape train_4k
# Run everything: python -m repro.launch.dryrun --all   (resumable; one
# subprocess per cell so a pathological compile cannot kill the sweep).

import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, get_config, shape_applicable
from repro.dist.sharding import batch_spec, dp_axes, param_specs
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.train.step import (make_prefill_step, make_serve_step,
                              make_train_step, train_state_specs)

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"

PEAK_FLOPS = 197e12          # TPU v5e bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
LINK_BW = 50e9               # bytes/s per ICI link


# --------------------------------------------------------------------------
# sharding helpers for non-param pytrees
# --------------------------------------------------------------------------

def _cache_spec(mesh, name: str, shape):
    dp = dp_axes(mesh)
    dpn = 1
    for a in dp:
        dpn *= mesh.shape[a]
    msize = mesh.shape.get("model", 1)

    def dp_if(dim):
        return dp if dim % dpn == 0 and dim >= dpn else None

    def model_if(dim):
        return "model" if dim % msize == 0 and dim >= msize else None

    if name in ("k", "v", "ak", "av", "ek", "ev"):      # [L,B,T,K,hd]
        L, B, T, K, hd = shape
        if model_if(K):
            return P(None, dp_if(B), None if dp_if(B) else dp_if(T), "model", None)
        # few-KV-head GQA: shard the cache sequence dim instead (context-
        # parallel decode; softmax partials are combined by GSPMD collectives)
        return P(None, dp_if(B), model_if(T), None, None)
    if name == "state":                                  # [L,B,H,dk,dv]
        L, B, H, dk, dv = shape
        return P(None, dp_if(B), model_if(H), None, None)
    if name == "conv":                                   # [L,B,w,C]
        L, B, w, C = shape
        return P(None, dp_if(B), None, model_if(C))
    return P(*([None] * len(shape)))


def cache_shardings(cache_tree, mesh):
    def rule(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        return NamedSharding(mesh, _cache_spec(mesh, name, tuple(leaf.shape)))

    return jax.tree_util.tree_map_with_path(rule, cache_tree)


def batch_shardings(batch_tree, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, batch_spec(mesh, len(s.shape))), batch_tree)


# --------------------------------------------------------------------------
# analytic MODEL_FLOPS (6*N*D dense / 6*N_active*D MoE; decode counts 2*N)
# --------------------------------------------------------------------------

def count_params(tree) -> int:
    import math
    return sum(math.prod(l.shape) for l in jax.tree.leaves(tree))


def active_params(cfg, params_tree) -> int:
    total = count_params(params_tree)
    if not cfg.n_experts:
        return total
    per_expert = 3 * cfg.d_model * cfg.d_expert
    expert_total = cfg.n_layers * cfg.n_experts * per_expert
    expert_active = cfg.n_layers * cfg.top_k * per_expert
    return total - expert_total + expert_active


def model_flops(cfg, shape, params_tree) -> float:
    n_act = active_params(cfg, params_tree)
    if shape.kind == "train":
        return 6.0 * n_act * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_act * shape.global_batch * shape.seq_len
    return 2.0 * n_act * shape.global_batch          # decode: per step


# --------------------------------------------------------------------------
# the cell dry-run
# --------------------------------------------------------------------------

def build_lowered(cfg, shape, mesh):
    """Returns (lowered, params_tree_for_flop_count)."""
    sds = registry.input_specs(cfg, shape)
    if shape.kind == "train":
        state_sds = train_state_specs(cfg)
        ps = lambda tree: jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            param_specs(tree, mesh, fsdp=cfg.fsdp,
                        expert_data_shard=getattr(cfg, 'expert_data_shard', False)))
        state_sh = {
            "params": ps(state_sds["params"]),
            "opt": {
                "m": ps(state_sds["opt"]["m"]),
                "v": ps(state_sds["opt"]["v"]),
                "step": NamedSharding(mesh, P()),
            },
        }
        fn = jax.jit(make_train_step(cfg),
                     in_shardings=(state_sh, batch_shardings(sds, mesh)),
                     out_shardings=(state_sh, None),
                     donate_argnums=0)
        with mesh:
            return fn.lower(state_sds, sds), state_sds["params"]

    params_sds = registry.param_specs_tree(cfg)
    params_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        param_specs(params_sds, mesh, fsdp=cfg.fsdp,
                    expert_data_shard=getattr(cfg, 'expert_data_shard',
                                              False)))
    if shape.kind == "prefill":
        cache_sh = cache_shardings(
            registry.cache_specs(cfg, shape), mesh)
        fn = jax.jit(make_prefill_step(cfg, max_len=shape.seq_len),
                     in_shardings=(params_sh, batch_shardings(sds, mesh)),
                     out_shardings=(cache_sh, None))
        with mesh:
            return fn.lower(params_sds, sds), params_sds

    # decode
    cache_sds = registry.cache_specs(cfg, shape)
    cache_sh = cache_shardings(cache_sds, mesh)
    dp = dp_axes(mesh)
    dpn = 1
    for a in dp:
        dpn *= mesh.shape[a]
    tok_sh = NamedSharding(
        mesh, P(dp) if shape.global_batch % dpn == 0 and
        shape.global_batch >= dpn else P())
    fn = jax.jit(make_serve_step(cfg),
                 in_shardings=(params_sh, cache_sh, tok_sh),
                 out_shardings=(cache_sh, tok_sh, None),
                 donate_argnums=1)
    with mesh:
        return fn.lower(params_sds, cache_sds,
                        jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)), \
            params_sds


def run_cell(arch: str, shape_name: str, multi_pod: bool) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "chips": 512 if multi_pod else 256}
    if not shape_applicable(cfg, shape):
        rec.update(status="skipped",
                   reason="long_500k needs sub-quadratic attention; "
                          "full-attention arch (see DESIGN.md §Arch-applicability)")
        return rec
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    lowered, params_tree = build_lowered(cfg, shape, mesh)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    text = compiled.as_text()
    hlo = hlo_analysis.analyze(text)
    terms = hlo_analysis.roofline_terms(hlo, chips=rec["chips"],
                                        peak_flops=PEAK_FLOPS, hbm_bw=HBM_BW,
                                        link_bw=LINK_BW)
    mflops = model_flops(cfg, shape, params_tree)
    chips = rec["chips"]
    dominant = max(("compute_s", "memory_s", "collective_s"),
                   key=lambda k: terms[k])
    rec.update(
        status="ok",
        lower_s=round(t1 - t0, 2), compile_s=round(t2 - t1, 2),
        hlo_chars=len(text),
        memory=dict(
            argument_bytes=int(mem.argument_size_in_bytes),
            output_bytes=int(mem.output_size_in_bytes),
            temp_bytes=int(mem.temp_size_in_bytes),
            alias_bytes=int(mem.alias_size_in_bytes),
            peak_bytes_per_device=int(mem.argument_size_in_bytes
                                      + mem.output_size_in_bytes
                                      + mem.temp_size_in_bytes
                                      - mem.alias_size_in_bytes),
        ),
        xla_cost=dict(flops=float(cost.get("flops", -1)),
                      bytes_accessed=float(cost.get("bytes accessed", -1))),
        hlo_analysis=hlo,
        model_flops_global=mflops,
        model_flops_per_chip=mflops / chips,
        useful_flops_ratio=(mflops / chips) / max(hlo["flops"], 1),
        roofline=terms,
        dominant_term=dominant,
        params_global=count_params(params_tree),
        params_active_global=active_params(cfg, params_tree),
    )
    return rec


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def cell_path(arch, shape_name, multi_pod) -> Path:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    return RESULTS_DIR / f"{arch}__{shape_name}__{mesh_name}.json"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS + ["blend-discovery"])
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--timeout", type=int, default=2400)
    args = ap.parse_args()
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)

    if args.all:
        cells = [(a, s, mp) for a in ARCH_IDS for s in SHAPES
                 for mp in (False, True)]
        failures = 0
        for a, s, mp in cells:
            out = cell_path(a, s, mp)
            if out.exists() and not args.force:
                print(f"[dryrun] skip existing {out.name}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", a,
                   "--shape", s] + (["--multipod"] if mp else [])
            print(f"[dryrun] {a} x {s} x "
                  f"{'2x16x16' if mp else '16x16'} ...", flush=True)
            try:
                r = subprocess.run(cmd, timeout=args.timeout,
                                   env={**os.environ, "PYTHONPATH": "src"})
                if r.returncode != 0:
                    failures += 1
            except subprocess.TimeoutExpired:
                out.write_text(json.dumps({
                    "arch": a, "shape": s,
                    "mesh": "pod2x16x16" if mp else "pod16x16",
                    "status": "timeout", "timeout_s": args.timeout}))
                failures += 1
        print(f"[dryrun] sweep done, failures={failures}")
        sys.exit(1 if failures else 0)

    if args.arch == "blend-discovery":
        from repro.dist.shard import dryrun_discovery
        rec = dryrun_discovery(multi_pod=args.multipod)
        shape_name = args.shape or "lake"
        out = cell_path("blend-discovery", shape_name, args.multipod)
        out.write_text(json.dumps(rec, indent=2, default=str))
        print(json.dumps({k: rec[k] for k in ("arch", "status")
                          if k in rec}, indent=2))
        return

    try:
        rec = run_cell(args.arch, args.shape, args.multipod)
    except Exception as e:  # record the failure for the sweep report
        rec = {"arch": args.arch, "shape": args.shape,
               "mesh": "pod2x16x16" if args.multipod else "pod16x16",
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    cell_path(args.arch, args.shape, args.multipod).write_text(
        json.dumps(rec, indent=2, default=str))
    brief = {k: rec.get(k) for k in
             ("arch", "shape", "mesh", "status", "compile_s", "dominant_term",
              "useful_flops_ratio", "error")}
    brief["peak_gb_per_device"] = (
        rec.get("memory", {}).get("peak_bytes_per_device", 0) / 1e9
        if rec.get("memory") else None)
    print(json.dumps(brief, indent=2))
    if rec["status"] == "error":
        sys.exit(1)


if __name__ == "__main__":
    main()
