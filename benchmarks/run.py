"""Benchmark entrypoint: one function per paper table/figure, plus the
engine autotune sweep.  Prints ``name,us_per_call,derived`` CSV rows and
writes machine-readable records (per-benchmark µs + the engine's chosen
backend) to BENCH_gaunt.json so the perf trajectory is tracked across PRs.

    PYTHONPATH=src python -m benchmarks.run [--only fig1a,table2] [--fast]
        [--backend auto|<registered backend>] [--json BENCH_gaunt.json]
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all")
    ap.add_argument("--fast", action="store_true", help="smaller L sweeps")
    ap.add_argument("--backend", default="auto",
                    help="engine backend for engine-routed rows ('auto' = "
                         "measured autotune)")
    ap.add_argument("--json", default="BENCH_gaunt.json",
                    help="output path for machine-readable records "
                         "('' disables)")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    only = None if args.only == "all" else set(args.only.split(","))

    from . import (
        bench_engine,
        bench_equiformer_selfmix,
        bench_equivariant_conv,
        bench_feature_interaction,
        bench_manybody,
        bench_mace_gaunt,
        bench_sanity_nbody,
        bench_serve,
    )

    jobs = {
        "engine": lambda: bench_engine.run(
            L_list=(1, 2, 3, 6) if args.fast else (1, 2, 3, 4, 6, 8),
            B_list=(64, 1024) if args.fast else (64, 1024, 8192),
            backend=args.backend),
        "engine_batched": lambda: bench_engine.run_batched(backend=args.backend),
        "engine_chain": bench_engine.run_chain,
        "engine_chain_kernel": bench_engine.run_chain_kernel,
        "engine_grid_gate": bench_engine.run_grid_gate,
        "engine_mixed": bench_engine.run_mixed_precision,
        "engine_autotune_cache": bench_engine.run_autotune_cache,
        "serve": lambda: bench_serve.run_serve(fast=args.fast),
        "serve_chaos": lambda: bench_serve.run_serve_chaos(fast=args.fast),
        "fig1a": lambda: bench_feature_interaction.run(
            L_list=(1, 2, 3, 4) if args.fast else (1, 2, 3, 4, 5, 6, 8),
            backend=args.backend),
        "fig1b": lambda: bench_equivariant_conv.run(
            L_list=(1, 2, 3) if args.fast else (1, 2, 3, 4, 5, 6),
            backend=args.backend),
        "fig1cd": lambda: bench_manybody.run(backend=args.backend),
        "fig1e": bench_sanity_nbody.run,
        "table1": lambda: bench_equiformer_selfmix.run(
            L_list=(2, 4) if args.fast else (2, 4, 6)),
        "table2": bench_mace_gaunt.run,
    }
    print("name,us_per_call,derived")
    failed = []
    records = []
    for name, job in jobs.items():
        if only and name not in only:
            continue
        try:
            out = job()
            if out:
                records.extend(r for r in out if isinstance(r, dict))
        except Exception:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
    if args.json and records:
        import jax

        payload = {
            "meta": {"fast": args.fast, "backend_arg": args.backend,
                     "jax": jax.__version__, "device": jax.default_backend()},
            "records": records,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"wrote {len(records)} records to {args.json}", file=sys.stderr)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
