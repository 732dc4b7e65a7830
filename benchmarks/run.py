"""Benchmark harness -- one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]

Prints ``name,us_per_call,derived`` CSV rows (plus a JSON dump of the
Fig. 1 bound traces under experiments/bench/).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402

from repro.utils import compile_cache  # noqa: E402

jax.config.update("jax_enable_x64", True)
compile_cache.enable()

MODULES = [
    "bounds_convergence",     # Fig. 1 a/b/c
    "dpp_speedup",            # Fig. 2 + Table 2 DPP/kDPP rows
    "double_greedy_speedup",  # Table 2 DG rows
    "real_kernels",           # Table 1/2 real-data regimes (stand-ins)
    "quadrature_scaling",     # Thm. 3/5 rate check
    "kernel_report",          # Pallas kernel validation + accounting
    "batched_judges",         # per-candidate loop vs solve_batch (Sec. 6)
    "sharded_judges",         # 1-dev vs 8-virtual-device lanes (Sec. 7)
    "engine_throughput",      # lockstep vs continuous batching (Sec. 8)
    "trace_logdet",           # bracketed logdet vs dense slogdet (Sec. 9)
    "incremental_greedy",     # factor carry vs warm vs scratch (Sec. 12)
    "block_quadrature",       # block-Krylov vs scalar probes (Sec. 13)
]

# Suites whose tables are ALSO written to BENCH_<name>.json at the repo
# root, so the perf trajectory is tracked in-tree across PRs.
ROOT_TRACKED = {"batched_judges", "sharded_judges", "engine_throughput",
                "trace_logdet", "incremental_greedy", "block_quadrature"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    out_dir = Path("experiments/bench")
    out_dir.mkdir(parents=True, exist_ok=True)
    print("name,us_per_call,derived")
    failures = 0
    import importlib
    for mod_name in MODULES:
        if args.only and args.only != mod_name:
            continue
        mod = importlib.import_module(f"benchmarks.{mod_name}")
        try:
            rows, tables = mod.run(quick=not args.full)
        except Exception as e:  # noqa: BLE001
            print(f"{mod_name},,ERROR:{type(e).__name__}:{e}")
            failures += 1
            continue
        for r in rows:
            print(f"{r['name']},{r['us_per_call']},{r['derived']}")
        if tables:
            (out_dir / f"{mod_name}.json").write_text(
                json.dumps(tables, indent=1))
            # BENCH_TINY smoke runs (the CI engine-scheduler smoke) must
            # not clobber the in-tree perf trajectory with toy sizes
            if mod_name in ROOT_TRACKED and not os.environ.get("BENCH_TINY"):
                repo_root = Path(__file__).resolve().parent.parent
                (repo_root / f"BENCH_{mod_name}.json").write_text(
                    json.dumps(tables, indent=1) + "\n")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
