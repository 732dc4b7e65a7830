"""Shared set-up of the benchmark's CPU tests: tiny sizes of every cell.

The harness runs here with JAX on the CPU (Pallas in interpret mode),
its look for a chip skipped, at sizes a test run can hold; the chip runs
the same code at the configurations' own sizes.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

SEED = 2 ** 33 + 17            # seeds may exceed 32 bits
TINY = {
    "elevators_gp": {"n": 700},
    "epinions_graph": {"n": 1000, "edges": 6706,
                       "max_expected_degree": 100.0},
}
TINY_TRAFFIC = {"batch": 128, "probes": 16, "check_sample": 128,
                "check_hardest": 16}


def overrides(workload: str) -> dict:
    return {"config": TINY[workload.split(".")[0]],
            "traffic": dict(TINY_TRAFFIC)}


def run(workload: str, *, seconds: float = 1.0, answerer: str = "program",
        grace: float = 120.0, setup_grace: float = 600.0, root=None,
        seed: int = SEED) -> dict:
    import jax

    from chipbench import harness

    lines = []
    # a benchmark run is a process of its own, without 64-bit types; the
    # repo's tests/conftest.py turns them on for a whole test worker
    with jax.enable_x64(False):
        res = harness.run_cell(workload, seed, seconds, False,
                               t0=time.perf_counter(), answerer=answerer,
                               overrides=overrides(workload),
                               window_grace_s=grace,
                               setup_grace_s=setup_grace, say=lines.append,
                               root=root or harness.ROOT)
    res["_lines"] = lines
    return res
