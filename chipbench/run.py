#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of BENCHMARK.json.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine whose JAX sees the chips
the cell asks for; off a TPU, or with too few chips, it exits non-zero
and prints no result. The last line of standard output is the result
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
the numbers compared with their limits, which also end standard error.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench.common import load_json

    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"run.py: no workload {args.workload!r}", file=sys.stderr)
        return 2
    import jax

    devs = jax.devices()
    need = int(cells[args.workload]["chips"])
    if devs[0].platform != "tpu" or len(devs) < need:
        print(f"run.py: needs {need} TPU chip(s); JAX sees "
              f"{len(devs)} {devs[0].platform!r} device(s)", file=sys.stderr)
        return 1

    from chipbench.harness import run_cell

    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), t0=T0, hard_exit=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
