#!/usr/bin/env python3
"""Readings that the limits of ``limits/<cell>.json`` are set from.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 7,8,9] [--seconds 5]

In one process (one chip, set-up paid once per seed): each program seed
runs the cell as the benchmark does, with a short window, and each
control seed runs it with the control in the program's place (the
reference in the precision below the configuration's, see
``builders/<builder>.py``). Every number compared is printed per run,
and the last line is a JSON object with all readings. The benchmark's
own runs never run the control.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(workload: str, seeds, answerer: str, seconds: float,
             overrides=None, root=None) -> list:
    from chipbench.harness import ROOT as HROOT, run_cell

    out = []
    for seed in seeds:
        t = time.perf_counter()
        res = run_cell(workload, seed, seconds, False, t0=t,
                       answerer=answerer, overrides=overrides,
                       root=root or HROOT)
        row = {"seed": seed, "answerer": answerer, "correct": res["correct"],
               "attempted": res["attempted"],
               **{k: v["value"] for k, v in res["checks"].items()}}
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control.py: readings are taken on the chip", file=sys.stderr)
        return 1
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]
    rows = readings(args.workload, seeds, "program", args.seconds)
    rows += readings(args.workload, cseeds, "control", args.seconds)
    print(json.dumps({"workload": args.workload, "readings": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
