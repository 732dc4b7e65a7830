"""1-device vs 8-virtual-device scaling of the sharded batched judges,
swept over the decision-round cadence (DESIGN.md Sec. 7 and 11).

Times ``judge_batch`` on one device against ``judge_batch_sharded`` on
an 8-virtual-CPU-device lane mesh for N in {256, 1024} x K in {8, 64},
at ``decide_every`` R in {1, 4, 8}.

Virtual devices time-share the host's cores (the CI rig has ONE), so
the raw sharded/1-device ratio conflates two different taxes:

  * the *compute floor* — eight serialized lane programs are slower
    than one batched gemm on the same silicon no matter what the
    collectives cost. The benchmark MEASURES this floor instead of
    guessing: a third mode runs the identical lane-sharded drive with
    ZERO collectives (``shard_map`` of the single-device ``judge_batch``
    over the lane shards — valid because the threshold decide is
    per-lane, and asserted to reach identical decisions);
  * the *collective tax* — what the lockstep gather rounds add on top
    of that floor. This is the quantity the round cadence and the
    packed flag-folding gather actually optimize, and it is the
    headline ``vdev_overhead`` (labelled via ``vdev_overhead_baseline``;
    the raw cross-topology ratio stays in the table as
    ``vdev_overhead_vs_1dev`` next to the measured
    ``floor_overhead_vs_1dev`` rig physics).

Each sharded timing also pins the COMPILED collective census: the
worker lowers the jitted drive and counts collective instructions in
the HLO (``repro.utils.hlo.collective_counts``). A ``lax.while`` body
appears once in HLO, so the count reads as collectives-per-round plus
the loop-boundary gather — and it must show zero psum at every cadence.
Decisions are asserted identical across all three modes AND cadences.

Because the device count must be fixed BEFORE jax initializes, each
timing runs in a subprocess of this file (``--worker``) with its own
``XLA_FLAGS``; the parent assembles the table
(``BENCH_sharded_judges.json`` at the repo root via run.py). On a TPU
the modes run in the calling process instead: a chip belongs to one
process (a JAX child would find it held by this one), and host device
counts do not apply there — the lane mesh spans the attached chips.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

SIZES = [(256, 8), (256, 64), (1024, 8), (1024, 64)]
CADENCES = [1, 4, 8]


def _measure(mode: str, sizes, cadences) -> dict:
    """One mode's timings; runs inside a subprocess whose XLA_FLAGS are
    already set, or in-process on a TPU."""
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
    import jax

    jax.config.update("jax_enable_x64", True)
    ndev = len(jax.devices())

    import jax.numpy as jnp
    import numpy as np

    from repro.core import BIFSolver, Dense, gershgorin_bounds
    from repro.utils.hlo import collective_counts

    def problem(n, k, seed=0, bandwidth=128):
        # block-banded diagonally dominant SPD: the certified Gershgorin
        # interval is tight (same generator as batched_judges)
        rng = np.random.default_rng(seed)
        m = rng.standard_normal((n, n))
        band = np.abs(np.arange(n)[:, None]
                      - np.arange(n)[None, :]) < bandwidth
        a = (m + m.T) / 2 * band
        a[np.diag_indices(n)] = np.abs(a).sum(axis=1) + 0.1
        us = rng.standard_normal((k, n))
        true = np.einsum("ki,ki->k", us, np.linalg.solve(a, us.T).T)
        ts = true * np.where(rng.random(k) < 0.5, 0.97, 1.03)
        return a, jnp.asarray(us), jnp.asarray(ts)

    from repro.obs.metrics import Histogram

    def time_fn(fn, repeats=5, warmup=2):
        """Median + exact p50/p99 over the repeats, via the obs
        histogram helper (DESIGN.md Sec. 14)."""
        import time
        for _ in range(warmup):
            jax.block_until_ready(fn())
        hist = Histogram("wall_s")
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            hist.observe(time.perf_counter() - t0)
        return (hist.percentile(50.0), hist.percentile(50.0),
                hist.percentile(99.0))

    if mode in ("sharded", "floor"):
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import make_lane_mesh
        mesh = make_lane_mesh()

    out = {"devices": ndev, "mode": mode, "results": {}}
    for n, k in sizes:
        a, us, ts = problem(n, k)
        op = Dense(jnp.asarray(a))
        est = gershgorin_bounds(op)
        lmn, lmx = float(est.lam_min), float(est.lam_max)
        per_r = {}
        for r in cadences:
            solver = BIFSolver.create(max_iters=64, rtol=1e-3,
                                      decide_every=r)
            if mode == "sharded":
                fn = jax.jit(
                    lambda us_, ts_, op=op, solver=solver:
                    solver.judge_batch_sharded(op, us_, ts_, mesh=mesh,
                                               lam_min=lmn, lam_max=lmx))
            elif mode == "floor":
                # the collective-free control: the SAME lane shards run
                # the single-device drive independently (no gathers, no
                # lockstep). Valid because the threshold decide is
                # per-lane; decisions are asserted identical outside.
                fn = jax.jit(jax.shard_map(
                    lambda us_, ts_, op=op, solver=solver:
                    solver.judge_batch(op, us_, ts_, lam_min=lmn,
                                       lam_max=lmx),
                    mesh=mesh, in_specs=(P("lanes"), P("lanes")),
                    out_specs=P("lanes"), check_vma=False))
            else:
                fn = jax.jit(
                    lambda us_, ts_, op=op, solver=solver:
                    solver.judge_batch(op, us_, ts_, lam_min=lmn,
                                       lam_max=lmx))
            res = jax.block_until_ready(fn(us, ts))
            wall, p50, p99 = time_fn(lambda: fn(us, ts))
            entry = {
                "wall_s": round(wall, 5),
                "wall_s_p50": round(p50, 5),
                "wall_s_p99": round(p99, 5),
                "iters_max": int(np.asarray(res.iterations).max()),
                "decisions_true": int(np.asarray(res.decision).sum()),
            }
            if mode == "sharded":
                # the compiled collective census: the while body appears
                # once in HLO, so this pins collectives-per-round (+ the
                # boundary gather) — and must show ZERO all-reduce/psum
                hlo = fn.lower(us, ts).compile().as_text()
                counts = collective_counts(hlo)
                entry["hlo_collectives"] = {
                    kk: vv for kk, vv in counts.items() if kk != "count"}
                entry["hlo_collective_count"] = counts["count"]
            per_r[f"R{r}"] = entry
        out["results"][f"dense_n{n}_k{k}"] = per_r
    return out


def _spawn(mode: str, devices: int, sizes, cadences):
    env = dict(os.environ)
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={devices}"
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).resolve()), "--worker", mode,
         json.dumps(sizes), json.dumps(cadences)],
        capture_output=True, text=True, timeout=2400, env=env)
    for line in proc.stdout.splitlines():
        if line.startswith("JSON:"):
            return json.loads(line[5:])
    raise RuntimeError(
        f"sharded_judges worker ({mode}, {devices} devices) failed:\n"
        f"{proc.stdout[-500:]}\n{proc.stderr[-2000:]}")


def run(quick: bool = True):
    # the acceptance grid N in {256,1024} x K in {8,64} runs in all
    # modes at every cadence; --full adds nothing (the grid IS the
    # artifact)
    sizes = SIZES
    import jax
    if jax.default_backend() == "tpu":
        single, floor, sharded = (_measure(mode, sizes, cadences)
                                  for mode, cadences in (
                                      ("single", [1]), ("floor", CADENCES),
                                      ("sharded", CADENCES)))
    else:
        single = _spawn("single", 1, sizes, [1])
        floor = _spawn("floor", 8, sizes, CADENCES)
        sharded = _spawn("sharded", 8, sizes, CADENCES)
    rows, tables = [], {}
    for key in single["results"]:
        s1 = single["results"][key]["R1"]
        entry = {"wall_s_1dev": s1["wall_s"],
                 "iters_max_1dev": s1["iters_max"],
                 "cadence": {}}
        best = best_vs1 = None
        for r in CADENCES:
            s8 = sharded["results"][key][f"R{r}"]
            sf = floor["results"][key][f"R{r}"]
            # the decision set is cadence- and topology-invariant
            # (Thm. 4.2); a divergence here is a correctness bug, not
            # a perf regression
            assert s1["decisions_true"] == s8["decisions_true"], \
                f"sharded decisions diverged on {key} at R={r}"
            assert s1["decisions_true"] == sf["decisions_true"], \
                f"collective-free floor decisions diverged on {key} R={r}"
            assert not s8["hlo_collectives"].get("all-reduce"), \
                f"psum leaked back into the sharded drive on {key} R={r}"
            tax = round(s8["wall_s"] / max(sf["wall_s"], 1e-9), 2)
            vs1 = round(s8["wall_s"] / max(s1["wall_s"], 1e-9), 2)
            entry["cadence"][f"R{r}"] = {
                "wall_s_8vdev": s8["wall_s"],
                "wall_s_p50_8vdev": s8["wall_s_p50"],
                "wall_s_p99_8vdev": s8["wall_s_p99"],
                "wall_s_floor_8vdev": sf["wall_s"],
                "collective_tax": tax,
                "vdev_overhead_vs_1dev": vs1,
                "iters_max_8vdev": s8["iters_max"],
                "hlo_collectives": s8["hlo_collectives"],
            }
            rows.append({
                "name": f"sharded_judges_{key}_R{r}",
                "us_per_call": round(s8["wall_s"] * 1e6, 2),
                "derived": f"collective_tax_{tax}x;"
                           f"vs_1dev_{vs1}x;"
                           f"hlo_collectives_"
                           f"{s8['hlo_collective_count']}"})
            if best is None or tax < best[1]:
                best = (r, tax)
            if best_vs1 is None or vs1 < best_vs1[1]:
                best_vs1 = (r, vs1)
        # headline overhead = what the collectives ADD over the measured
        # collective-free floor at the tuned cadence (decide_every exists
        # precisely to amortize the per-round gather away); the raw
        # cross-topology ratio (at ITS best cadence) and the rig's
        # time-sharing floor (at R1, the natural compute-floor point —
        # coarser cadences inflate iterations) sit next to it so nothing
        # hides
        entry["vdev_overhead"] = best[1]
        entry["vdev_overhead_cadence"] = f"R{best[0]}"
        entry["vdev_overhead_baseline"] = \
            "collective-free lane-local drive on the same 8-vdev mesh"
        entry["vdev_overhead_vs_1dev"] = best_vs1[1]
        entry["vdev_overhead_vs_1dev_cadence"] = f"R{best_vs1[0]}"
        entry["floor_overhead_vs_1dev"] = round(
            floor["results"][key]["R1"]["wall_s"]
            / max(s1["wall_s"], 1e-9), 2)
        tables[key] = entry
    return rows, tables


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--worker":
        cadences = json.loads(sys.argv[4]) if len(sys.argv) > 4 else [1]
        print("JSON:" + json.dumps(
            _measure(sys.argv[2], json.loads(sys.argv[3]), cadences)))
    else:
        rows, tables = run()
        print(json.dumps(tables, indent=1))
