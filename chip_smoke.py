#!/usr/bin/env python3
"""Bring-up smoke of the certified-quadrature service on a TPU chip.

    python chip_smoke.py                 # every one-chip phase, N = 16,384
    python chip_smoke.py --four-chips    # the lane-mesh engine on 4 chips
                                         # against one of them, nothing else

The script drives the normal entry points at a deployment size —
``BIFEngine`` for served judge/bracket traffic, ``logdet_quad`` for a
one-shot job — with f32 arrays only (x64 stays off; float64 is used
only by the host reference) and data made from ``--seed``:

* dense: the Gaussian (RBF) Gram matrix of N points in R^4 plus a ridge,
  built on the device (1 GiB of HBM at N = 16,384). 256 requests — three
  quarters threshold judges with t at +-3% of the truth, one quarter
  brackets — go through the engine once per solver backend (reference,
  fused, pallas); under fused, one ``solve_batch`` of 64 brackets with
  ``reorth=True`` drives the reorth basis;
* sparse: a ``SparseBELL`` (bs = 128) weighted ring-lattice graph
  Laplacian plus a ridge, the same traffic with ``backend="pallas"``;
* one-shot: ``logdet_quad`` with 64 Hutchinson probes on the dense A.

A float64 Cholesky of each matrix on the host is the reference. Every
judge decision must equal the truth, every request must resolve, no
bracket may miss the truth by more than 1e-3 relative, and on the TPU
the kernel routes must compile to a ``tpu_custom_call``. Any failure
raises; the JSON last line is printed only when every phase passed.
Off the TPU the script exits 1 before any work.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

DIM = 4              # RBF points live in R^4
SIGMA = 0.5          # RBF length scale
KAPPA = 1000.0       # condition bound each ridge is chosen for
DEGREE = 12          # ring-lattice degree of the sparse graph
BS = 128             # BELL block size
MAX_ITERS = 128      # engine and reorth iteration cap
LOGDET_ITERS = 64    # logdet_quad's cap: (lanes, 4, M+1, M+1) eigh per step
RTOL = 1e-3
CHUNK_ITERS = 8
REQUESTS = 256       # served requests per engine phase
MAX_BATCH = 64       # engine pool lanes
PROBES = 64          # logdet_quad's Hutchinson probes
MAX_MISS = 1e-3      # largest relative amount a bracket may miss the truth
LAM_MAX_SLACK = 1e-4  # relative margin over an f32-summed Gershgorin bound


class SmokeFailure(AssertionError):
    pass


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# Problems (device operator + float64 host reference)


class Problem:
    """One SPD operator on the device, its certified spectral interval,
    and a float64 host reference built from the exact f32 entries the
    device holds."""

    def __init__(self, name, op, lam_min, lam_max, hbm_bytes, quad,
                 logdet=None):
        self.name, self.op = name, op
        self.lam_min, self.lam_max = lam_min, lam_max
        self.hbm_bytes = hbm_bytes
        self.quad = quad          # (m, N) f32 queries -> (m,) f64 u^T A^-1 u
        self.logdet = logdet      # f64 log det A, or None


def dense_problem(n: int, seed: int) -> Problem:
    """Gaussian (RBF) Gram matrix of n points in R^4 plus a ridge, built
    on the device with no (n, n, dim) host intermediate.

    K is positive semidefinite with entries in (0, 1], so its spectrum
    lies in [0, max row sum] (Gershgorin). With ridge = rowmax /
    (KAPPA - 1) the interval [ridge, rowmax + ridge] is certified and
    its condition bound is KAPPA."""
    import jax
    import jax.numpy as jnp
    import scipy.linalg

    from repro.core import Dense

    rng = np.random.default_rng(seed)
    box = n ** (1.0 / DIM) * SIGMA * 1.2   # the repo's RBF point density
    pts = jnp.asarray(rng.random((n, DIM), dtype=np.float32) * box,
                      jnp.float32)

    @jax.jit
    def gram(p):
        d2 = jnp.sum((p[:, None, :] - p[None, :, :]) ** 2, axis=-1)
        k = jnp.exp(d2 * (-0.5 / SIGMA ** 2))
        return k, jnp.max(jnp.sum(k, axis=-1)), jnp.sqrt(jnp.sum(k * k))

    k, rowmax, fro = gram(pts)
    rowmax = float(rowmax)
    ridge = rowmax / (KAPPA - 1.0)
    a = jax.jit(lambda k, r: k + r * jnp.eye(n, dtype=k.dtype))(
        k, jnp.float32(ridge))
    del k
    lam_max = (rowmax + ridge) * (1.0 + LAM_MAX_SLACK)
    _say(f"dense A: n={n} rbf dim={DIM} sigma={SIGMA} ridge={ridge!r} "
         f"lam_min=ridge lam_max={lam_max!r} kappa_bound="
         f"{lam_max / ridge!r} f32_rounding_bound={2.0 ** -23 * float(fro)!r}")

    t0 = time.perf_counter()
    chol = scipy.linalg.cho_factor(np.asarray(a).astype(np.float64),
                                   lower=True, overwrite_a=True,
                                   check_finite=False)
    logdet = 2.0 * float(np.sum(np.log(np.diag(chol[0]))))
    _say(f"dense reference: f64 Cholesky on the host "
         f"{time.perf_counter() - t0:.3f}s logdet={logdet!r}")

    def quad(us):
        u = np.asarray(us, np.float64)
        x = scipy.linalg.cho_solve(chol, u.T, check_finite=False)
        return np.einsum("kn,nk->k", u, x)

    return Problem("dense", Dense(a), ridge, lam_max, a.nbytes, quad,
                   logdet)


def sparse_problem(n: int, seed: int) -> Problem:
    """Weighted ring-lattice graph Laplacian plus a ridge as a
    ``SparseBELL`` with bs = 128: every node links to its DEGREE nearest
    ring neighbours with seeded weights in [0.5, 1.5], so each block row
    stores three blocks (its own and its two ring neighbours). L has the
    constant vector in its null space: lam_min = ridge exactly, and
    Gershgorin gives lam_max <= 2 max degree + ridge."""
    import jax.numpy as jnp
    import scipy.sparse
    import scipy.sparse.linalg

    from repro.core import SparseBELL

    _check(n % BS == 0, f"sparse n={n} must be a multiple of bs={BS}")
    rng = np.random.default_rng(seed + 2)
    half = DEGREE // 2
    src = np.repeat(np.arange(n), half)
    dst = (src + np.tile(np.arange(1, half + 1), n)) % n
    w = rng.uniform(0.5, 1.5, size=src.size)
    deg = np.bincount(src, w, n) + np.bincount(dst, w, n)
    ridge = 2.0 * float(deg.max()) / (KAPPA - 1.0)
    lam_max = (2.0 * float(deg.max()) + ridge) * (1.0 + LAM_MAX_SLACK)
    rows = np.concatenate([src, dst, np.arange(n)])
    cols = np.concatenate([dst, src, np.arange(n)])
    vals = np.concatenate([-w, -w, deg + ridge]).astype(np.float32)

    nr = n // BS
    brow, bcol = rows // BS, cols // BS
    off = (bcol - brow) % nr
    slot = np.where(off == 0, 1, np.where(off == 1, 2, 0))
    data = np.zeros((nr, 3, BS, BS), np.float32)
    np.add.at(data, (brow, slot, rows % BS, cols % BS), vals)
    r = np.arange(nr)
    table = np.stack([(r - 1) % nr, r, (r + 1) % nr], axis=1)
    op = SparseBELL(jnp.asarray(data), jnp.asarray(table, jnp.int32),
                    jnp.asarray(deg + ridge, jnp.float32), n)
    _say(f"sparse A: n={n} ring lattice degree={DEGREE} bs={BS} "
         f"blocks/row=3 ridge={ridge!r} lam_min=ridge lam_max={lam_max!r} "
         f"kappa_bound={lam_max / ridge!r}")

    lu = scipy.sparse.linalg.splu(scipy.sparse.csc_matrix(
        (vals.astype(np.float64), (rows, cols)), shape=(n, n)))

    def quad(us):
        u = np.asarray(us, np.float64)
        return np.einsum("kn,nk->k", u, lu.solve(u.T))

    return Problem("sparse", op, ridge, lam_max,
                   op.data.nbytes + op.cols.nbytes, quad)


def traffic(prob: Problem, count: int, seed: int):
    """``count`` requests: every fourth a bracket, the rest threshold
    judges with t at +-3% of the f64 truth. Returns (us, ts, truth) with
    NaN thresholds for brackets."""
    rng = np.random.default_rng(seed + 1)
    us = rng.standard_normal((count, prob.op.n), dtype=np.float32)
    truth = prob.quad(us)
    sign = rng.choice([-1.0, 1.0], size=count)
    ts = (truth * (1.0 + 0.03 * sign)).astype(np.float32).astype(np.float64)
    ts[np.arange(count) % 4 == 3] = np.nan
    return us, ts, truth


# ---------------------------------------------------------------------------
# Phases


def _kernel_calls(hlo: str) -> int:
    return hlo.count('custom_call_target="tpu_custom_call"')


def _misses(lo, hi, truth):
    """Relative amount by which each bracket misses the truth (0 inside)."""
    return np.maximum(0.0, np.maximum(lo - truth, truth - hi)) / np.abs(truth)


def _route(prob: Problem, backend: str) -> str:
    if backend == "reference":
        return "reference: XLA matvec + jnp recurrence"
    if backend == "fused":
        return ("fused: fused_step_" + ("dense" if prob.name == "dense"
                                        else "bell") + " kernel + XLA tail")
    return ("pallas: " + ("XLA matvec" if prob.name == "dense"
                          else "bell_matvec_mrhs kernel")
            + " + gql_update kernel")


def serve_phase(prob: Problem, backend: str, us, ts, truth, *,
                max_batch: int = MAX_BATCH, mesh=None,
                label: str | None = None):
    """Serve the traffic through ``BIFEngine`` and check it against the
    f64 truth. Returns the per-request (decision, iterations, lower,
    upper) arrays for cross-checks. ``lower_s`` times tracing one
    scheduler round and seeding a zero pool (the pool init's compile and
    run); ``compile_s`` the round's XLA compile, which a warm persistent
    cache serves."""
    import jax

    from repro.core import BIFSolver
    from repro.serve import BIFEngine, BIFRequest

    label = label or f"{prob.name}/{backend}"
    solver = BIFSolver.create(max_iters=MAX_ITERS, rtol=RTOL,
                              backend=backend)
    eng = BIFEngine(prob.op, solver=solver, max_batch=max_batch,
                    chunk_iters=CHUNK_ITERS, lam_min=prob.lam_min,
                    lam_max=prob.lam_max, mesh=mesh)
    t0 = time.perf_counter()
    lowered = eng.lower_step()
    lower_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    kernels = _kernel_calls(lowered.compile().as_text())
    compile_s = time.perf_counter() - t0
    if backend != "reference" and jax.default_backend() == "tpu":
        _check(kernels > 0, f"{label}: the engine's step holds no "
               f"tpu_custom_call (silent reference fallback)")

    reqs = [BIFRequest(u=u, t=None if np.isnan(t) else float(t))
            for u, t in zip(us, ts)]
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    eng.flush()
    wall_s = time.perf_counter() - t0

    judge = ~np.isnan(ts)
    lo = np.array([r.lower for r in reqs])
    hi = np.array([r.upper for r in reqs])
    its = np.array([r.iterations for r in reqs])
    dec = np.array([bool(r.decision) if r.t is not None else False
                    for r in reqs])
    resolved = np.array([bool(r.resolved) for r in reqs])
    wrong = int(np.sum(dec[judge] != (ts[judge] < truth[judge])))
    miss = float(_misses(lo, hi, truth).max())
    _say(f"phase {label}: n={prob.op.n} hbm_bytes_A={prob.hbm_bytes} "
         f"requests={len(reqs)} judges={int(judge.sum())} "
         f"brackets={int((~judge).sum())} mean_iters={float(its.mean())!r} "
         f"max_iters={int(its.max())} route=[{_route(prob, backend)}] "
         f"kernel_calls_in_step={kernels} lower_s={lower_s:.3f} "
         f"compile_s={compile_s:.3f} wall_s={wall_s:.3f} "
         f"wrong_decisions={wrong} "
         f"unresolved={int((~resolved).sum())} max_rel_miss={miss!r}")
    _check(wrong == 0, f"{label}: {wrong} judge decisions differ from "
           f"the f64 truth")
    _check(bool(resolved.all()), f"{label}: {int((~resolved).sum())} "
           f"requests did not resolve")
    _check(miss <= MAX_MISS, f"{label}: a bracket misses the f64 truth by "
           f"{miss!r} > {MAX_MISS}")
    return dec, its, lo, hi


def reorth_phase(prob: Problem, us, truth):
    """One fused ``solve_batch`` of brackets with ``reorth=True``: the
    basis flavor of the fused step, which the engine never takes."""
    import jax
    import jax.numpy as jnp

    from repro.core import BIFSolver, Dense

    solver = BIFSolver.create(max_iters=MAX_ITERS, rtol=RTOL,
                              backend="fused", reorth=True)

    @jax.jit
    def run(a, u):
        res = solver.solve_batch(Dense(a), u, lam_min=prob.lam_min,
                                 lam_max=prob.lam_max)
        return res.lower, res.upper, res.iterations, res.converged

    a, u = prob.op.a, jnp.asarray(us, jnp.float32)
    t0 = time.perf_counter()
    lowered = run.lower(a, u)
    lower_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    kernels = _kernel_calls(compiled.as_text())
    if jax.default_backend() == "tpu":
        _check(kernels > 0, "dense/fused+reorth: no tpu_custom_call")
    t0 = time.perf_counter()
    lo, hi, its, conv = jax.block_until_ready(compiled(a, u))
    wall_s = time.perf_counter() - t0
    lo, hi, its, conv = (np.asarray(x) for x in (lo, hi, its, conv))
    miss = float(_misses(lo.astype(np.float64), hi.astype(np.float64),
                         truth).max())
    _say(f"phase dense/fused+reorth: n={prob.op.n} hbm_bytes_A="
         f"{prob.hbm_bytes} brackets={len(us)} basis_rows={MAX_ITERS + 1} "
         f"mean_iters={float(its.mean())!r} max_iters={int(its.max())} "
         f"route=[fused: fused_step_dense kernel + XLA reorth tail] "
         f"kernel_calls={kernels} lower_s={lower_s:.3f} "
         f"compile_s={compile_s:.3f} "
         f"wall_s={wall_s:.3f} unresolved={int((~conv).sum())} "
         f"max_rel_miss={miss!r}")
    _check(bool(conv.all()), "dense/fused+reorth: unresolved brackets")
    _check(miss <= MAX_MISS, f"dense/fused+reorth: a bracket misses the "
           f"f64 truth by {miss!r}")


def logdet_phase(prob: Problem, probes: int, seed: int):
    """``logdet_quad`` with Hutchinson probes (f = log, the matfun
    eigensolve) against the host f64 log-determinant."""
    import jax

    from repro.core import logdet_quad

    t0 = time.perf_counter()
    res = logdet_quad(prob.op, probes, lam_min=prob.lam_min,
                      lam_max=prob.lam_max, max_iters=LOGDET_ITERS,
                      rtol=RTOL, key=jax.random.key(seed))
    wall_s = time.perf_counter() - t0
    its = res.state.iterations
    z = (res.estimate - prob.logdet) / max(res.std_error, 1e-30)
    _say(f"phase {prob.name}/logdet: n={prob.op.n} hbm_bytes_A="
         f"{prob.hbm_bytes} probes={probes} mean_iters={float(its.mean())!r} "
         f"max_iters={int(its.max())} route=[reference: XLA matvec + "
         f"batched eigh] wall_s_incl_compile={wall_s:.3f} "
         f"estimate={res.estimate!r} f64_logdet={prob.logdet!r} "
         f"det_bracket=[{res.lower!r}, {res.upper!r}] "
         f"std_error={res.std_error!r} z={z!r}")
    _check(bool(np.all(np.isfinite([res.lower, res.upper, res.estimate]))),
           "logdet: non-finite result")
    _check(res.lower <= res.estimate <= res.upper,
           "logdet: estimate outside its own bracket")
    # the probe mean is a random estimate of the exact logdet: allow five
    # standard errors plus the deterministic bracket's half-width
    slack = 5.0 * res.std_error + 0.5 * (res.upper - res.lower)
    _check(abs(res.estimate - prob.logdet) <= slack,
           f"logdet: estimate {res.estimate!r} is {z!r} standard errors "
           f"from the f64 logdet {prob.logdet!r}")


def one_chip(n: int, seed: int) -> None:
    """Every one-chip phase."""
    dense = dense_problem(n, seed)
    us, ts, truth = traffic(dense, REQUESTS, seed)
    for backend in ("reference", "fused", "pallas"):
        serve_phase(dense, backend, us, ts, truth, max_batch=MAX_BATCH)
        if backend == "fused":
            reorth_phase(dense, us[:MAX_BATCH], truth[:MAX_BATCH])
    logdet_phase(dense, PROBES, seed)
    del dense

    sparse = sparse_problem(n, seed)
    us, ts, truth = traffic(sparse, REQUESTS, seed)
    serve_phase(sparse, "pallas", us, ts, truth, max_batch=MAX_BATCH)


def four_chips(n: int, seed: int, requests: int = REQUESTS,
               max_batch: int = MAX_BATCH) -> None:
    """The dense traffic through ``BIFEngine(mesh=...)`` on a 4-device
    lane mesh, and the same traffic on one of those devices in the same
    process: decisions and iteration counts must be equal."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core import Dense
    from repro.launch.mesh import make_lane_mesh

    _check(len(jax.devices()) >= 4, f"--four-chips needs 4 devices, got "
           f"{len(jax.devices())}")
    mesh = make_lane_mesh(4)
    dense = dense_problem(n, seed)
    us, ts, truth = traffic(dense, requests, seed)
    one = serve_phase(dense, "fused", us, ts, truth, max_batch=max_batch,
                      label="dense/fused/1-chip")
    dense.op = Dense(jax.device_put(dense.op.a,
                                    NamedSharding(mesh, PartitionSpec())))
    four = serve_phase(dense, "fused", us, ts, truth, max_batch=max_batch,
                       mesh=mesh, label="dense/fused/4-chip-lanes")
    judge = ~np.isnan(ts)
    same_dec = bool(np.array_equal(one[0][judge], four[0][judge]))
    same_its = bool(np.array_equal(one[1], four[1]))
    diff = float(max(np.abs(one[2] - four[2]).max(),
                     np.abs(one[3] - four[3]).max()))
    _say(f"phase four-chips: equal_decisions={same_dec} "
         f"equal_iterations={same_its} max_bracket_diff={diff!r}")
    _check(same_dec, "four-chips: decisions differ from the single chip")
    _check(same_its, "four-chips: iteration counts differ from the single "
           "chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=16384)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip lane-mesh engine phase and "
                         "its single-chip comparison")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform is {dev.platform!r}); this "
              f"script runs only on the chip", file=sys.stderr)
        return 1
    _say(f"device: platform={dev.platform} kind={dev.device_kind} "
         f"count={len(devs)} jax={jax.__version__}")
    from repro.utils import compile_cache
    _say(f"compile cache: {compile_cache.enable()}")

    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(args.n, args.seed)
    else:
        one_chip(args.n, args.seed)
    _say(f"all phases passed in {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
