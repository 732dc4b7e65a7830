"""Back-to-back log-determinant jobs: ``logdet_quad`` with ``probes``
Hutchinson probes and a new probe key per job, as one step of GP
hyperparameter fitting would run it. The window closes at the end of the
job running when ``--seconds`` have elapsed.

End-to-end: ``logdet_s`` = total time of the window's jobs / their count.

The check compares the brackets the jobs returned with the float64
reference, probe by probe: probe i of a job with key k is the Rademacher
vector ``rademacher(fold_in(key(k), i))`` of the library's documented
probe stream, drawn again here.
"""
from __future__ import annotations

import math
import time

import numpy as np

from chipbench import checks
from chipbench.common import rng, seed32

KIND = "job"


def probe_vectors(key: int, index, n: int) -> np.ndarray:
    """Probes ``index`` of the stream of ``key``, (len(index), n) f32."""
    import jax
    import jax.numpy as jnp

    base = jax.random.key(key)
    return np.asarray(jax.vmap(lambda i: jax.random.rademacher(
        jax.random.fold_in(base, i), (n,), jnp.float32))(
            jnp.asarray(index, jnp.int32)))


class _ControlState:
    def __init__(self, values: np.ndarray):
        self.probe_lower = self.probe_upper = values
        self.iterations = np.zeros(values.size, np.int32)


class _ControlResult:
    """The control's answer: a point value for every probe."""

    def __init__(self, values: np.ndarray):
        self.state = _ControlState(values)
        self.estimate = self.lower = self.upper = float(values.mean())
        self.std_error = float(values.std(ddof=1) / math.sqrt(values.size))


def setup(run):
    dep, mix, probes = run.dep, run.mix, int(run.mix["probes"])
    if run.answerer == "control":
        iters = int(run.cfg["control_lanczos_iters"])

        def job(key):
            us = probe_vectors(key, np.arange(probes), dep.n)
            return _ControlResult(run.builder.control_log_quad(dep, us, iters))
    else:
        import jax

        from repro.core import logdet_quad

        def job(key):
            return logdet_quad(dep.op, probes,
                               lam_min=dep.lam_min, lam_max=dep.lam_max,
                               max_iters=int(mix["max_iters"]),
                               rtol=float(mix["rtol"]),
                               key=jax.random.key(key))

    run.job = job
    run.job(seed32(run.seed, 2))


def prepare(run):
    pass


def _record(res, key: int, probes: int, t1: float, t2: float) -> dict:
    lo = np.asarray(res.state.probe_lower, np.float64)
    hi = np.asarray(res.state.probe_upper, np.float64)
    answered = int(np.sum(np.isfinite(lo) & np.isfinite(hi)))
    rec = {"key": key, "estimate": float(res.estimate),
           "std_error": float(res.std_error),
           "lower": float(res.lower), "upper": float(res.upper),
           "probe_lower": lo.tolist(), "probe_upper": hi.tolist(),
           "probes_answered": answered,
           "probe_iterations": np.asarray(res.state.iterations).tolist(),
           "start": t1, "done": t2, "error": None}
    rec["failed"] = answered < probes or not math.isfinite(rec["estimate"])
    return rec


def window(run):
    probes = int(run.mix["probes"])
    start = time.perf_counter()
    j = 0
    while True:
        key = seed32(run.seed, 40_000 + j)
        t1 = time.perf_counter()
        with run.span("job.logdet"):
            res = run.job(key)
        t2 = time.perf_counter()
        run.records.append(_record(res, key, probes, t1 - start, t2 - start))
        j += 1
        if t2 - start >= run.seconds:
            break


def end_to_end(run) -> dict:
    secs = sum(r["done"] - r["start"] for r in run.records)
    return {"logdet_s": secs / len(run.records)}


def release(run):
    run.job = None


def check(run) -> dict:
    """Every probe of one job drawn from the seed, and the probes of the
    other jobs that took the most iterations, against float64."""
    recs, probes = run.records, int(run.mix["probes"])
    whole = int(rng(run.seed, 8).integers(len(recs)))
    pairs = [(whole, i) for i in range(probes)]
    others = [(j, i) for j, r in enumerate(recs) if j != whole
              for i in range(len(r["probe_iterations"]))]
    others.sort(key=lambda p: -recs[p[0]]["probe_iterations"][p[1]])
    pairs += others[:int(run.mix["check_hardest"])]
    us = np.concatenate([probe_vectors(recs[j]["key"], [i], run.dep.n)
                         for j, i in pairs])
    t = time.perf_counter()
    truth = run.reference().log_quad(us)
    run.say(f"check: {len(pairs)} probes of {len(recs)} jobs against "
            f"float64 ({time.perf_counter() - t!r} s); job {whole} whole")
    return checks.job(recs, pairs, truth, whole, probes)
