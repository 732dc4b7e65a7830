"""The comparisons that decide ``correct``, and their limits.

Served cells: every request due in the window must come back with a
finite bracket (``unanswered``); a sample drawn from the seed, with the
requests that took the most iterations always in it, is compared with
the float64 reference: how many brackets miss the truth at all
(``bracket_misses``, a miss under MISS_TOL of the truth being the
reference's own rounding), by how much the worst one misses it, relative
to it (``bracket_miss``), and how many judge decisions disagree with the
truth where the truth lies farther from the threshold than that limit
(``wrong_decisions``).

Job cells: every job must return a finite bracket for every probe
(``unanswered_probes``). Every probe of one job drawn from the seed, and
the probes of the other jobs that took the most iterations, are compared
with the float64 value of their form u^T log(A) u: how many of their
brackets miss it by more than MISS_TOL of it (``probe_misses``). The drawn
job's own bracket on its probe mean must hold the float64 mean of its
probes' forms (``job_miss``, the miss relative to that mean): it is the
job's answer, deterministic, with no sampling error in it.

The limits live in ``limits/<cell>.json``, one file per cell.
"""
from __future__ import annotations

import math

import numpy as np

from chipbench.common import rng

MISS_TOL = 1e-9   # far below one float32 rounding, far above float64's


def sample_requests(records: list, size: int, hardest: int, seed: int) -> list:
    """Indices of answered records to compare: the `hardest` by
    iterations plus a seeded draw of the rest, `size` in all."""
    answered = [i for i, r in enumerate(records) if _answered(r)]
    if len(answered) <= size:
        return answered
    by_iters = sorted(answered, key=lambda i: -(records[i]["iterations"] or 0))
    pick = set(by_iters[:hardest])
    rest = [i for i in answered if i not in pick]
    pick.update(rng(seed, 7).choice(rest, size - len(pick), replace=False)
                .tolist())
    return sorted(pick)


def _answered(r: dict) -> bool:
    return (r.get("error") is None and r.get("lower") is not None
            and r.get("upper") is not None
            and math.isfinite(r["lower"]) and math.isfinite(r["upper"]))


def served(records: list, idx: list, truth: np.ndarray, limits: dict) -> dict:
    """Numbers compared for a served cell (see the module doc)."""
    unanswered = sum(1 for r in records if not _answered(r))
    lo = np.array([records[i]["lower"] for i in idx], np.float64)
    hi = np.array([records[i]["upper"] for i in idx], np.float64)
    scale = np.abs(truth)
    miss = np.maximum(0.0, np.maximum(lo - truth, truth - hi)) / scale
    tie = float(limits["bracket_miss"])
    wrong = 0
    for j, i in enumerate(idx):
        r = records[i]
        if r["t"] is None or abs(truth[j] - r["t"]) <= tie * scale[j]:
            continue
        if r["decision"] is None or bool(r["decision"]) != (r["t"] < truth[j]):
            wrong += 1
    return {"unanswered": float(unanswered),
            "bracket_misses": float(np.sum(miss > MISS_TOL)),
            "bracket_miss": float(miss.max()) if len(idx) else 0.0,
            "wrong_decisions": float(wrong)}


def job(records: list, pairs: list, truth: np.ndarray, whole: int,
        probes: int) -> dict:
    """Numbers compared for a logdet job cell (see the module doc);
    ``pairs`` are the (job, probe) of ``truth``'s entries, the probes of
    job ``whole`` among them."""
    missing = sum(probes - int(r["probes_answered"]) for r in records)

    def miss(lo, hi, t):
        if not (math.isfinite(lo) and math.isfinite(hi)):
            return math.inf
        return max(0.0, lo - t, t - hi) / abs(t)

    def probe_miss(j, i, t):
        lo, hi = records[j]["probe_lower"], records[j]["probe_upper"]
        return miss(lo[i], hi[i], t) if i < len(lo) else math.inf

    misses = sum(probe_miss(j, i, t) > MISS_TOL
                 for (j, i), t in zip(pairs, truth))
    mean = float(np.mean([t for (j, _), t in zip(pairs, truth)
                          if j == whole]))
    r = records[whole]
    return {"unanswered_probes": float(missing),
            "probe_misses": float(misses),
            "job_miss": float(miss(r["lower"], r["upper"], mean))}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): each number must be at or
    under its limit; a number with no limit is an error."""
    out, ok = {}, True
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in this cell's limits")
        limit = float(limits[name])
        out[name] = {"value": value, "limit": limit}
        ok = ok and bool(value <= limit)
    return ok, out
