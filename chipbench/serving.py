"""What the served loops share: the system under test (the program's
``BIFEngine``, or the control in its place), warm-up, the judges'
threshold, request records and the comparison with the reference."""
from __future__ import annotations

import gc
from collections import defaultdict

import numpy as np

from chipbench import checks
from chipbench.common import seed32

CONTROL_CHUNK = 512   # lanes of one control solve (a fixed shape)


class ControlEngine:
    """The reference in a lower precision, put in the engine's place: a
    point answer [v, v] per request from the configuration's
    ``builders/<builder>.py`` ``control_quad``."""

    def __init__(self, run):
        self.run = run
        self._queue = []

    def submit(self, req):
        self._queue.append(req)
        return req

    def pending(self) -> int:
        return len(self._queue)

    def flush(self):
        queue, self._queue = self._queue, []
        if not queue:
            return queue
        us = np.stack([np.asarray(r.u, np.float32) for r in queue])
        vals = []
        for s in range(0, len(us), CONTROL_CHUNK):
            blk = us[s:s + CONTROL_CHUNK]
            pad = CONTROL_CHUNK - len(blk)
            if pad:
                blk = np.concatenate([blk, np.zeros((pad, us.shape[1]),
                                                    np.float32)])
            v = self.run.builder.control_quad(self.run.dep, blk,
                                              int(self.run.cfg["control_cg_iters"]))
            vals.append(v[:CONTROL_CHUNK - pad])
        for r, v in zip(queue, np.concatenate(vals)):
            r.lower = r.upper = float(v)
            r.decision = None if r.t is None else bool(r.t < v)
            r.resolved, r.certified, r.iterations = True, True, 0
        return queue

    def stats(self) -> dict:
        return {}

    def reset_stats(self) -> None:
        pass


def make_engine(run):
    if run.answerer == "control":
        return ControlEngine(run)
    from repro.core import BIFSolver
    from repro.serve import BIFEngine

    cfg = run.cfg
    # the deployment fixes no backend: the library's default serves
    solver = BIFSolver.create(max_iters=int(cfg["max_iters"]),
                              rtol=float(cfg["rtol"]))
    return BIFEngine(run.dep.op, solver=solver,
                     max_batch=int(cfg["max_batch"]),
                     chunk_iters=int(cfg["chunk_iters"]),
                     lam_min=run.dep.lam_min, lam_max=run.dep.lam_max)


def request(u, t):
    from repro.serve import BIFRequest

    return BIFRequest(u=u, t=t)


def warm(run, engine, extra_query_sizes=()):
    """One warm-up flush of two pools' worth of mixed requests, and the
    query generator at every size the window draws."""
    m = 2 * int(run.cfg["max_batch"])
    us = run.dep.queries(seed32(run.seed, 2), m)
    for i, u in enumerate(us):
        engine.submit(request(u, None if i % 4 == 3 else 1.0))
    engine.flush()
    for size in extra_query_sizes:
        run.dep.queries(seed32(run.seed, 2), size)
    engine.reset_stats()


def thresholds(run):
    """The judges' t: the median of a seeded presample of float64 truths.
    The reference's cost is the benchmark's and stays out of setup_s."""
    n = int(run.mix["threshold_presample"])
    with run.excluded("reference factorisation and threshold presample"):
        truth = run.reference().quad(run.dep.queries(seed32(run.seed, 1), n))
        run.threshold = float(np.median(truth))
    run.say(f"threshold: median of {n} float64 truths = {run.threshold!r}")


def record(req, *, judge: bool, qkey: int, rows: int, row: int, due: float,
           submit: float, done: float) -> dict:
    failed = req.error is not None or not req.resolved
    return {"t": req.t if judge else None, "qkey": qkey, "rows": rows,
            "row": row, "due": due, "submit": submit, "done": done,
            "lower": req.lower, "upper": req.upper,
            "decision": req.decision, "resolved": bool(req.resolved),
            "iterations": req.iterations,
            "error": None if req.error is None else repr(req.error),
            "failed": failed}


def check(run) -> dict:
    """Compare a seeded sample of the window's answers with the float64
    reference (queries are drawn again from their keys)."""
    mix = run.mix
    idx = checks.sample_requests(run.records, int(mix["check_sample"]),
                                 int(mix["check_hardest"]), run.seed)
    groups = defaultdict(list)
    for j, i in enumerate(idx):
        r = run.records[i]
        groups[(r["qkey"], r["rows"])].append((j, r["row"]))
    us = np.zeros((len(idx), run.dep.n), np.float32)
    for (qkey, rows), members in groups.items():
        block = run.dep.queries(qkey, rows)
        for j, row in members:
            us[j] = block[row]
    truth = run.reference().quad(us) if len(idx) else np.zeros(0)
    run.say(f"check: {len(idx)} of {len(run.records)} requests compared")
    return checks.served(run.records, idx, truth, run.limits)


def release(run):
    run.engine = None
    gc.collect()
