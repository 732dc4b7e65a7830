"""Closed loop of candidate sets: ``batch`` queries are generated,
submitted together and flushed; the next set follows when the flush
returns. The window closes at the end of the flush that is running when
``--seconds`` have elapsed.

End-to-end: ``decisions_per_s`` = resolved requests in the window's
flushes / the total time of those flushes (submit to return).
"""
from __future__ import annotations

import time

from chipbench import serving, traffic_gen as traffic
from chipbench.common import seed32

KIND = "serve"


def setup(run):
    run.engine = serving.make_engine(run)
    serving.warm(run, run.engine, extra_query_sizes=(int(run.mix["batch"]),))


def prepare(run):
    serving.thresholds(run)


def window(run):
    mix, dep, eng = run.mix, run.dep, run.engine
    batch = int(mix["batch"])
    run.flushes = []
    start = time.perf_counter()
    b = 0
    while True:
        qkey = seed32(run.seed, 10_000 + b)
        with run.span("client.generate"):
            us = dep.queries(qkey, batch)
            judges = traffic.judge_mask(mix, batch, run.seed, 20_000 + b)
            reqs = [serving.request(u, run.threshold if j else None)
                    for u, j in zip(us, judges)]
        t1 = time.perf_counter()
        with run.span("client.submit"):
            for r in reqs:
                eng.submit(r)
        with run.span("engine.flush"):
            eng.flush()
        t2 = time.perf_counter()
        for row, (r, j) in enumerate(zip(reqs, judges)):
            run.records.append(serving.record(
                r, judge=bool(j), qkey=qkey, rows=batch, row=row,
                due=t1 - start, submit=t1 - start, done=t2 - start))
        run.flushes.append((t2 - t1, sum(1 for r in reqs if r.resolved)))
        b += 1
        if t2 - start >= run.seconds:
            break
    run.stats = eng.stats()


def end_to_end(run) -> dict:
    secs = sum(d for d, _ in run.flushes)
    return {"decisions_per_s": sum(n for _, n in run.flushes) / secs}


def release(run):
    serving.release(run)


def check(run) -> dict:
    return serving.check(run)
