"""CI smoke for the observability layer: run a tiny engine workload and
a tiny stochastic logdet under the JAX profiler, with metrics on, and
check the program's spans in the recorded trace.

Run as ``PYTHONPATH=src python -m repro.obs.smoke [outdir]``. The
profiler trace lands in ``outdir/plugins/profile/<run>/*.xplane.pb`` and
the engine's metrics snapshot in ``outdir/metrics.json``. Exits non-zero
(with a message on stderr) on any violated contract:

* the trace holds one ``bif.flush`` and, inside it, ``bif.round`` spans
  whose phases (``bif.admit``, ``bif.init``, ``bif.step``,
  ``bif.readback``, ``bif.retire``) nest within their round, and the
  job's ``trace.probes`` / ``trace.solve`` / ``trace.readback`` /
  ``trace.reduce`` spans;
* the trace's ``retrace.*`` markers match ``obs.registry``'s counts;
* the engine ``stats()`` snapshot carries the request counters and the
  percentile fields of the latency histogram.
"""
from __future__ import annotations

import glob
import json
import os
import sys
from collections import Counter
from pathlib import Path

PREFIXES = ("bif.", "trace.", "retrace.")
ROUND_PHASES = ("bif.admit", "bif.init", "bif.step", "bif.readback",
                "bif.retire")
JOB_PHASES = ("trace.probes", "trace.solve", "trace.readback",
              "trace.reduce")


def program_events(trace_dir, prefixes=PREFIXES) -> list:
    """(name, start_ns, end_ns, stats) of every host event whose name
    starts with one of ``prefixes`` in the newest profiler trace under
    ``trace_dir``, in start order."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise AssertionError(f"no profiler trace under {trace_dir}")
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events.extend((e.name, e.start_ns, e.end_ns, dict(e.stats))
                          for e in line.events
                          if e.name.startswith(prefixes))
    return sorted(events, key=lambda e: e[1])


def _within(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def run(outdir: Path) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .. import obs
    from ..core import Dense, logdet_quad
    from ..serve import BIFEngine, BIFRequest

    obs.enable()
    n = 16
    rng = np.random.default_rng(0)
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    lam = np.geomspace(1.0, 50.0, n)
    a = (q * lam) @ q.T
    a = 0.5 * (a + a.T)

    log = obs.ConvergenceLog()
    engine = BIFEngine(Dense(jnp.asarray(a)), max_batch=4, chunk_iters=4,
                       lam_min=0.99, lam_max=50.5, convergence_log=log)
    us = rng.standard_normal((6, n))
    true = np.einsum("ki,ki->k", us, np.linalg.solve(a, us.T).T)
    for i, u in enumerate(us):
        t = float(true[i] * (0.8 if i % 2 else 1.2)) if i % 3 else None
        engine.submit(BIFRequest(u=u, t=t))

    outdir.mkdir(parents=True, exist_ok=True)
    before = obs.retrace_counts()
    with jax.profiler.trace(str(outdir)):
        done = engine.flush()
        logdet_quad(Dense(jnp.asarray(a)), 4, lam_min=0.99, lam_max=50.5,
                    max_iters=n)
    after = obs.retrace_counts()
    stats = engine.stats()
    (outdir / "metrics.json").write_text(
        json.dumps(stats, indent=2, sort_keys=True), encoding="utf-8")

    events = program_events(outdir)
    names = Counter(e[0] for e in events)
    flushes = [e for e in events if e[0] == "bif.flush"]
    if len(flushes) != 1:
        raise AssertionError(f"expected one bif.flush, got {names}")
    rounds = [e for e in events if e[0] == "bif.round"]
    if not rounds or not all(_within(r, flushes[0]) for r in rounds):
        raise AssertionError("bif.round spans missing or outside bif.flush")
    for r in rounds:
        phases = [e[0] for e in events
                  if e[0] in ROUND_PHASES and _within(e, r)]
        if phases[-3:] != ["bif.step", "bif.readback", "bif.retire"] \
                or phases[0] != "bif.admit":
            raise AssertionError(f"round {r[3]}: phases {phases}")
    for phase in JOB_PHASES:
        if not names[phase]:
            raise AssertionError(f"no {phase} span in the trace")
    markers = Counter(e[0][len("retrace."):] for e in events
                      if e[0].startswith("retrace."))
    delta = {k: v - before.get(k, 0) for k, v in after.items()
             if v != before.get(k, 0)}
    if dict(markers) != delta:
        raise AssertionError(f"retrace markers {dict(markers)} != "
                             f"registry delta {delta}")

    counters = stats["counters"]
    if counters.get("requests.submitted") != len(us):
        raise AssertionError(f"submitted counter wrong: {counters}")
    if counters.get("requests.retired") != len(us):
        raise AssertionError(f"retired counter wrong: {counters}")
    lat = stats["histograms"]["request.latency_s"]
    for field in ("count", "p50", "p90", "p99"):
        if field not in lat:
            raise AssertionError(f"latency histogram missing {field!r}")
    if not all(r.resolved for r in done):
        raise AssertionError("smoke workload should fully resolve")
    if log.rounds == 0:
        raise AssertionError("convergence log recorded no rounds")

    return {"spans": dict(names), "retraces": delta,
            "counters": counters, "rounds": log.rounds, "out": str(outdir)}


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    outdir = Path(args[0]) if args else Path("obs_smoke_out")
    try:
        summary = run(outdir)
    except Exception as e:  # noqa: BLE001 - CI wants one-line verdicts
        print(f"obs smoke FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    print("obs smoke OK: "
          f"spans={summary['spans']}, retraces={summary['retraces']}, "
          f"counters={summary['counters']}, "
          f"convergence rounds={summary['rounds']} -> {summary['out']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
