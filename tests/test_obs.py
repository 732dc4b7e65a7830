"""Observability layer (DESIGN.md Sec. 14).

1. Metric primitives: counters/gauges, log-bucket histograms whose
   p50/p90/p99 are EXACT (nearest-rank, pinned against numpy's
   ``inverted_cdf``), global enable gate, snapshot shape.
2. Spans: profiler annotations, recorded only inside a profiler
   session, nested on the profiler's clock; the engine's ``bif.*`` and
   the trace job's ``trace.*`` spans nest in order, the ``retrace.*``
   markers match the registry, and no program span takes a name the
   chip benchmark's harness uses for its own spans.
3. Engine request metrics: a scripted mixed workload (judges, brackets,
   an expired-deadline request) produces exactly the expected counter
   ledger and histogram populations.
4. Convergence logs are bit-exact mirrors of the returned brackets on
   both the ``trace`` and ``step_n`` paths.
5. Health: the Thm. 4.2 monitor flags the documented reorth-off
   failure mode (kappa=1000 Krylov exhaustion, paper Sec. 5.4) and
   stays silent on healthy reorth=True runs across kappa.
6. THE invariant everything above rests on: telemetry never changes
   results — metrics on vs off, and the profiler on vs off, is
   bit-identical across an engine conformance grid (the sharded twin
   lives in tests/sharded_check.py::check_engine_stats_parity).
"""
import importlib.util
import math
import time
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import BIFSolver, Dense, logdet_quad, sparse_from_dense
from repro.obs.health import check_contraction
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.smoke import program_events
from repro.serve import BIFEngine, BIFRequest
from conftest import make_spd


@pytest.fixture(autouse=True)
def _obs_defaults():
    """Every test starts from (and restores) the shipped default:
    metrics on."""
    obs.metrics.set_enabled(True)
    yield
    obs.metrics.set_enabled(True)


# -- 1. metric primitives ---------------------------------------------------

def test_counter_gauge_and_snapshot_shape():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    reg.gauge("g").set(2.5)
    reg.histogram("h").observe(0.125)
    snap = reg.snapshot()
    assert snap["counters"] == {"c": 5}
    assert snap["gauges"] == {"g": 2.5}
    assert snap["histograms"]["h"]["count"] == 1
    # get-or-create returns the SAME object; a kind collision is an error
    assert reg.counter("c") is reg.counter("c")
    with pytest.raises(TypeError):
        reg.gauge("c")
    reg.reset()
    assert reg.snapshot()["counters"] == {"c": 0}


def test_histogram_percentiles_are_exact_nearest_rank():
    rng = np.random.default_rng(0)
    samples = np.concatenate([
        rng.lognormal(mean=-3.0, sigma=2.0, size=257),
        [0.0, -1.0, 5e-12, 3e7],  # under/overflow buckets
    ])
    h = Histogram("lat")
    for v in samples:
        h.observe(float(v))
    for q in (50.0, 90.0, 99.0, 1.0, 100.0):
        assert h.percentile(q) == float(
            np.percentile(samples, q, method="inverted_cdf")), q
    snap = h.snapshot()
    assert snap["count"] == len(samples)
    assert snap["min"] == samples.min() and snap["max"] == samples.max()
    np.testing.assert_allclose(snap["mean"], samples.mean(), rtol=1e-12)
    for q in (50, 90, 99):
        assert snap[f"p{q}"] == float(
            np.percentile(samples, q, method="inverted_cdf"))
    # bucket counts cover every observation exactly once
    assert sum(c for _, c in snap["buckets"]) == len(samples)
    with pytest.raises(ValueError):
        h.percentile(0.0)


def test_histogram_empty_snapshot_is_nan_not_crash():
    snap = Histogram("e").snapshot()
    assert snap["count"] == 0 and snap["buckets"] == []
    assert math.isnan(snap["p50"]) and math.isnan(snap["mean"])
    assert math.isnan(Histogram("e2").percentile(99.0))


def test_metrics_global_gate_stops_writes_not_reads():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    obs.metrics.set_enabled(False)
    reg.counter("c").inc(100)
    reg.gauge("g").set(9.0)
    reg.histogram("h").observe(1.0)
    snap = reg.snapshot()  # reads still work
    assert snap["counters"]["c"] == 1
    assert snap["gauges"]["g"] == 0.0
    assert snap["histograms"]["h"]["count"] == 0
    obs.metrics.set_enabled(True)
    reg.counter("c").inc()
    assert reg.counter("c").value == 2


# -- 2. spans ---------------------------------------------------------------

def test_spans_off_by_default_nest_and_validate_schema(tmp_path):
    """A span records nothing outside a profiler session; inside one it
    is a host event on the profiler's clock, nested by containment,
    with its arguments as event stats."""
    with obs.span("bif.test.dead"):
        pass
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("bif.test.outer", mode="test"):
            with obs.span("bif.test.inner"):
                time.sleep(0.002)
    events = program_events(tmp_path, ("bif.test.",))
    assert [e[0] for e in events] == ["bif.test.outer", "bif.test.inner"]
    outer, inner = events
    assert outer[3] == {"mode": "test"}
    assert outer[1] <= inner[1] and inner[2] <= outer[2]
    assert inner[2] - inner[1] >= 2_000_000  # the 2 ms sleep, in ns


def test_span_records_error_annotation(tmp_path):
    """An exception leaves through the span, which still closes and is
    recorded."""
    with jax.profiler.trace(str(tmp_path)):
        with pytest.raises(RuntimeError):
            with obs.span("bif.test.boom"):
                raise RuntimeError("x")
        with obs.span("bif.test.after"):
            pass
    (boom, after) = program_events(tmp_path, ("bif.test.",))
    assert boom[0] == "bif.test.boom" and after[0] == "bif.test.after"
    assert boom[2] <= after[1]


def _within(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _traced_engine_and_job(tmp_path):
    """A continuous flush whose budget-capped request comes back partial
    and is resubmitted (warm admission), a lockstep flush and a small
    logdet job, all under one profiler session."""
    a, us, true, lam = _engine_problem(n=24, kappa=50.0, seed=3, k=7)
    op = Dense(jnp.asarray(a))
    eng = BIFEngine(op, solver=BIFSolver.create(max_iters=26, rtol=1e-6),
                    max_batch=3, chunk_iters=2, **lam)
    before = obs.retrace_counts()
    with jax.profiler.trace(str(tmp_path)):
        reqs = [eng.submit(BIFRequest(
            u=u, t=float(true[i] * 1.1) if i % 2 else None,
            max_iters=3 if i == 0 else None)) for i, u in enumerate(us)]
        eng.flush()
        assert reqs[0].resolved is False and reqs[0].state is not None
        eng.submit(reqs[0])
        eng.flush()
        for u in us[:4]:
            eng.submit(BIFRequest(u=u))
        eng.flush(mode="lockstep")
        logdet_quad(op, 6, lam_min=lam["lam_min"], lam_max=lam["lam_max"],
                    max_iters=24, probe_chunk=3)
    after = obs.retrace_counts()
    delta = {k: v - before.get(k, 0) for k, v in after.items()
             if v != before.get(k, 0)}
    return program_events(tmp_path), delta


def test_engine_and_job_spans_nest_in_order(tmp_path):
    events, _ = _traced_engine_and_job(tmp_path)
    spans = [e for e in events if not e[0].startswith("retrace.")]
    flushes = [e for e in spans if e[0] == "bif.flush"]
    assert [f[3]["flush"] for f in flushes] == [1, 2, 3]
    for e in spans:
        if e[0].startswith("bif.") and e[0] != "bif.flush":
            assert any(_within(e, f) for f in flushes), e
    rounds = [e for e in spans if e[0] == "bif.round"]
    by_flush = Counter(r[3]["flush"] for r in rounds)
    assert set(by_flush) == {1, 2}  # the lockstep flush has no rounds
    for f in flushes[:2]:
        mine = [r for r in rounds if _within(r, f)]
        assert all(r[3]["flush"] == f[3]["flush"] for r in mine)
        assert [r[3]["round"] for r in mine] == list(range(len(mine)))
    order = ("bif.admit", "bif.init", "bif.scatter", "bif.step",
             "bif.readback", "bif.retire")
    seen = Counter()
    for r in rounds:
        assert 1 <= r[3]["occupied"] <= 3
        phases = [e for e in spans if e[0] in order and _within(e, r)]
        names = [e[0] for e in phases]
        seen.update(names)
        assert names[0] == "bif.admit", names
        assert names[-3:] == ["bif.step", "bif.readback", "bif.retire"]
        assert [order.index(x) for x in names] == sorted(
            order.index(x) for x in names), names
        for x, y in zip(phases, phases[1:]):
            assert x[2] <= y[1]  # siblings do not overlap
    assert seen["bif.init"] >= 2 and seen["bif.scatter"] == 1
    lock = [e[0] for e in spans if _within(e, flushes[2])
            and e is not flushes[2]]
    assert lock == ["bif.admit", "bif.step", "bif.readback", "bif.retire",
                    "bif.admit", "bif.step", "bif.readback", "bif.retire"]
    job = [e[0] for e in spans if e[0].startswith("trace.")]
    assert job == ["trace.probes", "trace.solve", "trace.readback"] * 2 \
        + ["trace.reduce"]


def test_retrace_markers_match_registry_delta(tmp_path):
    events, delta = _traced_engine_and_job(tmp_path)
    markers = Counter(e[0][len("retrace."):] for e in events
                      if e[0].startswith("retrace."))
    assert dict(markers) == delta
    # each probe chunk of a job is one cached compiled call: a second
    # job in the same process, with a new key, traces nothing
    a, _, _, lam = _engine_problem(n=24, kappa=50.0, seed=3, k=7)
    before = obs.retrace_counts()
    with jax.profiler.trace(str(tmp_path / "again")):
        logdet_quad(Dense(jnp.asarray(a)), 6, lam_min=lam["lam_min"],
                    lam_max=lam["lam_max"], max_iters=24, probe_chunk=3,
                    key=jax.random.key(5))
    assert obs.retrace_counts() == before
    again = program_events(tmp_path / "again")
    assert [e[0] for e in again if e[0].startswith("retrace.")] == []
    assert [e[0] for e in again if e[0].startswith("trace.")]


def test_program_span_names_avoid_harness_spans(tmp_path):
    """The chip benchmark's trace reduction attributes idle time to its
    own host spans by name: a program span with one of those names
    would be counted twice."""
    path = Path(__file__).resolve().parent.parent / "chipbench" / "common.py"
    spec = importlib.util.spec_from_file_location("_bench_common", path)
    common = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(common)
    events, _ = _traced_engine_and_job(tmp_path)
    names = {e[0] for e in events}
    assert {"bif.flush", "bif.round", "bif.scatter", "trace.solve"} <= names
    assert not names & set(common.SPANS)


# -- 3. engine request metrics ---------------------------------------------

def _engine_problem(n=32, kappa=60.0, seed=2, k=9):
    a = make_spd(n, kappa=kappa, seed=seed)
    w = np.linalg.eigvalsh(a)
    lam = dict(lam_min=float(w[0] * 0.99), lam_max=float(w[-1] * 1.01))
    us = np.random.default_rng(seed + 1).standard_normal((k, n))
    true = np.einsum("ki,ki->k", us, np.linalg.solve(a, us.T).T)
    return a, us, true, lam


def test_engine_stats_scripted_mixed_workload():
    a, us, true, lam = _engine_problem()
    n, k = a.shape[0], len(us)
    engine = BIFEngine(Dense(jnp.asarray(a)),
                       solver=BIFSolver.create(max_iters=n + 2, rtol=1e-4),
                       max_batch=4, chunk_iters=3, **lam)
    for i, u in enumerate(us):
        t = float(true[i] * (0.8 if i % 2 else 1.2)) if i % 3 else None
        engine.submit(BIFRequest(u=u, t=t, deadline=time.monotonic() + 60.0))
    # one request whose deadline already passed: retired at the door,
    # zero iterations, queue-wait still recorded
    dead = engine.submit(BIFRequest(u=us[0],
                                    deadline=time.monotonic() - 1.0))
    out = engine.flush()
    assert len(out) == k + 1
    assert dead.resolved is False and dead.iterations == 0

    s = engine.stats()
    c = s["counters"]
    assert c["requests.submitted"] == k + 1
    assert c["requests.retired"] == k + 1
    assert c["requests.resolved"] == k
    assert c["requests.partial"] == 1
    assert c["requests.expired"] == 1
    assert c["flush.count"] == 1
    assert c["flush.rounds"] >= math.ceil(k / 4)
    assert "requests.errored" not in c  # nothing failed

    h = s["histograms"]
    # queue-wait covers EVERY retirement, including the expired-at-door
    # one; admission-to-retire latency only the k admitted requests
    assert h["request.queue_wait_s"]["count"] == k + 1
    assert h["request.latency_s"]["count"] == k
    for field in ("p50", "p90", "p99", "mean", "min", "max"):
        assert field in h["request.latency_s"]
    assert h["request.latency_s"]["p99"] >= h["request.latency_s"]["p50"]
    # every request carried a deadline; slack is negative for the dead one
    assert h["request.deadline_slack_s"]["count"] == k + 1
    assert h["request.deadline_slack_s"]["min"] < 0.0
    assert h["request.iterations"]["count"] == k + 1
    assert h["request.iterations"]["min"] == 0.0  # the expired request
    occ = h["pool.occupancy"]
    assert occ["count"] == c["flush.rounds"] and occ["max"] <= 1.0

    engine.reset_stats()
    assert engine.stats()["counters"]["requests.submitted"] == 0


def test_engine_stats_count_errored_requests():
    a, us, _, lam = _engine_problem(k=3)
    engine = BIFEngine(Dense(jnp.asarray(a)), max_batch=2, **lam)
    for u in us:
        engine.submit(BIFRequest(u=u))

    class _Boom(Exception):
        pass

    orig = engine._step

    def boom(*a_, **k_):
        raise _Boom()

    engine._step = boom
    try:
        with pytest.raises(_Boom):
            engine.flush()
    finally:
        engine._step = orig
    assert engine.stats()["counters"]["requests.errored"] >= 1


def test_retrace_registry_feeds_flush_trace_count():
    from repro.serve.engine import flush_trace_count
    a, us, _, lam = _engine_problem(k=3)
    before_total = flush_trace_count()
    before = dict(obs.retrace_counts())
    engine = BIFEngine(Dense(jnp.asarray(a)), max_batch=4, chunk_iters=4,
                       **lam)
    for u in us:
        engine.submit(BIFRequest(u=u))
    engine.flush()
    after = obs.retrace_counts()
    grown = {k_: v - before.get(k_, 0) for k_, v in after.items()
             if v != before.get(k_, 0)}
    assert grown, "an engine flush must register at least one trace"
    assert all(k_.startswith("serve.engine.") for k_ in grown)
    # the legacy counter is a pure view over the registry
    assert flush_trace_count() - before_total == sum(
        v for k_, v in grown.items()
        if k_.split(".")[-1] in ("pool_admit", "pool_scatter", "pool_step",
                                 "flush"))


# -- 4. convergence logs mirror returned brackets bit-exactly ---------------

def test_convergence_log_matches_trace_bit_exact():
    n, kappa = 48, 100.0
    a = make_spd(n, kappa=kappa, seed=0)
    u = np.random.default_rng(1).standard_normal(n)
    solver = BIFSolver.create(max_iters=n, reorth=True)
    kw = dict(lam_min=1.0 / kappa * 0.999, lam_max=1.001)
    op = Dense(jnp.asarray(a))

    log = obs.ConvergenceLog()
    tr = solver.trace(op, jnp.asarray(u), n - 2, convergence_log=log, **kw)
    assert log.rounds == n - 2
    np.testing.assert_array_equal(log.lowers()[:, 0],
                                  np.asarray(tr.radau_lower))
    np.testing.assert_array_equal(log.uppers()[:, 0],
                                  np.asarray(tr.radau_upper))
    np.testing.assert_array_equal(log.its()[:, 0], np.arange(1, n - 1))
    # passing a log never perturbs the trace itself
    tr2 = solver.trace(op, jnp.asarray(u), n - 2, **kw)
    np.testing.assert_array_equal(np.asarray(tr.radau_lower),
                                  np.asarray(tr2.radau_lower))
    np.testing.assert_array_equal(np.asarray(tr.radau_upper),
                                  np.asarray(tr2.radau_upper))


def test_convergence_log_matches_step_n_states_bit_exact():
    n = 40
    a = make_spd(n, kappa=50.0, seed=3)
    u = np.random.default_rng(4).standard_normal(n)
    solver = BIFSolver.create(max_iters=n, rtol=1e-10)
    log = obs.ConvergenceLog()
    state = solver.init_state(Dense(jnp.asarray(a)), jnp.asarray(u),
                              lam_min=0.01, lam_max=1.1)
    ref = solver.init_state(Dense(jnp.asarray(a)), jnp.asarray(u),
                            lam_min=0.01, lam_max=1.1)
    for _ in range(4):
        state = solver.step_n(state, 5, convergence_log=log)
        ref = solver.step_n(ref, 5)
        lo, hi = state.bracket()
        np.testing.assert_array_equal(log.lowers()[-1],
                                      np.atleast_1d(np.asarray(lo)))
        np.testing.assert_array_equal(log.uppers()[-1],
                                      np.atleast_1d(np.asarray(hi)))
        # and the logged run IS the unlogged run, bit for bit
        rlo, rhi = ref.bracket()
        np.testing.assert_array_equal(np.asarray(lo), np.asarray(rlo))
        np.testing.assert_array_equal(np.asarray(hi), np.asarray(rhi))
    assert log.rounds == 4
    assert int(log.its()[-1, 0]) == int(np.asarray(state.it))


def test_convergence_log_rejects_shape_drift():
    log = obs.ConvergenceLog()
    log.record([1.0, 1.0], [2.0, 2.0], 1)
    with pytest.raises(ValueError):
        log.record([1.0], [2.0], 2)
    with pytest.raises(ValueError):
        log.record([1.0, 1.0], [2.0], 2)


# -- 5. convergence health -------------------------------------------------

def _health_report(kappa, *, reorth, n=64, seed=0):
    """The canonical convergence-pin setup (tests/test_convergence.py)."""
    a = make_spd(n, kappa=kappa, seed=seed)
    u = np.random.default_rng(seed + 1).standard_normal(n)
    solver = BIFSolver.create(max_iters=n, reorth=reorth)
    mon = obs.ContractionMonitor(1.0 / kappa * 0.999, 1.001, dim=n)
    solver.trace(Dense(jnp.asarray(a)), jnp.asarray(u), n - 2,
                 lam_min=1.0 / kappa * 0.999, lam_max=1.001,
                 convergence_log=mon.log)
    return mon.report()


def test_health_flags_reorth_off_instability_kappa_1000():
    """Paper Sec. 5.4: without reorthogonalization the kappa=1000 trace
    exhausts the Krylov dimension with the gap stuck ~1e-6 relative —
    orders of magnitude above the reorth=True floor. The monitor must
    flag it, and the exhaustion check is the signal that fires."""
    rep = _health_report(1000.0, reorth=False)
    assert not rep.ok
    assert bool(rep.unresolved[0])
    assert rep.last_rel_gap[0] > 1e-8  # the gap really is open
    # the early contraction is NOT the tell — finite-precision Lanczos
    # keeps the theorem rate while losing orthogonality
    assert rep.max_window_rate[0] <= rep.bound * 1.15


def test_health_silent_on_healthy_reorth_runs():
    for kappa in (10.0, 100.0, 1000.0):
        rep = _health_report(kappa, reorth=True)
        assert rep.ok, (kappa, rep)
        assert not rep.slow.any() and not rep.stalled.any() \
            and not rep.unresolved.any()
        # healthy runs finish below the floor
        assert rep.last_rel_gap[0] <= 1e-8, kappa


def test_health_rate_bound_and_edge_cases():
    assert obs.rate_bound(1.0, 1.0) == 0.0
    k = 100.0
    assert np.isclose(obs.rate_bound(1.0 / k, 1.0),
                      ((np.sqrt(k) - 1) / (np.sqrt(k) + 1)) ** 2)
    with pytest.raises(ValueError):
        obs.rate_bound(-1.0, 2.0)
    with pytest.raises(ValueError):
        obs.rate_bound(2.0, 1.0)
    # short logs report, never crash
    log = obs.ConvergenceLog()
    rep = check_contraction(log, 0.1, 1.0)
    assert rep.ok and rep.fitted_rate.shape == (0,)
    log.record(1.0, 2.0, 1)
    rep = check_contraction(log, 0.1, 1.0, dim=4)
    assert rep.ok


def test_health_resolved_mask_and_stall_flag():
    log = obs.ConvergenceLog()
    # lane 0 plateaus while live; lane 1 converges geometrically
    for t in range(12):
        log.record([1e-3 * 0.999 ** t, 4.0 * 0.25 ** t],
                   [2e-3 * 0.999 ** t, 8.0 * 0.25 ** t],
                   t + 1)
    rep = check_contraction(log, 1.0 / 100.0, 1.0, window=4)
    assert bool(rep.stalled[0]) and not bool(rep.stalled[1])
    assert bool(rep.flagged[0]) and not bool(rep.flagged[1])
    # a resolved mask silences lanes that finished for non-gap reasons
    rep2 = check_contraction(log, 1.0 / 100.0, 1.0, window=4,
                             resolved=[True, False])
    assert rep2.ok


# -- 6. telemetry is bit-invariant -----------------------------------------

@pytest.mark.parametrize("profiler", [False, True],
                         ids=["no_profiler", "profiler"])
@pytest.mark.parametrize("op_kind", ["dense", "coo"])
def test_engine_results_bit_identical_metrics_on_vs_off(op_kind, profiler,
                                                        tmp_path):
    """The conformance grid: mixed judge/bracket traffic, masked lanes,
    continuous + lockstep modes — every discrete outcome AND every
    bracket float must be bit-identical with telemetry fully on
    (metrics + convergence log, and with ``profiler`` the spans recorded
    in an active profiler session) vs fully off."""
    a, us, true, lam = _engine_problem(n=28, kappa=40.0, seed=5, k=7)
    n = a.shape[0]
    op = Dense(jnp.asarray(a)) if op_kind == "dense" \
        else sparse_from_dense(a)
    sv = BIFSolver.create(max_iters=n + 2, rtol=1e-4)
    mask = (np.random.default_rng(6).random(n) < 0.5).astype(float)

    def run(metrics_on, mode):
        if metrics_on:
            obs.enable()
            clog = obs.ConvergenceLog()
        else:
            obs.disable()
            clog = None
        try:
            eng = BIFEngine(op, solver=sv, max_batch=4, chunk_iters=3,
                            metrics=metrics_on, convergence_log=clog,
                            **lam)
            for i, u in enumerate(us):
                t = float(true[i] * (0.9 if i % 2 else 1.1)) \
                    if i % 3 else None
                eng.submit(BIFRequest(u=u, t=t,
                                      mask=mask if i == len(us) - 1
                                      else None))
            if metrics_on and profiler:
                with jax.profiler.trace(str(tmp_path / mode)):
                    out = eng.flush(mode=mode)
            else:
                out = eng.flush(mode=mode)
        finally:
            obs.metrics.set_enabled(True)
        return eng, out

    for mode in ("continuous", "lockstep"):
        eng_on, on = run(True, mode)
        eng_off, off = run(False, mode)
        for i, (x, y) in enumerate(zip(on, off)):
            assert x.decision == y.decision, (mode, i)
            assert x.certified == y.certified, (mode, i)
            assert x.iterations == y.iterations, (mode, i)
            assert x.resolved == y.resolved, (mode, i)
            assert (x.lower, x.upper) == (y.lower, y.upper), (mode, i)
        # ... and the telemetry really was on/off respectively
        assert eng_on.stats()["counters"]["requests.submitted"] == len(us)
        assert eng_off.stats() == {"counters": {}, "gauges": {},
                                   "histograms": {}}
        if mode == "continuous":
            assert eng_on.convergence_log.rounds > 0
        if profiler:
            names = {e[0] for e in program_events(tmp_path / mode)}
            assert {"bif.flush", "bif.step", "bif.readback"} <= names


def test_solver_paths_bit_identical_with_and_without_log():
    n, kappa = 32, 80.0
    a = make_spd(n, kappa=kappa, seed=7)
    u = np.random.default_rng(8).standard_normal(n)
    kw = dict(lam_min=1.0 / kappa * 0.999, lam_max=1.001)
    for reorth in (False, True):
        solver = BIFSolver.create(max_iters=n, reorth=reorth)
        t1 = solver.trace(Dense(jnp.asarray(a)), jnp.asarray(u), 12,
                          convergence_log=obs.ConvergenceLog(), **kw)
        t2 = solver.trace(Dense(jnp.asarray(a)), jnp.asarray(u), 12, **kw)
        for f in ("gauss", "radau_lower", "radau_upper", "lobatto"):
            np.testing.assert_array_equal(np.asarray(getattr(t1, f)),
                                          np.asarray(getattr(t2, f)),
                                          err_msg=f"{reorth} {f}")
