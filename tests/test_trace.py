"""Stochastic trace estimation (core/trace.py) + the logdet workloads
(dpp.log_likelihood, train.monitor.logdet_bounds), DESIGN.md Sec. 9.

Oracles are dense eigendecompositions / ``slogdet`` throughout: exact
unit-probe runs must bracket the TRUE trace deterministically; the
Hutchinson runs must bracket the probe-sample mean (recomputed here
from the identical reproducible probe stream) with the statistical
interval containing the truth.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import BIFSolver, Dense, Masked, sparse_from_dense, \
    trace_quad, logdet_quad, log_likelihood
from repro.core.trace import _probe_chunk, _rademacher_probe, _solve_chunk
from repro.train.monitor import logdet_bounds
from conftest import make_spd


def _problem(n=24, kappa=50.0, seed=0):
    a = make_spd(n, kappa=kappa, seed=seed)
    w, v = np.linalg.eigh(a)
    return a, w, v, float(w[0] * 0.99), float(w[-1] * 1.01)


@pytest.mark.parametrize("fn,f", [("log", np.log),
                                  ("invsqrt", lambda x: x ** -0.5),
                                  ("inv", lambda x: 1.0 / x)])
@pytest.mark.parametrize("op_kind", ["dense", "sparse_coo"])
def test_exact_probes_bracket_true_trace(fn, f, op_kind):
    a, w, _, lmn, lmx = _problem()
    op = Dense(jnp.asarray(a)) if op_kind == "dense" \
        else sparse_from_dense(a)
    true = float(np.sum(f(w)))
    r = trace_quad(op, fn, None, lam_min=lmn, lam_max=lmx)
    scale = max(abs(true), 1.0)
    assert r.lower <= true + 1e-8 * scale
    assert r.upper >= true - 1e-8 * scale
    assert r.upper - r.lower <= 1e-3 * scale
    # exact mode: no sampling error, stat interval == det bracket
    assert r.std_error == 0.0
    assert (r.stat_lower, r.stat_upper) == (r.lower, r.upper)
    assert r.num_probes == a.shape[0]


def test_hutchinson_brackets_probe_sample_mean():
    a, w, v, lmn, lmx = _problem(seed=3)
    n = a.shape[0]
    key = jax.random.key(7)
    r = trace_quad(Dense(jnp.asarray(a)), "log", 8, lam_min=lmn,
                   lam_max=lmx, key=key)
    # recompute the identical probes from the reproducible stream
    vals = []
    for i in range(8):
        z = np.asarray(_rademacher_probe(key, i, n, np.float64))
        c = v.T @ z
        vals.append(float(np.sum(c * c * np.log(w))))
    sample_mean = float(np.mean(vals))
    assert r.lower <= sample_mean <= r.upper
    # the per-probe brackets each contain their probe's true value
    for i, val in enumerate(vals):
        assert r.state.probe_lower[i] <= val <= r.state.probe_upper[i]
    # the statistical interval covers the true trace here
    true = float(np.sum(np.log(w)))
    assert r.stat_lower <= true <= r.stat_upper
    assert r.std_error > 0.0


def test_probe_by_probe_resume_matches_direct():
    a, _, _, lmn, lmx = _problem(seed=5)
    op = sparse_from_dense(a)
    key = jax.random.key(11)
    r8 = trace_quad(op, "log", 8, lam_min=lmn, lam_max=lmx, key=key)
    r16 = trace_quad(op, "log", 16, lam_min=lmn, lam_max=lmx, key=key,
                     state=r8.state)
    direct = trace_quad(op, "log", 16, lam_min=lmn, lam_max=lmx, key=key)
    # SparseCOO lanes are bit-exact across batch shapes, so resumed ==
    # direct exactly (probes 0..7 reuse the banked brackets)
    assert (r16.lower, r16.upper) == (direct.lower, direct.upper)
    assert r16.iterations == direct.iterations
    np.testing.assert_array_equal(r16.state.probe_lower,
                                  direct.state.probe_lower)
    # chunked probe batches accumulate the same estimate
    chunked = trace_quad(op, "log", 16, lam_min=lmn, lam_max=lmx,
                         key=key, probe_chunk=4)
    np.testing.assert_array_equal(chunked.state.probe_lower,
                                  direct.state.probe_lower)
    # guardrails
    with pytest.raises(ValueError, match="resume state banks"):
        trace_quad(op, "invsqrt", 16, lam_min=lmn, lam_max=lmx, key=key,
                   state=r8.state)
    with pytest.raises(ValueError, match="can only extend"):
        trace_quad(op, "log", 4, lam_min=lmn, lam_max=lmx, key=key,
                   state=r8.state)
    with pytest.raises(ValueError, match="num_probes"):
        trace_quad(op, "log", 0, lam_min=lmn, lam_max=lmx)
    with pytest.raises(ValueError, match="different key"):
        trace_quad(op, "log", 16, lam_min=lmn, lam_max=lmx,
                   key=jax.random.key(99), state=r8.state)
    with pytest.raises(ValueError, match="spectral interval"):
        trace_quad(op, "log", 16, lam_min=lmn * 0.5, lam_max=lmx,
                   key=key, state=r8.state)


def test_block_probe_extend_matches_direct_bit_exact():
    """The block-mode twin of the scalar extend pin: resuming with a
    larger ``num_probes`` under ``block_size > 1`` adds WHOLE blocks and
    keeps the banked probe stream bit-identical (probe i is still
    ``fold_in(key, i)``; blocks are consecutive index groups)."""
    a, w, _, lmn, lmx = _problem(seed=5)
    op = sparse_from_dense(a)
    key = jax.random.key(11)
    kw = dict(lam_min=lmn, lam_max=lmx, key=key, block_size=4)
    r8 = trace_quad(op, "log", 8, **kw)
    assert len(r8.state.probe_lower) == 2          # 2 banked block lanes
    r16 = trace_quad(op, "log", 16, state=r8.state, **kw)
    direct = trace_quad(op, "log", 16, **kw)
    # SparseCOO lanes are bit-exact across batch shapes, so resumed ==
    # direct exactly (blocks 0..1 reuse the banked lane brackets)
    assert (r16.lower, r16.upper) == (direct.lower, direct.upper)
    assert (r16.estimate, r16.std_error) == (direct.estimate,
                                             direct.std_error)
    assert r16.iterations == direct.iterations
    np.testing.assert_array_equal(r16.state.probe_lower,
                                  direct.state.probe_lower)
    np.testing.assert_array_equal(r16.state.probe_upper,
                                  direct.state.probe_upper)
    np.testing.assert_array_equal(r16.state.iterations,
                                  direct.state.iterations)
    # chunked walks round up to whole blocks and bank identically
    chunked = trace_quad(op, "log", 16, probe_chunk=6, **kw)
    np.testing.assert_array_equal(chunked.state.probe_lower,
                                  direct.state.probe_lower)
    # the statistical interval still covers the truth on this problem
    true = float(np.sum(np.log(w)))
    assert direct.stat_lower <= true <= direct.stat_upper
    # guardrails: whole blocks only, and no re-bucketing a banked state
    with pytest.raises(ValueError, match="multiple of block_size"):
        trace_quad(op, "log", 10, lam_min=lmn, lam_max=lmx, key=key,
                   block_size=4)
    with pytest.raises(ValueError, match="banks block_size"):
        trace_quad(op, "log", 16, lam_min=lmn, lam_max=lmx, key=key,
                   block_size=2, state=r8.state)
    with pytest.raises(ValueError, match="banks block_size"):
        trace_quad(op, "log", 16, lam_min=lmn, lam_max=lmx, key=key,
                   state=r8.state)


def test_block_exact_mode_brackets_true_trace_with_padding():
    """Exact unit-probe mode with a block width that does NOT divide N:
    the final block zero-pads, the pad slots deflate, and the summed
    bracket still certifies the true trace."""
    a, w, _, lmn, lmx = _problem(seed=2)      # N = 24, b = 7 pads to 28
    op = Dense(jnp.asarray(a))
    true = float(np.sum(np.log(w)))
    r = trace_quad(op, "log", None, lam_min=lmn, lam_max=lmx,
                   block_size=7)
    scale = max(abs(true), 1.0)
    assert r.lower <= true + 1e-8 * scale
    assert r.upper >= true - 1e-8 * scale
    assert r.std_error == 0.0
    assert r.num_probes == a.shape[0]
    assert len(r.state.probe_lower) == 4      # ceil(24 / 7) block lanes


def test_log_likelihood_brackets_slogdet_truth():
    a, w, _, lmn, lmx = _problem(seed=9, kappa=30.0)
    n = a.shape[0]
    rng = np.random.default_rng(5)
    for seed in (0, 1):
        mask = (rng.random(n) < 0.6).astype(float)
        idx = np.where(mask > 0.5)[0]
        true = float(np.linalg.slogdet(a[np.ix_(idx, idx)])[1]
                     - np.linalg.slogdet(a + np.eye(n))[1])
        ll = log_likelihood(Dense(jnp.asarray(a)), jnp.asarray(mask),
                            lmn, lmx)
        scale = max(abs(true), 1.0)
        assert ll.lower <= true + 1e-8 * scale
        assert ll.upper >= true - 1e-8 * scale
        assert ll.upper - ll.lower <= 1e-3 * scale
        assert abs(ll.estimate - true) <= 1e-4 * scale
    # the empty set: logdet(L_{}) = 0, so log P = -logdet(L + I)
    ll0 = log_likelihood(Dense(jnp.asarray(a)), jnp.zeros(n), lmn, lmx)
    true0 = -float(np.linalg.slogdet(a + np.eye(n))[1])
    assert ll0.lower <= true0 <= ll0.upper


def test_logdet_quad_masked_needs_no_correction():
    """tr log of the fixed-shape Masked operator IS logdet(A_Y): the
    identity block contributes log(1) = 0 — pinned explicitly because
    every other f would need a (N - |Y|) * f(1) correction."""
    a, _, _, lmn, lmx = _problem(seed=13, kappa=20.0)
    n = a.shape[0]
    mask = (np.random.default_rng(2).random(n) < 0.5).astype(float)
    idx = np.where(mask > 0.5)[0]
    true = float(np.linalg.slogdet(a[np.ix_(idx, idx)])[1])
    r = logdet_quad(Masked(Dense(jnp.asarray(a)), jnp.asarray(mask)),
                    None, lam_min=min(lmn, 1.0), lam_max=max(lmx, 1.0))
    assert r.lower <= true <= r.upper
    assert r.upper - r.lower <= 1e-3 * max(abs(true), 1.0)


def test_monitor_logdet_bounds():
    rng = np.random.default_rng(0)
    sketches = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    lam = 1e-2
    s = np.asarray(sketches, np.float64)
    f = s.T @ s / s.shape[0] + lam * np.eye(16)
    true = float(np.linalg.slogdet(f)[1])
    r = logdet_bounds(sketches, lam=lam, max_iters=32)
    # f32 quadrature against an f64 oracle: containment to f32 slack
    scale = max(abs(true), 1.0)
    assert r.lower <= true + 1e-4 * scale
    assert r.upper >= true - 1e-4 * scale
    assert abs(r.estimate - true) <= 1e-2 * scale


def _solve_chunk_traces() -> int:
    return obs.retrace_counts().get("core.trace.solve_chunk", 0)


def test_repeated_jobs_trace_nothing_after_the_first():
    """Back-to-back jobs with a new probe key and a new solver object
    each time reuse one compiled probe draw and chunk solve: only the
    first call may trace."""
    a, _, _, lmn, lmx = _problem(n=26, seed=4)
    op = Dense(jnp.asarray(a, jnp.float32))
    kw = dict(lam_min=lmn, lam_max=lmx)
    logdet_quad(op, 12, key=jax.random.key(0),
                solver=BIFSolver.create(max_iters=32, rtol=1e-4), **kw)
    before = obs.retrace_counts()
    for k in range(1, 4):
        r = logdet_quad(op, 12, key=jax.random.key(k),
                        solver=BIFSolver.create(max_iters=32, rtol=1e-4),
                        **kw)
        assert r.lower <= r.upper
    assert obs.retrace_counts() == before


def test_exact_mode_equal_chunks_trace_the_solve_once():
    """Exact unit-probe mode in equal chunks compiles one chunk solve
    for all of them; a shorter last chunk compiles once more."""
    a, w, _, lmn, lmx = _problem(n=30, seed=6)
    op = Dense(jnp.asarray(a))
    true = float(np.sum(np.log(w)))
    t0 = _solve_chunk_traces()
    r = trace_quad(op, "log", None, lam_min=lmn, lam_max=lmx,
                   probe_chunk=10)       # 3 chunks of 10
    assert _solve_chunk_traces() - t0 == 1
    assert r.lower <= true <= r.upper
    t1 = _solve_chunk_traces()
    r8 = trace_quad(op, "log", None, lam_min=lmn, lam_max=lmx,
                    probe_chunk=8)       # 8, 8, 8, then 6
    assert _solve_chunk_traces() - t1 == 2
    assert r8.lower <= true <= r8.upper
    np.testing.assert_allclose(r8.state.probe_lower, r.state.probe_lower,
                               rtol=1e-12)


@pytest.mark.parametrize("fn", ["log", "inv"])
def test_compiled_chunk_matches_eager_solve_batch(fn):
    """The compiled chunk call returns what an eager ``solve_batch``
    returns on the same probes, to float32 rounding, with the same
    iteration counts."""
    a, _, _, lmn, lmx = _problem(n=24, seed=8)
    op = Dense(jnp.asarray(a, jnp.float32))
    solver = BIFSolver.create(max_iters=32, rtol=1e-4, fn=fn)
    us = _probe_chunk(jax.random.key(21), 0, op, count=8, exact=False,
                      block=1)
    want = np.stack([np.asarray(_rademacher_probe(jax.random.key(21), i,
                                                  24, jnp.float32))
                     for i in range(8)])
    np.testing.assert_array_equal(np.asarray(us), want)
    lo, hi, it = _solve_chunk(solver, op, us, lmn, lmx)
    eager = solver.solve_batch(op, us, lam_min=lmn, lam_max=lmx)
    np.testing.assert_allclose(np.asarray(lo), np.asarray(eager.lower),
                               rtol=2e-5)
    np.testing.assert_allclose(np.asarray(hi), np.asarray(eager.upper),
                               rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(it),
                                  np.asarray(eager.iterations))
