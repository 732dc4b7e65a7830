"""Multi-device parity checks for the sharded batched driver.

Run as a SUBPROCESS with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(tests/test_sharded.py does; the main pytest process must stay at one
device, see tests/conftest.py). Prints ``OK`` when every check passes.

The contract (DESIGN.md Sec. 7): per-lane decisions, iteration counts,
certification, and the certified argmax index from the sharded driver
exactly match the single-device batched path on identical stacked
inputs; brackets are bit-exact on SparseCOO and agree to 1e-12 on
gemm-backed operators.
"""
import os
import sys
import tempfile
from pathlib import Path

assert "--xla_force_host_platform_device_count=8" in \
    os.environ.get("XLA_FLAGS", ""), "run me under 8 virtual devices"

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)
# Initialize backends BEFORE importing conftest: it pops XLA_FLAGS (the
# in-process suite must see one device), which would shrink our mesh if
# jax hadn't locked in the 8 virtual devices yet.
assert len(jax.devices()) == 8, jax.devices()

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from conftest import make_spd  # noqa: E402
from repro.core import BIFSolver, Dense, Masked, ShardedBIFSolver, \
    bell_from_dense, dpp, greedy_map, sparse_from_dense, stack_masks, \
    stack_ops, trace_quad  # noqa: E402
from repro.launch.mesh import make_lane_mesh  # noqa: E402
from repro.serve import BIFEngine, BIFRequest  # noqa: E402


def _problem(n=48, k=16, kappa=150.0, seed=0, density=0.3):
    a = make_spd(n, kappa=kappa, seed=seed, density=density)
    w = np.linalg.eigvalsh(a)
    us = np.random.default_rng(seed + 1).standard_normal((k, n))
    true = np.einsum("ki,ki->k", us, np.linalg.solve(a, us.T).T)
    return a, jnp.asarray(us), true, float(w[0] * 0.99), float(w[-1] * 1.01)


def _assert_solve_parity(ref, got, bit_exact, what):
    np.testing.assert_array_equal(np.asarray(got.iterations),
                                  np.asarray(ref.iterations), what)
    np.testing.assert_array_equal(np.asarray(got.certified),
                                  np.asarray(ref.certified), what)
    for field in ("lower", "upper", "gauss_lower", "lobatto_upper"):
        b, s = np.asarray(getattr(got, field)), np.asarray(getattr(ref,
                                                                   field))
        if bit_exact:
            np.testing.assert_array_equal(b, s, f"{what}.{field}")
        else:
            np.testing.assert_allclose(b, s, rtol=1e-12,
                                       err_msg=f"{what}.{field}")


def check_solve_batch_parity(mesh):
    a, us, true, lmn, lmx = _problem()
    s = BIFSolver.create(max_iters=50, rtol=1e-4)
    for kind, op in [("dense", Dense(jnp.asarray(a))),
                     ("coo", sparse_from_dense(a)),
                     ("bell", bell_from_dense(a, bs=16))]:
        ref = s.solve_batch(op, us, lam_min=lmn, lam_max=lmx)
        got = s.solve_batch_sharded(op, us, mesh=mesh, lam_min=lmn,
                                    lam_max=lmx)
        _assert_solve_parity(ref, got, kind == "coo", kind)
        assert np.all(np.asarray(got.lower) <= true * (1 + 1e-9))
        assert np.all(np.asarray(got.upper) >= true * (1 - 1e-9))


def check_nondivisible_padding(mesh):
    """K=11 over 8 devices: a padding lane per short device, results
    sliced back to the 11 real lanes."""
    a, us, true, lmn, lmx = _problem(k=11, seed=3)
    s = BIFSolver.create(max_iters=50, rtol=1e-4)
    op = sparse_from_dense(a)
    ref = s.solve_batch(op, us, lam_min=lmn, lam_max=lmx)
    got = s.solve_batch_sharded(op, us, mesh=mesh, lam_min=lmn,
                                lam_max=lmx)
    assert got.lower.shape == (11,)
    _assert_solve_parity(ref, got, True, "coo-pad")

    # stacked masks (lane-stacked operator leaves) must pad too
    base = Dense(jnp.asarray(a))
    masks = jnp.asarray(
        (np.random.default_rng(5).random((11, a.shape[0])) < 0.6)
        .astype(float))
    mop = stack_masks(base, masks)
    usm = us * masks
    ref = s.solve_batch(mop, usm, lam_min=lmn, lam_max=lmx)
    got = s.solve_batch_sharded(mop, usm, mesh=mesh, lam_min=lmn,
                                lam_max=lmx)
    _assert_solve_parity(ref, got, False, "masked-pad")


def check_stacked_ops(mesh):
    """K *different* systems (stack_ops): per-lane operator leaves shard
    with the lanes."""
    n, k = 32, 8
    mats = [make_spd(n, kappa=60.0, seed=s) for s in range(k)]
    w = [np.linalg.eigvalsh(m) for m in mats]
    lmn = min(v[0] for v in w) * 0.99
    lmx = max(v[-1] for v in w) * 1.01
    us = jnp.asarray(np.random.default_rng(9).standard_normal((k, n)))
    s = BIFSolver.create(max_iters=n + 2, rtol=1e-4)
    for kind, build in [("coo", sparse_from_dense),
                        ("bell", lambda m: bell_from_dense(m, bs=16))]:
        stacked = stack_ops([build(m) for m in mats])
        ref = s.solve_batch(stacked, us, lam_min=lmn, lam_max=lmx)
        got = s.solve_batch_sharded(stacked, us, mesh=mesh, lam_min=lmn,
                                    lam_max=lmx)
        _assert_solve_parity(ref, got, kind == "coo", f"stack_ops-{kind}")


def check_per_lane_spectrum(mesh):
    """Estimating spectrum modes return PER-LANE lam arrays from
    prepare(); they must shard with the lanes (and pad with the dummy
    lanes) instead of crashing as replicated scalars. On COO the matvec
    floats are bit-exact, so iteration counts must match exactly too.
    ridge mixes a scalar lam_min with a per-lane lam_max — the two specs
    are derived independently."""
    a, us, true, lmn_, lmx_ = _problem(k=16, seed=8)
    op = sparse_from_dense(a)
    for spec, k in [("lanczos", 16), ("lanczos", 11), ("ridge", 16),
                    ("ridge", 11)]:
        s = BIFSolver.create(max_iters=40, rtol=1e-5, spectrum=spec,
                             ridge=1e-3)
        ref = s.solve_batch(op, us[:k])
        got = s.solve_batch_sharded(op, us[:k], mesh=mesh)
        _assert_solve_parity(ref, got, True, f"{spec}-k{k}")

    # explicit per-lane lam arrays shard the same way
    s = BIFSolver.create(max_iters=40, rtol=1e-5)
    lmn = jnp.full((11,), lmn_) * (1 + 0.001 * jnp.arange(11))
    lmx = jnp.full((11,), lmx_)
    ref = s.solve_batch(op, us[:11], lam_min=lmn, lam_max=lmx)
    got = s.solve_batch_sharded(op, us[:11], mesh=mesh, lam_min=lmn,
                                lam_max=lmx)
    _assert_solve_parity(ref, got, True, "explicit-per-lane-lam")


def check_judge_batch(mesh):
    """Thresholds ride the lanes; the knife-edge lane exhausts max_iters
    on both paths."""
    a, us, true, lmn, lmx = _problem(k=5, seed=0)
    op = sparse_from_dense(a)
    s = BIFSolver.create(max_iters=12)
    ts = jnp.asarray(true * np.array([0.5, 0.95, 1.0 + 1e-12, 1.05, 2.0]))
    ref = s.judge_batch(op, us, ts, lam_min=lmn, lam_max=lmx)
    got = s.judge_batch_sharded(op, us, ts, mesh=mesh, lam_min=lmn,
                                lam_max=lmx)
    np.testing.assert_array_equal(np.asarray(got.decision),
                                  np.asarray(ref.decision))
    np.testing.assert_array_equal(np.asarray(got.iterations),
                                  np.asarray(ref.iterations))
    np.testing.assert_array_equal(np.asarray(got.certified),
                                  np.asarray(ref.certified))
    assert int(got.iterations[2]) == 12 and not bool(got.certified[2])
    assert int(got.iterations[0]) < 12 and bool(got.certified[0])


def check_judge_argmax(mesh):
    a, us, true, lmn, lmx = _problem(k=16, seed=5)
    op = Dense(jnp.asarray(a))
    s = BIFSolver.create(max_iters=50)
    ref = s.judge_argmax(op, us, lam_min=lmn, lam_max=lmx)
    got = s.judge_argmax_sharded(op, us, mesh=mesh, lam_min=lmn,
                                 lam_max=lmx)
    assert int(got.index) == int(ref.index) == int(np.argmax(true))
    assert bool(got.certified) and bool(ref.certified)
    np.testing.assert_array_equal(np.asarray(got.iterations),
                                  np.asarray(ref.iterations))
    np.testing.assert_allclose(np.asarray(got.lower),
                               np.asarray(ref.lower), rtol=1e-12)

    # per-lane shift/scale + valid mask, non-divisible K=11 (pads enter
    # the race invalid)
    us11, true11 = us[:11], true[:11]
    d = jnp.asarray(30.0 * np.abs(true11))
    valid = jnp.ones((11,), bool).at[int(np.argmax(true11))].set(False)
    ref = s.judge_argmax(op, us11, shift=d, scale=-1.0, valid=valid,
                         lam_min=lmn, lam_max=lmx)
    got = s.judge_argmax_sharded(op, us11, shift=d, scale=-1.0,
                                 valid=valid, mesh=mesh, lam_min=lmn,
                                 lam_max=lmx)
    assert int(got.index) == int(ref.index)
    assert bool(got.certified) == bool(ref.certified)
    np.testing.assert_array_equal(np.asarray(got.iterations),
                                  np.asarray(ref.iterations))


def check_engine_flush(mesh):
    """Mixed judge/bracket traffic with a masked request, flushed through
    the mesh: identical chunk shapes => identical per-request outcomes."""
    a = make_spd(32, kappa=60.0, seed=2)
    w = np.linalg.eigvalsh(a)
    lam = dict(lam_min=float(w[0] * 0.9), lam_max=float(w[-1] * 1.1))
    op = Dense(jnp.asarray(a))
    sv = BIFSolver.create(max_iters=40, rtol=1e-3)
    e0 = BIFEngine(op, solver=sv, max_batch=8, **lam)
    e1 = BIFEngine(op, solver=sv, max_batch=6, mesh=mesh, **lam)
    assert e1.max_batch == 8  # rounded up to num_devices x lanes_per_device
    rng = np.random.default_rng(4)
    us = rng.standard_normal((11, 32))
    true = np.einsum("ki,ki->k", us, np.linalg.solve(a, us.T).T)
    mask = (rng.random(32) < 0.5).astype(float)
    for eng in (e0, e1):
        for i, u in enumerate(us):
            t = float(true[i] * (0.9 if i % 2 else 1.1)) if i % 3 else None
            eng.submit(BIFRequest(u=u, t=t, mask=mask if i == 10 else None))
    r0, r1 = e0.flush(), e1.flush()
    for i, (x, y) in enumerate(zip(r0, r1)):
        assert x.decision == y.decision, i
        assert x.certified == y.certified, i
        assert x.iterations == y.iterations, i
        np.testing.assert_allclose([x.lower, x.upper], [y.lower, y.upper],
                                   rtol=1e-12)
    # the mesh engine really answered the BIF queries
    for i, r in enumerate(r1[:10]):
        assert r.lower <= true[i] * 1.0001 and r.upper >= true[i] * 0.9999


def check_engine_stats_parity(mesh):
    """Telemetry on the sharded engine (DESIGN.md Sec. 14): the mesh
    engine's request ledger matches the single-device engine on
    identical traffic, and metrics on vs off on the mesh is
    BIT-identical — instrumentation must not perturb the sharded path
    either."""
    from repro import obs

    a = make_spd(32, kappa=60.0, seed=11)
    w = np.linalg.eigvalsh(a)
    lam = dict(lam_min=float(w[0] * 0.9), lam_max=float(w[-1] * 1.1))
    op = Dense(jnp.asarray(a))
    sv = BIFSolver.create(max_iters=40, rtol=1e-3)
    rng = np.random.default_rng(12)
    us = rng.standard_normal((13, 32))
    true = np.einsum("ki,ki->k", us, np.linalg.solve(a, us.T).T)

    e0 = BIFEngine(op, solver=sv, max_batch=8, chunk_iters=4, **lam)
    e1 = BIFEngine(op, solver=sv, max_batch=8, chunk_iters=4, mesh=mesh,
                   **lam)
    e_off = BIFEngine(op, solver=sv, max_batch=8, chunk_iters=4, mesh=mesh,
                      metrics=False, **lam)
    for eng in (e0, e1, e_off):
        for i, u in enumerate(us):
            t = float(true[i] * (0.9 if i % 2 else 1.1)) if i % 3 else None
            eng.submit(BIFRequest(u=u, t=t))
    # the metered engines flush inside a profiler session (spans
    # recorded), the bare one outside it
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            r0, r1 = e0.flush(), e1.flush()
    r_off = e_off.flush()

    # same compiled driver, same mesh: metrics on vs off is bit-exact
    for i, (x, y) in enumerate(zip(r1, r_off)):
        assert x.decision == y.decision, i
        assert x.certified == y.certified, i
        assert x.iterations == y.iterations, i
        assert (x.lower, x.upper) == (y.lower, y.upper), i
    assert e_off.stats() == {"counters": {}, "gauges": {},
                             "histograms": {}}

    # request-ledger parity across single-device vs mesh: every counter
    # equal, histogram populations equal, and the iteration histogram
    # (whose observations are exact-parity ints) identical
    s0, s1 = e0.stats(), e1.stats()
    assert s0["counters"] == s1["counters"], (s0["counters"],
                                              s1["counters"])
    assert s0["counters"]["requests.submitted"] == len(us)
    assert s0["counters"]["requests.resolved"] == len(us)
    assert set(s0["histograms"]) == set(s1["histograms"])
    for name in s0["histograms"]:
        assert s0["histograms"][name]["count"] == \
            s1["histograms"][name]["count"], name
    for field in ("min", "max", "sum", "p50", "p99"):
        assert s0["histograms"]["request.iterations"][field] == \
            s1["histograms"]["request.iterations"][field], field
    for eng in (e0, e1):
        lat = eng.stats()["histograms"]["request.latency_s"]
        assert lat["count"] == len(us) and lat["p99"] >= lat["p50"]


def check_applications(mesh):
    """greedy MAP + k-DPP swap ride the sharded judges unchanged."""
    n = 28
    a = make_spd(n, kappa=60.0, seed=7)
    d = np.sqrt(np.diag(a))
    a = a / np.outer(d, d) + 0.1 * np.eye(n)
    w = np.linalg.eigvalsh(a)
    op = Dense(jnp.asarray(a))
    r1 = greedy_map(op, 6, w[0] * 0.99, w[-1] * 1.01, max_iters=n + 2)
    r2 = greedy_map(op, 6, w[0] * 0.99, w[-1] * 1.01, max_iters=n + 2,
                    mesh=mesh)
    np.testing.assert_array_equal(np.asarray(r1.order),
                                  np.asarray(r2.order))
    assert int(r2.uncertified) == 0
    assert int(r1.quad_iterations) == int(r2.quad_iterations)

    # the incremental factor carry composes with the mesh: the sharded
    # race sees the same exact lower/upper priors, so selections AND
    # iteration totals match the single-device incremental run
    r3 = greedy_map(op, 6, w[0] * 0.99, w[-1] * 1.01, max_iters=n + 2,
                    incremental=True)
    r4 = greedy_map(op, 6, w[0] * 0.99, w[-1] * 1.01, max_iters=n + 2,
                    incremental=True, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(r1.order),
                                  np.asarray(r3.order))
    np.testing.assert_array_equal(np.asarray(r3.order),
                                  np.asarray(r4.order))
    assert int(r4.uncertified) == 0
    assert int(r3.quad_iterations) == int(r4.quad_iterations)
    # exact priors resolve every lane at its first decide check; this
    # well-conditioned regime already sits at the floor from scratch, so
    # parity (not strict savings — test_update.py pins that) is the bar
    assert int(r3.quad_iterations) == 6 * n

    st = dpp.init_chain(jax.random.key(0), jnp.zeros(n).at[:5].set(1.0))
    s1 = dpp.kdpp_step(op, st, w[0] * 0.99, w[-1] * 1.01, max_iters=n + 2)
    s2 = dpp.kdpp_step(op, st, w[0] * 0.99, w[-1] * 1.01, max_iters=n + 2,
                       mesh=mesh)
    np.testing.assert_array_equal(np.asarray(s1.mask), np.asarray(s2.mask))
    assert int(s1.stats.quad_iterations) == int(s2.stats.quad_iterations)


def check_resumable_stepping(mesh):
    """The sharded stepping API (DESIGN.md Sec. 8): interrupt with
    step_n_sharded, resume with resume_sharded — the final result equals
    the uninterrupted sharded drive AND the single-device path (bit-exact
    COO, 1e-12 dense), including non-divisible K=11 padding."""
    from repro.core import sharded as core_sharded

    a, us, true, lmn, lmx = _problem(k=11, seed=12)
    s = BIFSolver.create(max_iters=50, rtol=1e-4)
    for kind, op in [("coo", sparse_from_dense(a)),
                     ("dense", Dense(jnp.asarray(a)))]:
        ref = s.solve_batch_sharded(op, us, mesh=mesh, lam_min=lmn,
                                    lam_max=lmx)
        st = core_sharded.init_state_sharded(s, op, us, mesh=mesh,
                                             lam_min=lmn, lam_max=lmx)
        assert st.st.it.shape == (16,)  # padded to the device multiple
        for k in (1, 3):
            st = core_sharded.step_n_sharded(s, st, k, mesh=mesh)
        st = core_sharded.resume_sharded(s, st, mesh=mesh)
        got = core_sharded.finalize_sharded(s, st, nlanes=11)
        _assert_solve_parity(ref, got, kind == "coo", f"stepping-{kind}")
        single = s.solve_batch(op, us, lam_min=lmn, lam_max=lmx)
        _assert_solve_parity(single, got, kind == "coo",
                             f"stepping-vs-single-{kind}")

    # per-lane iteration budgets shard with the lanes; lifting the cap
    # resumes to the uninterrupted endpoint bit-exactly
    op = sparse_from_dense(a)
    ref = s.solve_batch_sharded(op, us, mesh=mesh, lam_min=lmn,
                                lam_max=lmx)
    st = core_sharded.init_state_sharded(s, op, us, mesh=mesh, lam_min=lmn,
                                         lam_max=lmx)
    st = core_sharded.resume_sharded(s, st, it_cap=np.full(16, 3, np.int32),
                                     mesh=mesh)
    assert int(np.asarray(st.it).max()) <= 3
    st = core_sharded.resume_sharded(s, st, mesh=mesh)
    got = core_sharded.finalize_sharded(s, st, nlanes=11)
    _assert_solve_parity(ref, got, True, "budget-resume")


def check_cadence_rounds(mesh):
    """Round-cadenced collectives (DESIGN.md Sec. 11): at every
    ``decide_every`` the sharded drive stays bit-exact with the single-
    device solver at the SAME cadence, decisions and certificates match
    the R=1 run, ``step_n_sharded`` quantizes to whole rounds, and the
    step counter stays round-aligned."""
    from repro.core import sharded as core_sharded

    a, us, true, lmn, lmx = _problem(k=11, seed=33)
    op = sparse_from_dense(a)
    base = None
    for r in (1, 2, 4):
        s = BIFSolver.create(max_iters=50, rtol=1e-4, decide_every=r)
        single = s.solve_batch(op, us, lam_min=lmn, lam_max=lmx)
        got = s.solve_batch_sharded(op, us, mesh=mesh, lam_min=lmn,
                                    lam_max=lmx)
        # sharded == single-device at the same cadence, bit-exact (COO)
        _assert_solve_parity(single, got, True, f"cadence-R{r}")
        if base is None:
            base = got
        else:
            # cadence never flips a decision: certificates match R=1 and
            # deferring the decide costs at most R-1 extra contractions
            np.testing.assert_array_equal(np.asarray(got.certified),
                                          np.asarray(base.certified),
                                          f"cadence-R{r}-certified")
            extra = np.asarray(got.iterations) - np.asarray(base.iterations)
            assert np.all((extra >= 0) & (extra <= r - 1)), \
                f"R={r}: {extra}"
        # interrupted + resumed at this cadence lands on the same result
        st = core_sharded.init_state_sharded(s, op, us, mesh=mesh,
                                             lam_min=lmn, lam_max=lmx)
        small = core_sharded.step_n_sharded(s, st, r - 1, mesh=mesh)
        assert small is st, "n < R must quantize to a no-op"
        for k in (r, 2 * r + 1):
            st = core_sharded.step_n_sharded(s, st, k, mesh=mesh)
            assert int(st.step) % r == 0, "step must stay round-aligned"
        st = core_sharded.resume_sharded(s, st, mesh=mesh)
        got2 = core_sharded.finalize_sharded(s, st, nlanes=11)
        _assert_solve_parity(got, got2, True, f"cadence-R{r}-stepped")
        # the cross-device argmax race at this cadence: same certified
        # winner as the single-device race
        ja = s.judge_argmax_sharded(op, us, mesh=mesh, lam_min=lmn,
                                    lam_max=lmx)
        ja1 = s.judge_argmax(op, us, lam_min=lmn, lam_max=lmx)
        assert int(ja.index) == int(ja1.index) == int(np.argmax(true))
        assert bool(ja.certified) == bool(ja1.certified)


def check_matfun_and_trace_probes(mesh):
    """Matrix-function lanes over the mesh (DESIGN.md Sec. 9): the
    fn='log' batched drive — including its resumable stepping — and the
    trace-probe estimator match the single-device path exactly,
    non-divisible probe counts included."""
    from repro.core import sharded as core_sharded

    a, us, true, lmn, lmx = _problem(k=11, seed=21)
    op = sparse_from_dense(a)
    s = BIFSolver.create(max_iters=50, rtol=1e-6, fn="log")
    ref = s.solve_batch(op, us, lam_min=lmn, lam_max=lmx)
    got = s.solve_batch_sharded(op, us, mesh=mesh, lam_min=lmn,
                                lam_max=lmx)
    _assert_solve_parity(ref, got, True, "matfun-log")
    # dense-oracle containment, not just parity
    w, v = np.linalg.eigh(a)
    c = v.T @ np.asarray(us).T
    truth = np.sum(c * c * np.log(w)[:, None], axis=0)
    assert np.all(np.asarray(got.lower) <= truth + 1e-9 * np.abs(truth))
    assert np.all(np.asarray(got.upper) >= truth - 1e-9 * np.abs(truth))

    # interrupted sharded stepping carries the coefficient history
    st = core_sharded.init_state_sharded(s, op, us, mesh=mesh,
                                         lam_min=lmn, lam_max=lmx)
    assert st.coeffs is not None and st.coeffs.alphas.shape[0] == 16
    for k in (1, 3):
        st = core_sharded.step_n_sharded(s, st, k, mesh=mesh)
    st = core_sharded.resume_sharded(s, st, mesh=mesh)
    got2 = core_sharded.finalize_sharded(s, st, nlanes=11)
    _assert_solve_parity(ref, got2, True, "matfun-stepping")

    # trace probes as sharded lanes: 10 Hutchinson probes over 8 devices
    key = jax.random.key(3)
    single = trace_quad(op, "log", 10, lam_min=lmn, lam_max=lmx, key=key)
    sharded = trace_quad(op, "log", 10, lam_min=lmn, lam_max=lmx,
                         key=key, mesh=mesh)
    assert (sharded.lower, sharded.upper) == (single.lower, single.upper)
    assert sharded.iterations == single.iterations
    np.testing.assert_array_equal(sharded.state.probe_lower,
                                  single.state.probe_lower)
    ldtruth = float(np.sum(np.log(w)))
    assert sharded.stat_lower <= ldtruth <= sharded.stat_upper

    # exact unit probes: deterministic logdet certificate off the mesh
    exact = trace_quad(op, "log", None, lam_min=lmn, lam_max=lmx,
                       mesh=mesh)
    assert exact.lower <= ldtruth <= exact.upper


def check_block_quadrature(mesh):
    """Block-Krylov lanes over the mesh (DESIGN.md Sec. 13): sharded
    block brackets are decision-identical to the single-device block
    driver at every ``decide_every`` cadence (bit-exact on COO — only
    scalar trace summaries cross devices, under the PR 7 round gather),
    non-divisible K and block trace probes included."""
    a, _, _, lmn, lmx = _problem(seed=17)
    op = sparse_from_dense(a)
    n = a.shape[0]
    b, k = 4, 11
    us = jnp.asarray(
        np.random.default_rng(18).standard_normal((k, b, n)))
    wv, vv = np.linalg.eigh(a)
    g = np.asarray(us) @ vv
    truth = np.sum(g * g / wv, axis=(-2, -1))
    for r in (1, 2, 4):
        s = BIFSolver.create(max_iters=24, rtol=1e-6, block_size=b,
                             decide_every=r)
        single = s.solve_batch(op, us, lam_min=lmn, lam_max=lmx)
        got = s.solve_batch_sharded(op, us, mesh=mesh, lam_min=lmn,
                                    lam_max=lmx)
        _assert_solve_parity(single, got, True, f"block-R{r}")
        assert np.all(np.asarray(got.lower) <= truth * (1 + 1e-9))
        assert np.all(np.asarray(got.upper) >= truth * (1 - 1e-9))

    # block trace probes over the mesh match the single-device estimator
    key = jax.random.key(13)
    single = trace_quad(op, "log", 16, lam_min=lmn, lam_max=lmx, key=key,
                        block_size=b)
    sharded = trace_quad(op, "log", 16, lam_min=lmn, lam_max=lmx, key=key,
                         block_size=b, mesh=mesh)
    assert (sharded.lower, sharded.upper) == (single.lower, single.upper)
    assert sharded.std_error == single.std_error
    assert sharded.iterations == single.iterations
    np.testing.assert_array_equal(sharded.state.probe_lower,
                                  single.state.probe_lower)


def check_sharded_solver_wrapper(mesh):
    """ShardedBIFSolver is static: closure-capture under jit works and
    matches the unbound calls."""
    a, us, true, lmn, lmx = _problem(k=8, seed=6)
    op = sparse_from_dense(a)
    sh = ShardedBIFSolver(BIFSolver.create(max_iters=50, rtol=1e-4), mesh)
    res = sh.solve_batch(op, us, lam_min=lmn, lam_max=lmx)
    jres = jax.jit(lambda u: sh.solve_batch(op, u, lam_min=lmn,
                                            lam_max=lmx))(us)
    # outer jit refuses nothing and fuses differently: discrete outcomes
    # stay exact, floats to the usual gemm-caveat tolerance
    np.testing.assert_allclose(np.asarray(res.lower),
                               np.asarray(jres.lower), rtol=1e-12)
    np.testing.assert_array_equal(np.asarray(res.iterations),
                                  np.asarray(jres.iterations))

    ja = sh.judge_argmax(op, us, lam_min=lmn, lam_max=lmx)
    assert int(ja.index) == int(np.argmax(true))


def main():
    mesh = make_lane_mesh()
    assert dict(mesh.shape) == {"lanes": 8}
    for check in (check_solve_batch_parity,
                  check_nondivisible_padding,
                  check_per_lane_spectrum,
                  check_stacked_ops,
                  check_judge_batch,
                  check_judge_argmax,
                  check_resumable_stepping,
                  check_cadence_rounds,
                  check_engine_flush,
                  check_engine_stats_parity,
                  check_applications,
                  check_matfun_and_trace_probes,
                  check_block_quadrature,
                  check_sharded_solver_wrapper):
        check(mesh)
        # progress marker per check: an 8-virtual-device run compiles
        # for minutes, and a silent harness makes a hang look like slow
        print(f"{check.__name__} ok", flush=True)
    print("OK")


if __name__ == "__main__":
    main()
