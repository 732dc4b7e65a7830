"""Fused Lanczos-step kernel parity (DESIGN.md Sec. 11).

``kernels/lanczos_step.py`` runs the pass over A of each quadrature
iteration — the lane-stacked matvec and alpha — in one ``pallas_call``,
and the Lanczos update, reorth projection and GQL/Sherman-Morrison
bracket recurrence as the reference tail.
The contract: for every sandwich-decomposable operator the fused step
matches the reference composition (``gql.gql_step``) to 1e-12 on
gemm-backed paths, and operators WITHOUT a sandwich form (SparseCOO)
fall back to the reference composition bit-exactly. The 'fused' solver
backend must therefore be a drop-in: same iterations, same
certificates, brackets within 1e-12 everywhere.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BIFSolver, Dense, Jacobi, Masked, Shifted, \
    bell_from_dense, gql, sparse_from_dense
from repro.kernels import ops
from conftest import make_spd

SANDWICH_KINDS = ["dense", "sparse_bell", "masked", "shifted", "jacobi",
                  "masked_bell"]


def _operator(kind, a, rng):
    n = a.shape[0]
    if kind == "dense":
        return Dense(jnp.asarray(a))
    if kind == "sparse_coo":
        return sparse_from_dense(a)
    if kind == "sparse_bell":
        return bell_from_dense(a, bs=8)
    if kind == "masked":
        m = (rng.random(n) < 0.7).astype(np.float64)
        return Masked(Dense(jnp.asarray(a)), jnp.asarray(m))
    if kind == "masked_bell":
        m = (rng.random(n) < 0.7).astype(np.float64)
        return Masked(bell_from_dense(a, bs=8), jnp.asarray(m))
    if kind == "shifted":
        return Shifted(Dense(jnp.asarray(a)), jnp.asarray(0.75))
    if kind == "jacobi":
        return Jacobi.create(Dense(jnp.asarray(a)))
    raise AssertionError(kind)


def _problem(n=33, kappa=150.0, seed=0, lanes=4):
    a = make_spd(n, kappa=kappa, seed=seed, density=0.4)
    w = np.linalg.eigvalsh(a)
    us = np.random.default_rng(seed + 1).standard_normal((lanes, n))
    return a, jnp.asarray(us), float(w[0] * 0.5), float(w[-1] * 2.5)


def _assert_state_close(got, ref, what, *, bit_exact=False):
    for path, g, r in zip(
            [str(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(ref)[0]],
            jax.tree.leaves(got), jax.tree.leaves(ref)):
        g, r = np.asarray(g), np.asarray(r)
        if bit_exact or not np.issubdtype(r.dtype, np.floating):
            np.testing.assert_array_equal(g, r, f"{what}{path}")
        else:
            np.testing.assert_allclose(g, r, rtol=1e-12, atol=1e-12,
                                       err_msg=f"{what}{path}")


@pytest.mark.parametrize("op_kind", SANDWICH_KINDS)
def test_fused_step_matches_reference_composition(op_kind):
    """Step-by-step parity from the SAME state each iteration (no error
    accumulation): every GQLState leaf within 1e-12 of gql.gql_step."""
    rng = np.random.default_rng(3)
    a, us, lmn, lmx = _problem(seed=3)
    op = _operator(op_kind, a, rng)
    st = gql.gql_init(op, us, lmn, lmx)
    for i in range(8):
        fused = ops.gql_step_fused(op, st, lmn, lmx)
        refst = gql.gql_step(op, st, lmn, lmx)
        _assert_state_close(fused, refst, f"{op_kind}@{i}:")
        st = refst


def test_fused_step_coo_fallback_is_bit_exact():
    """No sandwich form -> the fused entry point IS the reference
    composition, bit for bit."""
    rng = np.random.default_rng(5)
    a, us, lmn, lmx = _problem(seed=5)
    op = _operator("sparse_coo", a, rng)
    st = gql.gql_init(op, us, lmn, lmx)
    for i in range(6):
        fused = ops.gql_step_fused(op, st, lmn, lmx)
        refst = gql.gql_step(op, st, lmn, lmx)
        _assert_state_close(fused, refst, f"coo@{i}:", bit_exact=True)
        st = refst


@pytest.mark.parametrize("batch", ["scalar", "grid"])
def test_fused_step_batch_shapes(batch):
    """Lane layouts beyond (K,): a single unbatched lane and a 2-D lane
    grid both round-trip the lane flattening."""
    rng = np.random.default_rng(7)
    a, us, lmn, lmx = _problem(seed=7)
    op = Dense(jnp.asarray(a))
    u = us[0] if batch == "scalar" else \
        jnp.broadcast_to(us, (3, 4, us.shape[-1]))
    st = gql.gql_init(op, u, lmn, lmx)
    for i in range(5):
        fused = ops.gql_step_fused(op, st, lmn, lmx)
        refst = gql.gql_step(op, st, lmn, lmx)
        _assert_state_close(fused, refst, f"{batch}@{i}:")
        st = refst


@pytest.mark.parametrize("op_kind", SANDWICH_KINDS + ["sparse_coo"])
def test_fused_backend_solver_is_drop_in(op_kind):
    """backend='fused' end to end: identical iterations/certificates,
    brackets within 1e-12 (bit-exact on the COO fallback)."""
    rng = np.random.default_rng(11)
    a, us, lmn, lmx = _problem(seed=11)
    op = _operator(op_kind, a, rng)
    ref = BIFSolver.create(max_iters=30, rtol=1e-6) \
        .solve(op, us, lam_min=lmn, lam_max=lmx)
    got = BIFSolver.create(max_iters=30, rtol=1e-6, backend="fused") \
        .solve(op, us, lam_min=lmn, lam_max=lmx)
    np.testing.assert_array_equal(np.asarray(got.iterations),
                                  np.asarray(ref.iterations))
    np.testing.assert_array_equal(np.asarray(got.certified),
                                  np.asarray(ref.certified))
    bit_exact = op_kind == "sparse_coo"
    for field in ("lower", "upper", "gauss_lower", "lobatto_upper"):
        g, r = np.asarray(getattr(got, field)), \
            np.asarray(getattr(ref, field))
        if bit_exact:
            np.testing.assert_array_equal(g, r, field)
        else:
            np.testing.assert_allclose(g, r, rtol=1e-12, atol=1e-12,
                                       err_msg=field)


def test_fused_backend_with_reorth_basis():
    """The in-kernel reorth projection against the banked basis matches
    the reference einsum pair (dense path only; BELL+basis falls back)."""
    a, us, lmn, lmx = _problem(seed=13, kappa=500.0)
    op = Dense(jnp.asarray(a))
    for backend in ("reference", "fused"):
        s = BIFSolver.create(max_iters=25, rtol=1e-10, reorth=True,
                             backend=backend)
        res = s.finalize(s.resume(s.init_state(op, us, lam_min=lmn,
                                               lam_max=lmx)))
        if backend == "reference":
            ref = res
    np.testing.assert_array_equal(np.asarray(res.iterations),
                                  np.asarray(ref.iterations))
    np.testing.assert_allclose(np.asarray(res.lower), np.asarray(ref.lower),
                               rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(np.asarray(res.upper), np.asarray(ref.upper),
                               rtol=1e-12, atol=1e-12)


def test_fused_backend_matfun_states():
    """fn != 'inv': the fused step only changes HOW alpha/beta are
    produced; the coefficient history and retrospective log bracket must
    match the reference backend within 1e-12."""
    a, us, lmn, lmx = _problem(n=24, seed=17)
    op = Dense(jnp.asarray(a))
    ref = BIFSolver.create(max_iters=24, rtol=1e-5, fn="log") \
        .solve(op, us, lam_min=lmn, lam_max=lmx)
    got = BIFSolver.create(max_iters=24, rtol=1e-5, fn="log",
                           backend="fused") \
        .solve(op, us, lam_min=lmn, lam_max=lmx)
    np.testing.assert_array_equal(np.asarray(got.iterations),
                                  np.asarray(ref.iterations))
    np.testing.assert_array_equal(np.asarray(got.certified),
                                  np.asarray(ref.certified))
    np.testing.assert_allclose(np.asarray(got.lower), np.asarray(ref.lower),
                               rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(np.asarray(got.upper), np.asarray(ref.upper),
                               rtol=1e-12, atol=1e-12)


def test_fused_backend_composes_with_cadence():
    """decide_every > 1 on the fused backend: the two tentpole halves
    compose — certificates match the R=1 reference run."""
    a, us, lmn, lmx = _problem(seed=19)
    op = Dense(jnp.asarray(a))
    ref = BIFSolver.create(max_iters=30, rtol=1e-6) \
        .solve(op, us, lam_min=lmn, lam_max=lmx)
    got = BIFSolver.create(max_iters=30, rtol=1e-6, backend="fused",
                           decide_every=4) \
        .solve(op, us, lam_min=lmn, lam_max=lmx)
    np.testing.assert_array_equal(np.asarray(got.certified),
                                  np.asarray(ref.certified))
    extra = np.asarray(got.iterations) - np.asarray(ref.iterations)
    assert np.all((extra >= 0) & (extra <= 3)), extra
