"""Exact-GP posterior queries against a dense RBF Gram matrix plus noise.

The operator: n points drawn uniformly from [0, 1]^d, an RBF kernel with
the median-heuristic lengthscale (l^2 = median squared distance of a
seeded subsample), symmetrised, with unit diagonal, plus a noise term
that makes [noise, rowmax + noise] a Gershgorin-certified spectral
interval of condition bound kappa. It is built on the device in one
jitted call from the seed, as ||x||^2 + ||y||^2 - 2 x^T y, in float32.

A query is u = k(X, x*) for a seeded test point x*: u^T A^-1 u is the
variance the data explain at x*, and 1 - u^T A^-1 u its posterior
variance.

The plain reference (`Reference`) works on the host in float64 on the
exact float32 entries the device holds: `quad` (u^T A^-1 u) by a
Cholesky factorisation, `log_quad` (u^T log(A) u) by Lanczos with full
reorthogonalisation, run until the Gauss rule and the two Gauss-Radau
rules at the certified interval's ends, which bracket the form, agree
to 1e-11 of it. The control (`control_quad`, `control_log_quad`) is the
reference computed on the device in float32 with bfloat16x3 products
(`high`), put in the program's place.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32_EPS = 2.0 ** -23
LAM_MAX_SLACK = 1e-4   # relative margin over an f32-summed Gershgorin bound


def _sqdist(x, y):
    g = jnp.matmul(x, y.T, precision=jax.lax.Precision.HIGHEST)
    sx = jnp.sum(x * x, axis=-1)
    sy = jnp.sum(y * y, axis=-1)
    return jnp.maximum(sx[:, None] + sy[None, :] - 2.0 * g, 0.0)


@partial(jax.jit, static_argnames=("n", "d", "sub", "kappa"))
def _build(key, *, n, d, sub, kappa):
    kx, ks = jax.random.split(key)
    x = jax.random.uniform(kx, (n, d), jnp.float32)
    xs = x[jax.random.permutation(ks, n)[:sub]]
    iu = jnp.triu_indices(sub, 1)
    ell2 = jnp.median(_sqdist(xs, xs)[iu])
    k = jnp.exp(_sqdist(x, x) * (-0.5 / ell2))
    k = 0.5 * (k + k.T)
    eye = jnp.eye(n, dtype=jnp.bool_)
    k = jnp.where(eye, jnp.float32(1.0), k)
    rowmax = jnp.max(jnp.sum(k, axis=-1))
    fro = jnp.sqrt(jnp.sum(k * k))
    noise = rowmax / (kappa - 1.0)
    a = jnp.where(eye, k + noise, k)
    return x, ell2, a, rowmax, noise, fro


@partial(jax.jit, static_argnames=("m",))
def _queries(x, ell2, key, *, m):
    xs = jax.random.uniform(key, (m, x.shape[1]), jnp.float32)
    return jnp.exp(_sqdist(xs, x) * (-0.5 / ell2))


@dataclass
class Deployment:
    op: object
    a: object              # (n, n) f32 device array
    x: object
    ell2: object
    n: int
    lam_min: float
    lam_max: float
    storage: dict          # what work.py counts a pass of
    describe: str

    def queries(self, key: int, m: int) -> np.ndarray:
        """``m`` query vectors (m, n) f32 on the host, from the key."""
        return np.asarray(_queries(self.x, self.ell2, jax.random.key(key),
                                   m=m))

    def host_matrix(self) -> np.ndarray:
        return np.asarray(self.a)


def build(cfg: dict, key: int) -> Deployment:
    from repro.core import Dense

    n, d = int(cfg["n"]), int(cfg["d"])
    x, ell2, a, rowmax, noise, fro = _build(
        jax.random.key(key), n=n, d=d, sub=min(int(cfg["median_subsample"]), n),
        kappa=float(cfg["kappa"]))
    rowmax, noise, fro = float(rowmax), float(noise), float(fro)
    # lam_min stays below noise by the f32 rounding bound of K's entries,
    # so the interval is certified for the float32 matrix the device holds
    lam_min = noise - F32_EPS * fro
    lam_max = (rowmax + noise) * (1.0 + LAM_MAX_SLACK)
    return Deployment(
        op=Dense(a), a=a, x=x, ell2=ell2, n=n, lam_min=lam_min,
        lam_max=lam_max,
        storage={"kind": "dense", "n": n, "nnz": n * n, "value_bytes": 4},
        describe=(f"dense rbf n={n} d={d} ell2={float(ell2)!r} "
                  f"noise={noise!r} lam_min={lam_min!r} lam_max={lam_max!r} "
                  f"kappa_bound={lam_max / lam_min!r} hbm_bytes_A={a.nbytes}"))


class Reference:
    """float64 reference on the host, of the float32 matrix the device
    holds. Imports nothing of the program."""

    def __init__(self, dep: Deployment):
        self._a = dep.host_matrix().astype(np.float64)
        self._interval = (float(dep.lam_min), float(dep.lam_max))
        self._chol = None

    def quad(self, us: np.ndarray) -> np.ndarray:
        """u^T A^-1 u per row of us, by a Cholesky factorisation."""
        import scipy.linalg

        if self._chol is None:
            self._chol = scipy.linalg.cho_factor(
                self._a, lower=True, overwrite_a=False, check_finite=False)
        u = np.asarray(us, np.float64)
        x = scipy.linalg.cho_solve(self._chol, u.T, check_finite=False)
        return np.einsum("kn,nk->k", u, x)

    def log_quad(self, us: np.ndarray) -> np.ndarray:
        """u^T log(A) u per row of us, each certified to LOG_RTOL."""
        u = np.asarray(us, np.float64)
        return np.concatenate([_lanczos_log(self._a, u[s:s + REF_CHUNK].T,
                                            *self._interval)
                               for s in range(0, len(u), REF_CHUNK)]
                              or [np.zeros(0)])


LOG_RTOL = 1e-11       # the reference's certified relative error
REF_MAX_ITERS = 400
REF_CHUNK = 96         # query columns per reference Lanczos


def _log_form(alpha, beta, norm2):
    """norm2 * e1^T log(T) e1 for the symmetric tridiagonal T."""
    t = np.diag(alpha) + np.diag(beta, 1) + np.diag(beta, -1)
    theta, v = np.linalg.eigh(t)
    return norm2 * float(np.sum(v[0] ** 2 * np.log(theta)))


def _radau(alpha, beta, b_last, node, norm2):
    """The Gauss-Radau rule with a node fixed at `node`: T extended by
    one row whose diagonal puts an eigenvalue of it at `node`."""
    j = len(alpha)
    t = np.diag(alpha) + np.diag(beta, 1) + np.diag(beta, -1)
    e = np.zeros(j)
    e[-1] = b_last ** 2
    delta = np.linalg.solve(t - node * np.eye(j), e)
    return _log_form(np.append(alpha, node + delta[-1]),
                     np.append(beta, b_last), norm2)


def _lanczos_log(a, b, lam_min, lam_max):
    """u^T log(A) u for every column u of b (n, k): float64 Lanczos with
    full reorthogonalisation, each column until its Gauss and Gauss-Radau
    rules, which bracket the form for log, lie within LOG_RTOL."""
    norm2 = np.sum(b * b, axis=0)
    q = b / np.sqrt(norm2)
    basis = np.empty((32,) + b.shape)       # grows by doubling
    alphas, betas = [], []
    out = np.full(b.shape[1], np.nan)
    for j in range(REF_MAX_ITERS):
        if j == len(basis):
            basis = np.concatenate([basis, np.empty_like(basis)])
        basis[j] = q
        w = a @ q
        alpha = np.sum(q * w, axis=0)
        qs = basis[:j + 1]
        for _ in range(2):                  # full reorthogonalisation
            w -= np.einsum("jnk,jk->nk", qs, np.einsum("jnk,nk->jk", qs, w))
        beta = np.sqrt(np.sum(w * w, axis=0))
        alphas.append(alpha)
        betas.append(beta)
        for c in np.flatnonzero(np.isnan(out)):
            al = np.array([x[c] for x in alphas])
            be = np.array([x[c] for x in betas])
            g = _log_form(al, be[:-1], norm2[c])
            if be[-1] <= 1e-14 * np.abs(al).max():   # Krylov space exhausted
                out[c] = g
                continue
            rules = [g] + [_radau(al, be[:-1], be[-1], x, norm2[c])
                           for x in (lam_min, lam_max)]
            lo, hi = min(rules), max(rules)
            if hi - lo <= LOG_RTOL * abs(0.5 * (lo + hi)):
                out[c] = 0.5 * (lo + hi)
        if not np.isnan(out).any():
            return out
        q = w / np.where(beta > 0.0, beta, 1.0)
    raise RuntimeError(f"reference Lanczos did not certify {LOG_RTOL} in "
                       f"{REF_MAX_ITERS} iterations")


# ---------------------------------------------------------------------------
# The control: the reference in float32 at `high` (three bfloat16 passes)


def round_bf16(x):
    """x rounded to bfloat16, kept in float32. An explicit
    reduce_precision: a float32 -> bfloat16 -> float32 round trip of
    converts may be folded away by XLA's excess-precision rewrite."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def mm_high(a, x):
    """a @ x at `high`: three bfloat16 passes (hi.hi + hi.lo + lo.hi)
    with float32 sums. On the TPU that is Precision.HIGH itself; other
    platforms ignore the flag, so the passes are spelled out there."""
    if jax.default_backend() == "tpu":
        return jnp.matmul(a, x, precision=jax.lax.Precision.HIGH)
    a_hi, x_hi = round_bf16(a), round_bf16(x)
    a_lo, x_lo = round_bf16(a - a_hi), round_bf16(x - x_hi)

    def mm(p, q):
        return jnp.matmul(p.astype(jnp.bfloat16), q.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)

    return mm(a_hi, x_hi) + mm(a_hi, x_lo) + mm(a_lo, x_hi)


def cg_point(matmul, b, iters: int):
    """Conjugate gradients on every column of b (n, m) for a fixed number
    of iterations; returns u^T x per column, in float32."""

    def body(_, c):
        x, r, p, rr = c
        ap = matmul(p)
        alpha = rr / jnp.maximum(jnp.sum(p * ap, axis=0), 1e-30)
        x = x + alpha * p
        r = r - alpha * ap
        rr_new = jnp.sum(r * r, axis=0)
        p = r + (rr_new / jnp.maximum(rr, 1e-30)) * p
        return x, r, p, rr_new

    x0 = jnp.zeros_like(b)
    c = jax.lax.fori_loop(0, iters, body, (x0, b, b, jnp.sum(b * b, axis=0)))
    return jnp.sum(b * c[0], axis=0)


@partial(jax.jit, static_argnames=("iters",))
def _control_quad(a, us, *, iters):
    return cg_point(lambda p: mm_high(a, p), us.T, iters)


def control_quad(dep: Deployment, us: np.ndarray, iters: int) -> np.ndarray:
    return np.asarray(_control_quad(dep.a, jnp.asarray(us, jnp.float32),
                                    iters=iters), np.float64)


@partial(jax.jit, static_argnames=("iters",))
def _control_lanczos(a, us, *, iters):
    """The reference's Lanczos recurrence (full reorthogonalisation) in
    float32 with bfloat16x3 products: (alpha, beta), each (iters, k)."""
    b = us.T                                          # (n, k)
    hi = jax.lax.Precision.HIGHEST

    def step(c, j):
        q, q_prev, beta_prev, basis = c
        basis = basis.at[j].set(q)
        w = mm_high(a, q)
        alpha = jnp.sum(q * w, axis=0)
        w = w - alpha * q - beta_prev * q_prev
        for _ in range(2):                  # full reorthogonalisation
            w = w - jnp.einsum("jnk,jk->nk", basis, jnp.einsum(
                "jnk,nk->jk", basis, w, precision=hi), precision=hi)
        beta = jnp.sqrt(jnp.sum(w * w, axis=0))
        return (w / jnp.where(beta > 0.0, beta, 1.0), q, beta, basis), \
            (alpha, beta)

    q0 = b / jnp.sqrt(jnp.sum(b * b, axis=0))
    init = (q0, jnp.zeros_like(q0), jnp.zeros(b.shape[1], b.dtype),
            jnp.zeros((iters,) + b.shape, b.dtype))
    return jax.lax.scan(step, init, jnp.arange(iters))[1]


def control_log_quad(dep: Deployment, us: np.ndarray, iters: int) -> np.ndarray:
    """u^T log(A) u per row of us, a point answer: the Gauss rule of the
    reference's Lanczos run for `iters` steps in float32 with bfloat16x3
    products; the small tridiagonal eigenproblem is the reference's own,
    in float64 on the host, so only the products' precision differs."""
    alpha, beta = (np.asarray(x, np.float64) for x in _control_lanczos(
        dep.a, jnp.asarray(us, jnp.float32), iters=iters))
    norm2 = np.sum(np.asarray(us, np.float64) ** 2, axis=1)
    return np.array([_log_form(alpha[:, c], beta[:-1, c], norm2[c])
                     for c in range(len(norm2))])
