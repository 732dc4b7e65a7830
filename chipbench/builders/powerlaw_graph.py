"""Regularised graph-Laplacian queries on a power-law graph.

The operator: a Chung-Lu random graph on n nodes with m distinct
undirected edges, drawn from the seed. Node i's expected degree is
w_i = w_max * ((1 + i0) / (i + 1 + i0)) ** (1 / (gamma - 1)), a power
law of exponent gamma capped at w_max, with i0 set so that the mean is
2 m / n; each edge joins two nodes drawn in proportion to w, self-loops
and repeats are drawn again. Edge weights are uniform in [w_lo, w_hi].
The matrix is the weighted Laplacian L plus a ridge chosen so that
[ridge, 2 max degree + ridge] is a certified spectral interval of
condition bound kappa (L has the constant vector in its null space, so
lam_min = ridge less the float32 rounding of the entries). It is stored
as the program's ``SparseCOO`` in row order, the diagonal among the
entries: every seed gives the same number of entries. The graph is
drawn on the host with numpy (under a second at the source's size) and
uploaded once.

A query is a Gaussian signal u from the seed.

The plain reference (`Reference`) is a float64 conjugate-gradient solve
on the host, preconditioned by the diagonal, run until its residual
certifies every answer to 1e-12 of itself: for any x with r = u - A x,
u^T A^-1 u = u^T x + x^T r + r^T A^-1 r, and the last term lies in
[0, |r|^2 / lam_min]. The control (`control_quad`) is that solve on the
device with the entries and the vectors rounded to bfloat16 and the
products summed in float32 (the configuration's float32 entries and
products one precision down), put in the program's place.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.builders.dense_rbf_gp import round_bf16

F32_EPS = 2.0 ** -23
LAM_MAX_SLACK = 1e-4
REF_RTOL = 1e-12       # the reference's certified relative error
REF_MAX_ITERS = 1000
REF_CHUNK = 128        # query columns per reference solve


@partial(jax.jit, static_argnames=("m", "n"))
def _queries(key, *, m, n):
    return jax.random.normal(key, (m, n), jnp.float32)


@dataclass
class Deployment:
    op: object
    n: int
    lam_min: float
    lam_max: float
    rows: np.ndarray       # the entries in row order, diagonal included,
    cols: np.ndarray       # for the reference
    vals: np.ndarray       # float32, exactly what the device holds
    storage: dict
    describe: str

    def queries(self, key: int, m: int) -> np.ndarray:
        return np.asarray(_queries(jax.random.key(key), m=m, n=self.n))


def expected_degrees(n: int, edges: int, gamma: float, w_max: float):
    """The Chung-Lu weights: a power law of exponent gamma capped at
    w_max, with mean 2 * edges / n (i0 by bisection)."""
    mean, a = 2.0 * edges / n, 1.0 / (gamma - 1.0)
    i = np.arange(n, dtype=np.float64)

    def w(i0):
        return w_max * ((1.0 + i0) / (i + 1.0 + i0)) ** a

    lo, hi = 1e-9, 1e9
    if not w(lo).mean() < mean < w(hi).mean():
        raise ValueError("no power law with this cap has the mean degree")
    for _ in range(200):
        mid = np.sqrt(lo * hi)
        lo, hi = (lo, mid) if w(mid).mean() > mean else (mid, hi)
    return w(np.sqrt(lo * hi))


def chung_lu_edges(w: np.ndarray, edges: int, rng):
    """`edges` distinct pairs (src < dst), endpoints drawn in proportion
    to w; self-loops and repeats are drawn again."""
    n = w.size
    p = w / w.sum()
    keys = np.zeros(0, np.int64)
    while keys.size < edges:
        k = 2 * (edges - keys.size) + 1024
        s, d = rng.choice(n, k, p=p), rng.choice(n, k, p=p)
        ok = s != d
        new = np.minimum(s[ok], d[ok]) * n + np.maximum(s[ok], d[ok])
        keys = np.concatenate([keys, new])
        _, first = np.unique(keys, return_index=True)
        keys = keys[np.sort(first)]          # keep the first draw of each
    keys = keys[:edges]
    return keys // n, keys % n


def _entries(cfg: dict, rng):
    n, m = int(cfg["n"]), int(cfg["edges"])
    w = expected_degrees(n, m, float(cfg["gamma"]),
                         float(cfg["max_expected_degree"]))
    src, dst = chung_lu_edges(w, m, rng)
    wt = rng.uniform(float(cfg["weight_low"]), float(cfg["weight_high"]),
                     size=m)
    deg = np.bincount(src, wt, n) + np.bincount(dst, wt, n)
    ridge = 2.0 * float(deg.max()) / (float(cfg["kappa"]) - 1.0)
    rows = np.concatenate([src, dst, np.arange(n)])
    cols = np.concatenate([dst, src, np.arange(n)])
    vals = np.concatenate([-wt, -wt, deg + ridge]).astype(np.float32)
    order = np.lexsort((cols, rows))
    links = np.bincount(src, minlength=n) + np.bincount(dst, minlength=n)
    return rows[order], cols[order], vals[order], deg, ridge, links


def build(cfg: dict, key: int) -> Deployment:
    from chipbench.common import rng as make_rng
    from repro.core import SparseCOO

    n = int(cfg["n"])
    rows, cols, vals, deg, ridge, links = _entries(cfg, make_rng(key, 2))
    diag = np.zeros(n, np.float32)
    diag[rows[rows == cols]] = vals[rows == cols]
    op = SparseCOO(jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32),
                   jnp.asarray(vals), n, jnp.asarray(diag))
    lam_max = (2.0 * float(deg.max()) + ridge) * (1.0 + LAM_MAX_SLACK)
    # the float32 entries no longer sum to zero along a row: the constant
    # vector leaves the null space by their rounding, at most F32_EPS of
    # the largest absolute row sum, so lam_min stays below ridge by that
    lam_min = ridge - F32_EPS * lam_max
    nnz = int(vals.size)
    return Deployment(
        op=op, n=n, lam_min=lam_min, lam_max=lam_max, rows=rows,
        cols=cols, vals=vals,
        storage={"kind": "sparse", "n": n, "nnz": nnz, "value_bytes": 4,
                 "index_bytes": 4},
        describe=(f"chung-lu graph n={n} edges={(nnz - n) // 2} nnz={nnz} "
                  f"max_degree={int(links.max())} "
                  f"median_degree={float(np.median(links))!r} "
                  f"isolated={int(np.sum(links == 0))} ridge={ridge!r} "
                  f"lam_min={lam_min!r} lam_max={lam_max!r} "
                  f"kappa_bound={lam_max / lam_min!r} coo_bytes="
                  f"{op.rows.nbytes + op.cols.nbytes + op.vals.nbytes}"))


class Reference:
    """float64 diagonally preconditioned CG on the host, certified by its
    residual to REF_RTOL of each answer. Imports nothing of the program."""

    def __init__(self, dep: Deployment):
        import scipy.sparse

        self._a = scipy.sparse.csr_matrix(
            (dep.vals.astype(np.float64), (dep.rows, dep.cols)),
            shape=(dep.n, dep.n))
        self._inv_diag = 1.0 / self._a.diagonal()
        self._lam_min = float(dep.lam_min)

    def quad(self, us: np.ndarray) -> np.ndarray:
        u = np.asarray(us, np.float64)
        return np.concatenate([self._solve(u[s:s + REF_CHUNK].T)
                               for s in range(0, len(u), REF_CHUNK)]
                              or [np.zeros(0)])

    def _solve(self, b: np.ndarray) -> np.ndarray:
        a, minv = self._a, self._inv_diag[:, None]
        x = np.zeros_like(b)
        r = b.copy()
        z = minv * r
        p = z.copy()
        rz = np.sum(r * z, axis=0)
        for it in range(1, REF_MAX_ITERS + 1):
            ap = a @ p
            alpha = rz / np.sum(p * ap, axis=0)
            x += alpha * p
            r -= alpha * ap
            z = minv * r
            rz_new = np.sum(r * z, axis=0)
            p = z + (rz_new / rz) * p
            rz = rz_new
            if it % 5 == 0:
                res = b - a @ x      # the true residual, not the recurrence
                est = np.sum(b * x, axis=0) + np.sum(x * res, axis=0)
                err = np.sum(res * res, axis=0) / self._lam_min
                if np.all(err <= REF_RTOL * np.abs(est)):
                    return est + 0.5 * err
        raise RuntimeError(f"reference CG did not certify {REF_RTOL} in "
                           f"{REF_MAX_ITERS} iterations")


# ---------------------------------------------------------------------------
# The control: the reference's solve with bfloat16 entries and vectors


@partial(jax.jit, static_argnames=("iters", "n"))
def _control_quad(rows, cols, vals, inv_diag, b, *, iters, n):
    vb = round_bf16(vals)

    def matmul(x):                                    # x (n, m)
        return jax.ops.segment_sum(vb[:, None] * round_bf16(x)[cols], rows,
                                   num_segments=n, indices_are_sorted=True)

    minv = inv_diag[:, None]

    def body(_, c):
        x, r, p, rz = c
        ap = matmul(p)
        alpha = rz / jnp.maximum(jnp.sum(p * ap, axis=0), 1e-30)
        x = x + alpha * p
        r = r - alpha * ap
        z = minv * r
        rz_new = jnp.sum(r * z, axis=0)
        p = z + (rz_new / jnp.maximum(rz, 1e-30)) * p
        return x, r, p, rz_new

    z0 = minv * b
    c = jax.lax.fori_loop(0, iters, body,
                          (jnp.zeros_like(b), b, z0, jnp.sum(b * z0, axis=0)))
    return jnp.sum(b * c[0], axis=0)


CONTROL_COLUMNS = 64   # columns per device solve: (nnz, 64) f32 products


def control_quad(dep: Deployment, us: np.ndarray, iters: int) -> np.ndarray:
    op = dep.op
    inv_diag = 1.0 / op.diag_vals
    out = []
    for s in range(0, len(us), CONTROL_COLUMNS):
        b = jnp.asarray(us[s:s + CONTROL_COLUMNS], jnp.float32).T
        out.append(np.asarray(_control_quad(op.rows, op.cols, op.vals,
                                            inv_diag, b, iters=iters,
                                            n=dep.n), np.float64))
    return np.concatenate(out)
