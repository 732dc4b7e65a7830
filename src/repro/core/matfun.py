"""Matrix-function quadrature: ``u^T f(A) u`` brackets beyond f(x)=1/x.

The GQL recurrence (core/gql.py) hardcodes the Sherman-Morrison pivot
updates that evaluate ``e_1^T J_i^{-1} e_1`` in O(1) per iteration — a
specialization to the paper's f(x) = 1/x. But the machinery around it
(Lanczos -> Jacobi matrix -> Gauss/Radau/Lobatto rules with
retrospective, monotonically tightening brackets) applies to ANY
spectral function whose derivatives have constant sign on the spectral
interval (Golub & Meurant; Zimmerling-Druskin-Simoncini 2024 for the
block/phi(A) setting). This module supplies that generalization:

  * a REGISTRY of spectral functions (inv, log, invsqrt, sqrt) carrying
    the derivative-sign data that decides which of the four quadrature
    rules bounds ``u^T f(A) u`` from above vs below;
  * :class:`CoeffHistory` — the alpha/beta coefficient history of the
    Lanczos tridiagonalization, threaded through the resumable
    :class:`~repro.core.solver.QuadState` (the scalar pivot recurrences
    alone cannot reconstruct J_i for a general f);
  * :func:`estimates` / :func:`bracket` — all four quadrature estimates
    at iteration i, by materializing the iteration-i Jacobi tridiagonal
    (plus its Radau/Lobatto extensions, Golub 1973) and taking
    ``e_1^T f(J) e_1`` via a fixed-size symmetric eigensolve, then
    orienting the bracket per the sign table.

Derivative-sign -> bracket-orientation table (on (0, inf); verified
against dense-eigendecomposition oracles in tests/test_matfun.py):

  quadrature-rule error sign   = s_even  (Gauss)      [I - Q = f^(2i)(x) * (+)]
                               = s_odd   (Radau-left)  [weight (x - a) >= 0]
                               = -s_odd  (Radau-right) [weight (x - b) <= 0]
                               = -s_even (Lobatto)     [weight (x-a)(x-b) <= 0]

  f        s_even  s_odd   lower family          upper family
  inv       +       -      Gauss, Radau-right    Radau-left, Lobatto
  invsqrt   +       -      Gauss, Radau-right    Radau-left, Lobatto
  log       -       +      Radau-left, Lobatto   Gauss, Radau-right
  sqrt      -       +      Radau-left, Lobatto   Gauss, Radau-right

(`I - Q > 0` means the rule UNDERestimates, i.e. bounds from below.)
In every case the two Radau rules form the tight bracket (degree of
exactness 2i vs 2i-1 for Gauss/Lobatto at the same Lanczos depth);
``bracket`` returns them as (lower, upper) and the Gauss/Lobatto pair
as the loose (lower, upper). All four f here have constant-sign
derivatives on (0, inf), so every bracket is a GUARANTEED bound (up to
finite-precision Lanczos; reorthogonalize for sharp containment, the
same caveat as f=1/x — tests/test_convergence.py). A registry entry
with ``guaranteed=False`` would mark an f whose derivatives change
sign on the interval: the four estimates still converge to the true
value but the lower/upper labels become estimates-only.

The per-lane ``fnidx`` array (rather than a static tag) lets ONE
batched drive mix spectral functions across lanes — the serving
engine's mixed-fn request pools ride on exactly this: the eigensolve
is fn-independent, so mixed lanes share it and only the cheap
``f(theta)`` contraction differs.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from . import gql as _gql

Array = jax.Array

_EPS = 1e-30


def _safe_inv(x):
    return 1.0 / jnp.maximum(x, _EPS)


def _safe_log(x):
    return jnp.log(jnp.maximum(x, _EPS))


def _safe_invsqrt(x):
    return jax.lax.rsqrt(jnp.maximum(x, _EPS))


def _safe_sqrt(x):
    return jnp.sqrt(jnp.maximum(x, 0.0))


@dataclasses.dataclass(frozen=True)
class SpectralFn:
    """One registry entry: how to evaluate f on Ritz values and which
    way each quadrature rule bounds (the derivative-sign table above).

    ``s_even``/``s_odd``: sign of the even/odd derivatives of f on
    (0, inf). ``guaranteed``: constant-sign derivatives hold, so the
    four rules are true bounds (not just estimates). ``apply`` clamps
    its argument away from 0 so post-breakdown / padding eigenvalues
    never produce non-finite values (dead lanes are collapsed onto the
    exact Gauss value before these can matter).
    """
    name: str
    index: int
    s_even: int
    s_odd: int
    apply: Callable[[Array], Array]
    guaranteed: bool = True

    @property
    def gauss_is_lower(self) -> bool:
        return self.s_even > 0


REGISTRY: dict[str, SpectralFn] = {
    "inv": SpectralFn("inv", 0, +1, -1, _safe_inv),
    "log": SpectralFn("log", 1, -1, +1, _safe_log),
    "invsqrt": SpectralFn("invsqrt", 2, +1, -1, _safe_invsqrt),
    "sqrt": SpectralFn("sqrt", 3, -1, +1, _safe_sqrt),
}

_FNS = tuple(REGISTRY.values())
# static orientation table, indexed by fnidx
_GAUSS_IS_LOWER = tuple(f.gauss_is_lower for f in _FNS)


def fn_index(fn: str) -> int:
    if fn not in REGISTRY:
        raise ValueError(f"fn must be one of {tuple(REGISTRY)}, got {fn!r}")
    return REGISTRY[fn].index


def fn_name(index: int) -> str:
    return _FNS[int(index)].name


@dataclasses.dataclass(frozen=True)
class CoeffHistory:
    """Per-lane Lanczos coefficient history riding the QuadState.

    ``alphas``/``betas`` have shape (..., M): entry j holds
    alpha_{j+1}/beta_{j+1} of the lane's tridiagonalization, valid for
    j < it (the lane's iteration counter). Writes are indexed by the
    PER-LANE ``it`` (not the global step), so budget-frozen lanes that
    resume later keep a gapless history. ``fnidx`` ((..., ) int32)
    names each lane's spectral function by registry index — a data
    leaf, so it freezes, shards, and checkpoints with the lanes.
    """
    alphas: Array
    betas: Array
    fnidx: Array


jax.tree_util.register_dataclass(
    CoeffHistory, data_fields=["alphas", "betas", "fnidx"], meta_fields=[])

# CoeffHistory threading contract (quadlint QL001): fields the per-step
# writer deliberately never rewrites. `fnidx` names each lane's spectral
# function — set at init/admission, constant across steps; update_coeffs
# only records the new (alpha, beta) row.
COEFF_REPLACE_EXCLUDED = ("fnidx",)


def init_coeffs(st0, fn: str | Array, rows: int) -> CoeffHistory:
    """Coefficient storage for a fresh drive: capacity ``rows``
    iterations, row 0 = iteration 1 (``gql_init``'s alpha_1/beta_1).
    ``fn`` is a registry name (all lanes) or a per-lane index array."""
    dtype = st0.g.dtype
    shape = st0.it.shape
    al = jnp.zeros(shape + (rows,), dtype).at[..., 0].set(st0.lz.alpha)
    be = jnp.zeros(shape + (rows,), dtype).at[..., 0].set(st0.lz.beta)
    if isinstance(fn, str):
        fnidx = jnp.full(shape, fn_index(fn), jnp.int32)
    else:
        fnidx = jnp.broadcast_to(jnp.asarray(fn, jnp.int32), shape)
    return CoeffHistory(alphas=al, betas=be, fnidx=fnidx)


def update_coeffs(coeffs: CoeffHistory, st_prev, st_new) -> CoeffHistory:
    """Record the new iteration's (alpha, beta) at each advancing lane's
    own write cursor (its pre-step ``it``); finished lanes don't write.
    The caller's ``tree_freeze`` still applies on top, exactly like the
    reorth basis."""
    m = coeffs.alphas.shape[-1]
    it = st_prev.it
    hit = (jnp.arange(m, dtype=it.dtype) == it[..., None]) \
        & (~st_prev.done)[..., None]
    return dataclasses.replace(
        coeffs,
        alphas=jnp.where(hit, st_new.lz.alpha[..., None], coeffs.alphas),
        betas=jnp.where(hit, st_new.lz.beta[..., None], coeffs.betas))


def _extension_scalars(st, lam_min, lam_max):
    """Modified last-row entries of the Radau/Lobatto extensions of J_i,
    from the running pivot recurrences the GQL state already carries —
    the SAME ``gql.extension_coefficients`` the Sherman-Morrison
    recurrence uses, so the two routes cannot drift."""
    alpha_lr, alpha_rr, alpha_lo, b2_lo, _ = _gql.extension_coefficients(
        st.lz.beta, st.delta_lr, st.delta_rr, lam_min, lam_max)
    return alpha_lr, alpha_rr, alpha_lo, jnp.sqrt(jnp.maximum(b2_lo, 0.0)), \
        st.lz.beta


@jax.jit
def _eigh(t):
    """``jnp.linalg.eigh`` named ``quad.eigh`` on the device (profiler
    framework op names): the quadrature step's own cost, apart from the
    operator pass. The jit of its own keeps the name on eager calls too,
    which a dispatched op would otherwise compile without; inside a
    compiled caller it inlines, so results are unchanged."""
    with jax.named_scope("quad.eigh"):
        return jnp.linalg.eigh(t)


def estimates(coeffs: CoeffHistory, st, lam_min, lam_max) -> Array:
    """All four unit-normalized quadrature estimates of
    ``e_1^T f(J) e_1`` at the current iteration, stacked on a trailing
    axis in the order (gauss, radau_left, radau_right, lobatto).

    Materializes the iteration-i Jacobi tridiagonal J_i and its three
    one-row extensions inside ONE fixed-size (M+1, M+1) buffer — rows
    past the active block are decoupled (off-diagonal zero, diagonal 1),
    so they contribute eigenpairs with zero weight — and diagonalizes
    the stacked (..., 4, M+1, M+1) batch in one ``eigh``. The estimate
    is then sum_j w_j f(theta_j) with w_j the squared first components,
    with every registered f evaluated on the shared Ritz values and the
    per-lane ``fnidx`` selecting among them (this is what lets one
    batched drive mix spectral functions across lanes).

    Exhausted lanes (Krylov breakdown — the measure is fully resolved,
    Lemma 15) collapse all four estimates onto the exact Gauss value.
    """
    al, be = coeffs.alphas, coeffs.betas
    dtype = al.dtype
    m = al.shape[-1]
    m1 = m + 1
    it = st.it
    lam_min = jnp.asarray(lam_min, dtype)
    lam_max = jnp.asarray(lam_max, dtype)

    j1 = jnp.arange(m1, dtype=it.dtype)
    jm = jnp.arange(m, dtype=it.dtype)
    # active history, embedded in the fixed buffer with a decoupled
    # identity tail (zero off-diagonal => block-diagonal => the tail's
    # eigenvectors carry zero first component and drop out of e_1^T...)
    diag_base = jnp.where(j1 < it[..., None],
                          jnp.concatenate(
                              [al, jnp.ones(al.shape[:-1] + (1,), dtype)],
                              axis=-1),
                          jnp.asarray(1.0, dtype))
    off_gauss = jnp.where(jm < (it - 1)[..., None], be, 0.0)
    off_ext = jnp.where(jm < it[..., None], be, 0.0)  # + beta_i at row i

    a_lr, a_rr, a_lo, b_lo, _ = _extension_scalars(st, lam_min, lam_max)
    at_ext = j1 == it[..., None]           # the appended extension row
    at_blo = jm == (it - 1)[..., None]     # its off-diagonal slot

    def ext_diag(alpha_hat):
        return jnp.where(at_ext, alpha_hat[..., None], diag_base)

    diags = jnp.stack([diag_base, ext_diag(a_lr), ext_diag(a_rr),
                       ext_diag(a_lo)], axis=-2)            # (..., 4, m1)
    offs = jnp.stack([off_gauss, off_ext, off_ext,
                      jnp.where(at_blo, b_lo[..., None], off_gauss)],
                     axis=-2)                               # (..., 4, m)

    eye = jnp.eye(m1, dtype=dtype)
    up = jnp.eye(m1, k=1, dtype=dtype)
    op = jnp.concatenate([offs, jnp.zeros(offs.shape[:-1] + (1,), dtype)],
                         axis=-1)
    t = (diags[..., :, None] * eye
         + op[..., :, None] * up
         + op[..., None, :] * up.T)
    theta, vecs = _eigh(t)
    weights = vecs[..., 0, :] ** 2                          # (..., 4, m1)

    # every registered f on the shared Ritz values; per-lane select
    est = jnp.sum(weights * _FNS[0].apply(theta), axis=-1)  # (..., 4)
    for f in _FNS[1:]:
        est = jnp.where((coeffs.fnidx == f.index)[..., None],
                        jnp.sum(weights * f.apply(theta), axis=-1), est)

    # breakdown => the Gauss estimate is exact; collapse the bracket
    return jnp.where(st.done[..., None], est[..., :1], est)


def bracket(coeffs: CoeffHistory, st, lam_min, lam_max):
    """Sign-aware oriented views of :func:`estimates`, scaled by
    ``||u||^2``: ``(lower, upper, loose_lower, loose_upper)`` with
    (lower, upper) the tight Radau bracket and (loose_lower,
    loose_upper) the Gauss/Lobatto pair, each oriented per the
    registry's derivative-sign table."""
    est = estimates(coeffs, st, lam_min, lam_max)
    scale = st.u_norm_sq[..., None]
    est = jnp.where(scale > 0.0, est * scale, 0.0)
    g, rl, rr, lo = (est[..., 0], est[..., 1], est[..., 2], est[..., 3])
    gauss_lower = jnp.asarray(_GAUSS_IS_LOWER)[coeffs.fnidx]
    lower = jnp.where(gauss_lower, rr, rl)
    upper = jnp.where(gauss_lower, rl, rr)
    loose_lower = jnp.where(gauss_lower, g, lo)
    loose_upper = jnp.where(gauss_lower, lo, g)
    return lower, upper, loose_lower, loose_upper


def log_gain_bounds(t: Array, lo_bif: Array, hi_bif: Array):
    """Bounds on ``log(t - bif)`` given ``bif in [lo_bif, hi_bif]`` —
    the log-gain scorer of the greedy / double-greedy judges, routed
    through the registry's ``log`` entry so the bound orientation lives
    in one place: x -> log(t - x) is DECREASING in x, so the log upper
    bound comes from the BIF lower bound and vice versa. The true Schur
    complement t - bif is positive, but a loose BIF *upper* bound can
    push t - hi_bif <= 0, in which case the log lower bound is -inf
    (the -1e30 sentinel)."""
    log = REGISTRY["log"].apply
    big_neg = jnp.asarray(-1e30, lo_bif.dtype)
    arg_hi = t - lo_bif
    arg_lo = t - hi_bif
    hi = jnp.where(arg_hi > 0, log(arg_hi), big_neg)
    lo = jnp.where(arg_lo > 0, log(arg_lo), big_neg)
    return lo, hi
