"""Gauss Quadrature Lanczos (GQL) — paper Alg. 5, batched for TPU.

Produces, per iteration i, the four quadrature estimates of
``u^T A^{-1} u``:

    g_i      Gauss             (lower bound, Thm. 2)
    g_i^rr   right Gauss-Radau (lower bound, tighter: Thm. 4)
    g_i^lr   left Gauss-Radau  (upper bound, tighter: Thm. 6)
    g_i^lo   Gauss-Lobatto     (upper bound)

Internally all estimates are for the *unit-normalized* problem
``e_1^T J_i^{-1} e_1`` and are multiplied by ||u||^2 at the API boundary.
(Alg. 5 in the paper carries a ||u|| factor that is inconsistent with the
||v||^2 scaling used by Alg. 7; we use the unambiguous Golub-Meurant
convention, which its own Appendix-B proofs follow.)

Modified Jacobi extensions (Radau/Lobatto) follow Golub (1973):
  alpha^lr = lam_min + beta_i^2 / delta_i^lr
  alpha^rr = lam_max + beta_i^2 / delta_i^rr
  (beta^lo)^2 = (lam_max - lam_min) * d_lr * d_rr / (d_rr - d_lr)
  alpha^lo    = (lam_max * d_rr - lam_min * d_lr) / (d_rr - d_lr)
where delta, delta^lr, delta^rr are the running last-pivot recurrences of
J_i, J_i - lam_min I and J_i - lam_max I.

Everything is lockstep-batched with per-lane freezing; see DESIGN.md Sec. 3.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import lanczos as _lz

Array = jax.Array

_EPS = 1e-30


class GQLState(NamedTuple):
    lz: _lz.LanczosState
    # Sherman-Morrison recursion state (unit-normalized)
    g: Array          # Gauss estimate g_i
    c: Array          # c_i = prod beta_j / delta_j  ( [J_i^-1]_{1i} * delta_i )
    delta: Array      # last pivot of J_i
    delta_lr: Array   # last pivot of J_i - lam_min I
    delta_rr: Array   # last pivot of J_i - lam_max I
    # Per-iteration quadrature estimates (unit-normalized)
    g_rr: Array
    g_lr: Array
    g_lo: Array
    # Scaling + termination
    u_norm_sq: Array
    done: Array       # lane finished (breakdown or externally frozen)
    it: Array         # int32, iterations completed


def extension_coefficients(beta, d_lr, d_rr, lam_min, lam_max):
    """Golub (1973) modified last-row entries of the Radau/Lobatto
    extensions of J_i, from the running pivot recurrences:
    ``(alpha_lr, alpha_rr, alpha_lo, b2_lo, b2)``. The ONE home for
    these formulas and their sign guards — shared by the
    Sherman-Morrison recurrence below and the matfun eigensolve
    (core/matfun.py), so the two routes cannot drift."""
    b2 = beta * beta
    d_lr_s = jnp.maximum(d_lr, _EPS)        # last pivot of (J - lmin I) > 0
    d_rr_s = jnp.minimum(d_rr, -_EPS)       # last pivot of (J - lmax I) < 0

    alpha_lr = lam_min + b2 / d_lr_s
    alpha_rr = lam_max + b2 / d_rr_s
    denom_lo = d_rr_s - d_lr_s              # < 0
    b2_lo = (lam_max - lam_min) * d_lr_s * d_rr_s / denom_lo
    alpha_lo = (lam_max * d_rr_s - lam_min * d_lr_s) / denom_lo
    return alpha_lr, alpha_rr, alpha_lo, b2_lo, b2


def _extensions(g, c, delta, d_lr, d_rr, beta, lam_min, lam_max):
    """Radau/Lobatto estimates for the J_i extended with off-diag ``beta``."""
    alpha_lr, alpha_rr, alpha_lo, b2_lo, b2 = extension_coefficients(
        beta, d_lr, d_rr, lam_min, lam_max)
    delta_s = jnp.maximum(delta, _EPS)

    c2 = c * c

    def sm(alpha_hat, b2_hat):
        den = delta_s * (alpha_hat * delta_s - b2_hat)
        # sign-preserving, never-zero guard (den > 0 for live PD lanes;
        # degenerate post-breakdown lanes are frozen by the caller)
        safe = jnp.where(den >= 0, jnp.maximum(den, _EPS),
                         jnp.minimum(den, -_EPS))
        return g + b2_hat * c2 / safe

    return sm(alpha_rr, b2), sm(alpha_lr, b2), sm(alpha_lo, b2_lo)


def gql_init(op, u: Array, lam_min: Array, lam_max: Array) -> GQLState:
    """Iteration i=1 of Alg. 5."""
    lam_min = jnp.asarray(lam_min, u.dtype)
    lam_max = jnp.asarray(lam_max, u.dtype)
    lz = _lz.lanczos_init(op, u)
    u_norm_sq = jnp.sum(u * u, axis=-1)

    alpha1, beta1 = lz.alpha, lz.beta
    g1 = 1.0 / jnp.maximum(alpha1, _EPS)
    c1 = jnp.ones_like(alpha1)
    delta1 = alpha1
    d_lr1 = alpha1 - lam_min
    d_rr1 = alpha1 - lam_max
    g_rr, g_lr, g_lo = _extensions(g1, c1, delta1, d_lr1, d_rr1, beta1,
                                   lam_min, lam_max)
    done = ~lz.live  # immediate breakdown => u is an eigvec combination hit
    zero_u = u_norm_sq <= 0.0
    g1 = jnp.where(zero_u, 0.0, g1)
    g_rr = jnp.where(done, g1, g_rr)
    g_lr = jnp.where(done, g1, g_lr)
    g_lo = jnp.where(done, g1, g_lo)
    return GQLState(lz=lz, g=g1, c=c1, delta=delta1, delta_lr=d_lr1,
                    delta_rr=d_rr1, g_rr=g_rr, g_lr=g_lr, g_lo=g_lo,
                    u_norm_sq=u_norm_sq, done=done | zero_u,
                    it=jnp.ones_like(lz.it))


def recurrence_update(alpha_n, beta_n, beta_p, g, c, delta, d_lr, d_rr,
                      lam_min, lam_max):
    """Pure-math body of one Alg. 5 iteration (no Lanczos, no freezing).

    Elementwise over lanes — this is exactly what the fused Pallas kernel
    ``kernels/gql_update.py`` computes on the VPU; kept here as the single
    source of truth and as its oracle.
    """
    b2p = beta_p * beta_p
    delta_s = jnp.maximum(delta, _EPS)
    d_lr_s = jnp.maximum(d_lr, _EPS)
    d_rr_s = jnp.minimum(d_rr, -_EPS)

    den_g = delta_s * (alpha_n * delta_s - b2p)
    g_new = g + b2p * (c * c) / jnp.maximum(den_g, _EPS)
    c_new = c * beta_p / delta_s
    delta_new = alpha_n - b2p / delta_s
    d_lr_new = alpha_n - lam_min - b2p / d_lr_s
    d_rr_new = alpha_n - lam_max - b2p / d_rr_s

    g_rr, g_lr, g_lo = _extensions(g_new, c_new, delta_new, d_lr_new,
                                   d_rr_new, beta_n, lam_min, lam_max)
    return g_new, c_new, delta_new, d_lr_new, d_rr_new, g_rr, g_lr, g_lo


def gql_assemble(st: GQLState, lz: _lz.LanczosState, raw) -> GQLState:
    """Fold one iteration's raw recurrence outputs into the next state:
    exact-collapse on Krylov exhaustion, frozen-lane pass-through, and
    done/it bookkeeping. The ONE home for this select logic — shared by
    :func:`gql_step` and the fused step kernel
    (``kernels/lanczos_step.py``), so the two routes cannot drift.

    ``lz`` is the post-step Lanczos state; ``raw`` is the 8-tuple
    returned by :func:`recurrence_update` (which may carry garbage on
    lanes with ``st.done`` — every output masks those back)."""
    (g_new, c_new, delta_new, d_lr_new, d_rr_new, g_rr, g_lr, g_lo) = raw

    # Lanes that just exhausted the Krylov space: estimate is exact
    # (Lemma 15); collapse the bracket onto g.
    just_died = st.lz.live & ~lz.live
    g_rr = jnp.where(just_died, g_new, g_rr)
    g_lr = jnp.where(just_died, g_new, g_lr)
    g_lo = jnp.where(just_died, g_new, g_lo)

    upd = ~st.done

    def sel(new, old):
        return jnp.where(upd, new, old)

    return GQLState(
        lz=lz,
        g=sel(g_new, st.g), c=sel(c_new, st.c),
        delta=sel(delta_new, st.delta),
        delta_lr=sel(d_lr_new, st.delta_lr),
        delta_rr=sel(d_rr_new, st.delta_rr),
        g_rr=sel(g_rr, st.g_rr), g_lr=sel(g_lr, st.g_lr),
        g_lo=sel(g_lo, st.g_lo),
        u_norm_sq=st.u_norm_sq,
        done=st.done | ~lz.live,
        it=st.it + upd.astype(jnp.int32),
    )


def gql_step(op, st: GQLState, lam_min: Array, lam_max: Array,
             basis: Array | None = None, recurrence=None) -> GQLState:
    """Iterations i>=2 of Alg. 5; frozen lanes pass through unchanged.

    ``recurrence`` lets callers swap the scalar-update implementation (same
    signature and return as ``recurrence_update``); the solver uses it to
    route the arithmetic through the fused Pallas kernel
    (``kernels/gql_update.py``) instead of the reference path.
    """
    return gql_finish(st, _lz.lanczos_step(op, st.lz, basis=basis),
                      lam_min, lam_max, recurrence)


def gql_finish(st: GQLState, lz: _lz.LanczosState, lam_min: Array,
               lam_max: Array, recurrence=None) -> GQLState:
    """The recurrence and :func:`gql_assemble` for the post-step Lanczos
    state ``lz`` — the part of :func:`gql_step` after the Lanczos step,
    shared with the fused step kernel (``kernels/lanczos_step.py``)."""
    if recurrence is None:
        recurrence = recurrence_update
    lam_min = jnp.asarray(lam_min, st.g.dtype)
    lam_max = jnp.asarray(lam_max, st.g.dtype)
    # Quantities of the *new* iteration (i+1): lz.alpha / lz.beta are
    # alpha_{i+1} / beta_{i+1}; lz.beta_prev is beta_i.
    raw = recurrence(
        lz.alpha, lz.beta, lz.beta_prev, st.g, st.c, st.delta,
        st.delta_lr, st.delta_rr, lam_min, lam_max)
    return gql_assemble(st, lz, raw)


# ---------------------------------------------------------------------------
# Scaled views


def lower_bound(st: GQLState) -> Array:
    """Best available lower bound: right Gauss-Radau (Thm. 4)."""
    return st.g_rr * st.u_norm_sq


def lower_bound_gauss(st: GQLState) -> Array:
    return st.g * st.u_norm_sq


def upper_bound(st: GQLState) -> Array:
    """Best available upper bound: left Gauss-Radau (Thm. 6)."""
    return st.g_lr * st.u_norm_sq


def upper_bound_lobatto(st: GQLState) -> Array:
    return st.g_lo * st.u_norm_sq


def gap(st: GQLState) -> Array:
    return (st.g_lr - st.g_rr) * st.u_norm_sq
