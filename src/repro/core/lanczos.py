"""Batched Lanczos iteration.

The tridiagonalization driving GQL (paper Alg. 5) and the extremal
eigenvalue estimates (spectrum.py). All state carries arbitrary leading
batch dims; the TPU execution model is lockstep-batched with masked
freezing (DESIGN.md Sec. 3.1).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

Array = jax.Array

BREAKDOWN_TOL = 1e-12


class LanczosState(NamedTuple):
    v_prev: Array   # (..., N) u_{i-2}
    v: Array        # (..., N) u_{i-1}
    alpha: Array    # (...,)  alpha_i   (diagonal entry produced this step)
    beta: Array     # (...,)  beta_i    (off-diagonal produced this step)
    beta_prev: Array  # (...,) beta_{i-1}
    it: Array       # (...,) int32 iteration counter (1-based)
    live: Array     # (...,) bool — False after breakdown (Krylov exhausted)


def lanczos_init(op, u: Array) -> LanczosState:
    """First Lanczos step: alpha_1 = u0^T A u0, beta_1 = ||(A - a1 I) u0||."""
    unorm = jnp.linalg.norm(u, axis=-1, keepdims=True)
    v0 = u / jnp.maximum(unorm, 1e-30)
    w = op.matvec(v0)
    alpha1 = jnp.sum(v0 * w, axis=-1)
    r = w - alpha1[..., None] * v0
    beta1 = jnp.linalg.norm(r, axis=-1)
    live = beta1 > BREAKDOWN_TOL * jnp.maximum(jnp.abs(alpha1), 1.0)
    v1 = jnp.where(live[..., None], r / jnp.maximum(beta1, 1e-30)[..., None], 0.0)
    it = jnp.ones(alpha1.shape, jnp.int32)
    return LanczosState(v_prev=v0, v=v1, alpha=alpha1, beta=beta1,
                        beta_prev=jnp.zeros_like(beta1), it=it, live=live)


def lanczos_assemble(st: LanczosState, alpha: Array, beta: Array,
                     r: Array) -> LanczosState:
    """Fold one step's raw outputs (``alpha``, ``beta = ||r||``, residual
    ``r``) into the next state: breakdown detection, residual
    normalization, and pass-through of frozen lanes. The ONE home for
    this select logic — shared by :func:`lanczos_step` and the fused
    step kernel (``kernels/lanczos_step.py``), so the two routes cannot
    drift. Dead lanes (``st.live`` False) may carry garbage in the raw
    inputs; every output masks them back to the old state."""
    still = st.live & (beta > BREAKDOWN_TOL * jnp.maximum(jnp.abs(alpha), 1.0))
    v_new = jnp.where(still[..., None], r / jnp.maximum(beta, 1e-30)[..., None], 0.0)

    keep = st.live
    return LanczosState(
        v_prev=jnp.where(keep[..., None], st.v, st.v_prev),
        v=jnp.where(keep[..., None], v_new, st.v),
        alpha=jnp.where(keep, alpha, st.alpha),
        beta=jnp.where(keep, beta, st.beta),
        beta_prev=jnp.where(keep, st.beta, st.beta_prev),
        it=st.it + keep.astype(jnp.int32),
        live=still,
    )


def lanczos_step(op, st: LanczosState, basis: Array | None = None) -> LanczosState:
    """One three-term-recurrence step; frozen lanes are passed through.

    ``basis``: optional (..., M, N) stored Lanczos vectors for full
    reorthogonalization (paper Sec. 5.4 'Instability'); rows past the
    current iteration must be zero.
    """
    w = op.matvec(st.v)
    return lanczos_finish(st, w, jnp.sum(st.v * w, axis=-1), basis)


def lanczos_finish(st: LanczosState, w: Array, alpha: Array,
                   basis: Array | None = None) -> LanczosState:
    """The rest of a step once ``w = A v`` and ``alpha = v^T w`` are
    known: residual, optional reorth projection, ``beta = ||r||`` and
    :func:`lanczos_assemble`. Shared by :func:`lanczos_step` and the
    fused step kernel (``kernels/lanczos_step.py``), which produces
    ``w`` and ``alpha`` in one pass over A."""
    r = w - alpha[..., None] * st.v - st.beta[..., None] * st.v_prev
    if basis is not None:
        # r <- r - V^T (V r): one pass of classical Gram-Schmidt against all
        # stored vectors (zero rows contribute nothing).
        coeff = jnp.einsum("...mn,...n->...m", basis, r,
                           precision=jax.lax.Precision.HIGHEST)
        r = r - jnp.einsum("...mn,...m->...n", basis, coeff,
                           precision=jax.lax.Precision.HIGHEST)
    beta = jnp.linalg.norm(r, axis=-1)
    return lanczos_assemble(st, alpha, beta, r)


def tridiag_coefficients(op, u: Array, num_iters: int):
    """Run ``num_iters`` Lanczos steps, returning (alphas, betas, valid).

    alphas: (num_iters, ...), betas: (num_iters, ...) with beta_i the
    off-diagonal produced at step i; valid[i] marks pre-breakdown entries.
    Mostly used by oracles/tests; GQL consumes the state stream directly.
    """
    st0 = lanczos_init(op, u)

    def body(st, _):
        st1 = lanczos_step(op, st)
        return st1, (st1.alpha, st1.beta, st1.live)

    _, (al, be, lv) = jax.lax.scan(body, st0, None, length=num_iters - 1)
    alphas = jnp.concatenate([st0.alpha[None], al], axis=0)
    betas = jnp.concatenate([st0.beta[None], be], axis=0)
    valid = jnp.concatenate([st0.live[None], lv], axis=0)
    return alphas, betas, valid
