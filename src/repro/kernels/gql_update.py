"""Fused batched GQL recurrence update — one VPU pass per iteration.

The per-iteration scalar update of Alg. 5 (Sherman-Morrison + the three
modified-Jacobi extensions) is ~40 elementwise ops on 8 state lanes. As
separate XLA ops on a (B,)-batch this is eight kernel launches of tiny
arithmetic; fused in Pallas it is a single VPU pass over 8x128 lanes.

The kernel body re-implements the arithmetic explicitly (it is the unit
under test); the oracle is ``repro.core.gql.recurrence_update``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_EPS = 1e-30


def recurrence_math(alpha_n, beta_n, beta_p, g, c, delta, d_lr, d_rr,
                    lam_min, lam_max):
    """Traced arithmetic of one Alg. 5 recurrence update, written for
    in-kernel use (plain jnp elementwise ops on values, not refs). The
    oracle is ``repro.core.gql.recurrence_update``."""
    b2p = beta_p * beta_p
    delta_s = jnp.maximum(delta, _EPS)
    dlr_s = jnp.maximum(d_lr, _EPS)
    drr_s = jnp.minimum(d_rr, -_EPS)

    den_g = delta_s * (alpha_n * delta_s - b2p)
    g_new = g + b2p * (c * c) / jnp.maximum(den_g, _EPS)
    c_new = c * beta_p / delta_s
    delta_new = alpha_n - b2p / delta_s
    dlr_new = alpha_n - lam_min - b2p / dlr_s
    drr_new = alpha_n - lam_max - b2p / drr_s

    # extensions with beta_{i+1}
    b2 = beta_n * beta_n
    dlr_c = jnp.maximum(dlr_new, _EPS)
    drr_c = jnp.minimum(drr_new, -_EPS)
    dn_c = jnp.maximum(delta_new, _EPS)
    alpha_lr = lam_min + b2 / dlr_c
    alpha_rr = lam_max + b2 / drr_c
    den_lo = drr_c - dlr_c
    b2_lo = (lam_max - lam_min) * dlr_c * drr_c / den_lo
    alpha_lo = (lam_max * drr_c - lam_min * dlr_c) / den_lo

    c2 = c_new * c_new

    def sm(alpha_hat, b2_hat):
        # identical guard to core.gql._extensions (the oracle)
        den = dn_c * (alpha_hat * dn_c - b2_hat)
        safe = jnp.where(den >= 0, jnp.maximum(den, _EPS),
                         jnp.minimum(den, -_EPS))
        return g_new + b2_hat * c2 / safe

    return (g_new, c_new, delta_new, dlr_new, drr_new,
            sm(alpha_rr, b2), sm(alpha_lr, b2), sm(alpha_lo, b2_lo))


def _kernel(alpha_ref, beta_ref, betap_ref, g_ref, c_ref, delta_ref,
            dlr_ref, drr_ref, lmin_ref, lmax_ref,
            g_o, c_o, delta_o, dlr_o, drr_o, grr_o, glr_o, glo_o):
    (g_o[...], c_o[...], delta_o[...], dlr_o[...], drr_o[...],
     grr_o[...], glr_o[...], glo_o[...]) = recurrence_math(
        alpha_ref[...], beta_ref[...], betap_ref[...], g_ref[...],
        c_ref[...], delta_ref[...], dlr_ref[...], drr_ref[...],
        lmin_ref[...], lmax_ref[...])


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def gql_update(alpha_n, beta_n, beta_p, g, c, delta, d_lr, d_rr,
               lam_min, lam_max, *, block: int = 1024,
               interpret: bool = True):
    """Batched fused recurrence update over (B,) lanes."""
    bsz = alpha_n.shape[-1]
    lam_min = jnp.broadcast_to(jnp.asarray(lam_min, g.dtype), g.shape)
    lam_max = jnp.broadcast_to(jnp.asarray(lam_max, g.dtype), g.shape)
    block = min(block, bsz)
    pad = -bsz % block
    ins = [alpha_n, beta_n, beta_p, g, c, delta, d_lr, d_rr,
           lam_min, lam_max]
    if pad:
        # pad with benign values (delta=1, drr=-1) to avoid spurious infs
        fills = [1.0, 0.0, 0.0, 0.0, 0.0, 1.0, 1.0, -1.0, 0.0, 1.0]
        ins = [jnp.pad(v, (0, pad), constant_values=f)
               for v, f in zip(ins, fills)]
    n = bsz + pad
    grid = (n // block,)
    spec = pl.BlockSpec((block,), lambda i: (i,))
    outs = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[spec] * 10,
        out_specs=[spec] * 8,
        out_shape=[jax.ShapeDtypeStruct((n,), g.dtype)] * 8,
        interpret=interpret,
    )(*ins)
    return tuple(o[:bsz] for o in outs)
