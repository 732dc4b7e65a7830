"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True off the TPU (the kernel bodies execute as
jnp for correctness validation) and False on a TPU backend.
"""
from __future__ import annotations

import jax

from . import bilinear_matvec as _bmv
from . import flash_attention as _fa
from . import gql_update as _gu
from . import lanczos_step as _ls
from . import spmv_bell as _sb


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def gql_step_fused(op, st, lam_min, lam_max, basis=None, *,
                   interpret: bool | None = None):
    """Fused Lanczos+GQL step (one pallas_call per iteration for w, alpha);
    falls back to the reference composition for non-sandwich operators."""
    itp = _default_interpret() if interpret is None else interpret
    return _ls.gql_step_fused(op, st, lam_min, lam_max, basis=basis,
                              interpret=itp)


def fused_matvec(a, x, *, bm: int = 128, bn: int = 128,
                 interpret: bool | None = None):
    """(y, alpha) = (A @ x, x^T A x), batched over the leading dim."""
    itp = _default_interpret() if interpret is None else interpret
    return _bmv.fused_matvec(a, x, bm=bm, bn=bn, interpret=itp)


def bell_matvec(data, cols, x, *, interpret: bool | None = None):
    """Blocked-ELL SpMV."""
    itp = _default_interpret() if interpret is None else interpret
    return _sb.bell_matvec(data, cols, x, interpret=itp)


def bell_matvec_mrhs(data, cols, x, *, interpret: bool | None = None):
    """Blocked-ELL SpMM: x is (N, m) column-stacked right-hand sides."""
    itp = _default_interpret() if interpret is None else interpret
    return _sb.bell_matvec_mrhs(data, cols, x, interpret=itp)


def gql_update(alpha_n, beta_n, beta_p, g, c, delta, d_lr, d_rr,
               lam_min, lam_max, *, interpret: bool | None = None):
    """Fused batched GQL recurrence update."""
    itp = _default_interpret() if interpret is None else interpret
    return _gu.gql_update(alpha_n, beta_n, beta_p, g, c, delta, d_lr, d_rr,
                          lam_min, lam_max, interpret=itp)


def flash_attention(q, k, v, *, causal: bool = True, bt: int = 128,
                    bs: int = 128, interpret: bool | None = None):
    """Streaming attention forward over (BH, T/S, D) layouts."""
    itp = _default_interpret() if interpret is None else interpret
    return _fa.flash_attention(q, k, v, causal=causal, bt=bt, bs=bs,
                               interpret=itp)


def mha_flash(q, k, v, *, causal: bool = True, bt: int = 128, bs: int = 128,
              interpret: bool | None = None):
    itp = _default_interpret() if interpret is None else interpret
    return _fa.mha_flash(q, k, v, causal=causal, bt=bt, bs=bs, interpret=itp)


dense_to_bell = _sb.dense_to_bell
