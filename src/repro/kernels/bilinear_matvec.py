"""Fused bilinear matvec Pallas kernel — the Lanczos hot spot.

One pass over A computes BOTH
    y     = A @ x                (the next Krylov direction)
    alpha = x^T A x              (the Lanczos diagonal coefficient)
so HBM traffic for A (the dominant term: N^2 elements vs N for vectors)
is paid once per GQL iteration instead of twice.

TPU mapping: A is streamed HBM->VMEM in (bm, bn) tiles (128-aligned for
the MXU); the per-row accumulator and the alpha accumulator live in VMEM
scratch. Batched over independent quadrature systems on the leading grid
dimension (DESIGN.md Sec. 3 item 2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, xj_ref, xi_ref, y_ref, al_ref, acc_y, acc_al):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc_y[...] = jnp.zeros_like(acc_y)

    @pl.when((i == 0) & (j == 0))
    def _():
        acc_al[...] = jnp.zeros_like(acc_al)

    a = a_ref[0]            # (bm, bn)
    xj = xj_ref[0]          # (bn,)
    t = jax.lax.dot_general(a, xj.astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bm,)
    acc_y[...] += t
    acc_al[0] += jnp.sum(xi_ref[0].astype(jnp.float32) * t)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        y_ref[0] = acc_y[...].astype(y_ref.dtype)

    @pl.when((i == pl.num_programs(1) - 1) & (j == pl.num_programs(2) - 1))
    def _():
        al_ref[0] = acc_al[0].astype(al_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def fused_matvec(a: jax.Array, x: jax.Array, *, bm: int = 128,
                 bn: int = 128, interpret: bool | None = None):
    """y = A @ x and alpha = x^T A x, batched.

    a: (B, N, N) symmetric blocks; x: (B, N). N is zero-padded up to the
    tile size by the wrapper (zero rows/cols change neither y's valid
    entries nor alpha). ``interpret=None`` picks interpret mode off the
    TPU only.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, n, _ = a.shape
    bm = bn = min(bm, bn, n)
    n_pad = -n % bm
    if n_pad:
        a = jnp.pad(a, ((0, 0), (0, n_pad), (0, n_pad)))
        x = jnp.pad(x, ((0, 0), (0, n_pad)))
    npad = n + n_pad
    grid = (b, npad // bm, npad // bn)

    y, al = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bn), lambda b, i, j: (b, i, j)),
            pl.BlockSpec((1, bn), lambda b, i, j: (b, j)),
            pl.BlockSpec((1, bm), lambda b, i, j: (b, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, bm), lambda b, i, j: (b, i)),
            pl.BlockSpec((1,), lambda b, i, j: (b,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, npad), jnp.float32),
            jax.ShapeDtypeStruct((b,), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(a, x, x)
    return y[:, :n], al
