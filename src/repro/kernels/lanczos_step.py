"""Fused Lanczos step kernel — the pass over A of one iteration.

A quadrature iteration needs ``w = matvec(v)`` and ``alpha = v^T w``,
then the residual, the optional reorth projection, ``beta = ||r||`` and
the bracket recurrence. Wrapped operators reach the kernel through the
diagonal-sandwich form (``core.operators.fused_operands``):

    matvec(x) = s_out * (A @ (s_in * x)) + t * x

which is closed under Masked / Shifted / Jacobi. One ``pallas_call``
streams A once per lane block (every lane of a pool of up to
``_LANE_BLOCK`` shares each tile) and runs the sandwich and ``alpha`` in
the epilogue of each output tile, so ``w`` and ``alpha`` leave the
kernel together. Two payload flavors:

* **Dense tile** (``_dense_kernel``): grid ``(lane_blocks, row_blocks,
  col_blocks)`` over ``(_ROW_BLOCK, _COL_BLOCK)`` tiles of A, so the
  VMEM footprint does not grow with N.
* **Blocked-ELL** (``_bell_kernel``): the scalar-prefetch walk of
  ``spmv_bell.py`` over ``(block_row, block_col)`` pairs; the lane block
  of x for each stored block is gathered by its index map.

Lanes are rows of 2-D ``(K, N)`` blocks and ``alpha`` is a ``(K, 1)``
block: Mosaic tiles the two minor dims of a block by ``(8, 128)``, so
rank-1 per-lane blocks and a 1-D accumulator do not lower.

Everything after ``alpha`` needs the whole of ``w`` (the residual needs
the global alpha, ``beta`` the whole residual, reorth two passes over
the basis), so it runs as plain jnp through the reference code
(``lanczos.lanczos_finish`` / ``gql.gql_finish``) and XLA fuses it: at
K = 64 lanes and N = 16,384 that tail moves tens of MB against the
1 GiB of A. Operators with no sandwich form (SparseCOO, MatvecFn) fall
back to the reference composition bit-for-bit.

Off-TPU the kernels run in interpret mode in the native dtype, so the
fused path only differs from the reference by summation order inside
the matvec and alpha (<= 1e-12 relative on gemm-backed operators).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import gql as _gql
from ..core import lanczos as _lanczos
from ..core import operators as _operators

_LANE_BLOCK = 128     # lanes per grid step; larger pools re-stream A
_ROW_BLOCK = 1024     # rows of A (entries of w) per output tile
_COL_BLOCK = 512      # columns of A per streamed tile
_ALIGN = 128          # TPU lane width: N pads to a multiple of this


def _tile(n: int, cap: int) -> int:
    """Largest power-of-two multiple of ``_ALIGN`` that divides ``n``
    (itself a multiple of ``_ALIGN``) and does not exceed ``cap``."""
    t = _ALIGN
    while t * 2 <= cap and n % (t * 2) == 0:
        t *= 2
    return t


def _lane_block(k: int) -> tuple[int, int]:
    """(lanes per block, padded lane count): one block holds every lane
    of a pool up to ``_LANE_BLOCK``; a block equal to the full lane dim
    needs no multiple-of-8 padding."""
    if k <= _LANE_BLOCK:
        return k, k
    return _LANE_BLOCK, -(-k // _LANE_BLOCK) * _LANE_BLOCK


def _epilogue(acc, so_ref, t_ref, v_ref, w_ref, al_ref, first):
    """Finish one output tile: the sandwich ``w = s_out * acc + t * v``
    and this tile's share of ``alpha = v^T w``."""
    v = v_ref[...]
    w = so_ref[...] * acc + t_ref[...] * v
    w_ref[...] = w

    @pl.when(first)
    def _():
        al_ref[...] = jnp.zeros_like(al_ref)

    al_ref[...] += jnp.sum(v * w, axis=-1, keepdims=True)


# ---------------------------------------------------------------------------
# Dense flavor


def _dense_kernel(nj, x_ref, a_ref, so_ref, t_ref, v_ref, w_ref, al_ref,
                  acc):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    # (bk, bn) lanes x (bm, bn) tile of A, contracted over the columns
    acc[...] += jax.lax.dot_general(
        x_ref[...], a_ref[...].astype(acc.dtype), (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=acc.dtype)

    @pl.when(j == nj - 1)
    def _():
        _epilogue(acc[...], so_ref, t_ref, v_ref, w_ref, al_ref, i == 0)


def _pad_lanes(arrays, k: int, kp: int, n: int, np_: int):
    if kp == k and np_ == n:
        return arrays
    return [jnp.pad(x, ((0, kp - k), (0, np_ - n))) for x in arrays]


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_step_dense(a, x, s_out, t, v, *, interpret: bool = True):
    """``(w, alpha)`` for K lanes with one dense A shared by every lane.

    ``a``: (N, N); ``x = s_in * v``, ``s_out``, ``t`` and ``v``: (K, N).
    Returns ``w = s_out * (A @ x) + t * v`` (K, N) and ``alpha = v^T w``
    (K,). N pads to a multiple of 128 (zero rows and columns change
    neither), lanes to whole lane blocks.
    """
    kk, n = v.shape
    dtype = v.dtype
    bk, kp = _lane_block(kk)
    np_ = -(-n // _ALIGN) * _ALIGN
    bm, bn = _tile(np_, _ROW_BLOCK), _tile(np_, _COL_BLOCK)
    if np_ != n:
        a = jnp.pad(a, ((0, np_ - n), (0, np_ - n)))
    x, s_out, t, v = _pad_lanes([x, s_out, t, v], kk, kp, n, np_)
    ni, nj = np_ // bm, np_ // bn
    row = pl.BlockSpec((bk, bm), lambda l, i, j: (l, i))
    w, alpha = pl.pallas_call(
        functools.partial(_dense_kernel, nj),
        grid=(kp // bk, ni, nj),
        in_specs=[pl.BlockSpec((bk, bn), lambda l, i, j: (l, j)),
                  pl.BlockSpec((bm, bn), lambda l, i, j: (i, j)),
                  row, row, row],
        out_specs=[row, pl.BlockSpec((bk, 1), lambda l, i, j: (l, 0))],
        out_shape=[jax.ShapeDtypeStruct((kp, np_), dtype),
                   jax.ShapeDtypeStruct((kp, 1), dtype)],
        scratch_shapes=[pltpu.VMEM((bk, bm), dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(x, a, s_out, t, v)
    return w[:kk, :n], alpha[:kk, 0]


# ---------------------------------------------------------------------------
# Blocked-ELL flavor


def _bell_kernel(nk, cols_ref, x_ref, d_ref, so_ref, t_ref, v_ref, w_ref,
                 al_ref):
    r, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _():
        w_ref[...] = jnp.zeros_like(w_ref)

    # gathered (bk, bs) lane block x stored (bs, bs) block, transposed:
    # row i of the block row is sum_j D[i, j] x[j]
    w_ref[...] += jax.lax.dot_general(
        x_ref[...], d_ref[0, 0].astype(w_ref.dtype),
        (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=w_ref.dtype)

    @pl.when(k == nk - 1)
    def _():
        _epilogue(w_ref[...], so_ref, t_ref, v_ref, w_ref, al_ref, r == 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_step_bell(data, cols, x, s_out, t, v, *, interpret: bool = True):
    """``(w, alpha)`` for K lanes with one blocked-ELL A shared by every
    lane.

    ``data``: (R, Kb, bs, bs), ``cols``: (R, Kb); ``x = s_in * v``,
    ``s_out``, ``t`` and ``v``: (K, N) with N <= R * bs (zero-padded
    here). Same outputs as :func:`fused_step_dense`.
    """
    nr, nk, bs, _ = data.shape
    kk, n = v.shape
    dtype = v.dtype
    bk, kp = _lane_block(kk)
    np_ = nr * bs
    x, s_out, t, v = _pad_lanes([x, s_out, t, v], kk, kp, n, np_)
    row = pl.BlockSpec((bk, bs), lambda l, r, k, cols: (l, r))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(kp // bk, nr, nk),
        in_specs=[
            pl.BlockSpec((bk, bs), lambda l, r, k, cols: (l, cols[r, k])),
            pl.BlockSpec((1, 1, bs, bs), lambda l, r, k, cols: (r, k, 0, 0)),
            row, row, row,
        ],
        out_specs=[row, pl.BlockSpec((bk, 1), lambda l, r, k, cols: (l, 0))],
    )
    w, alpha = pl.pallas_call(
        functools.partial(_bell_kernel, nk),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((kp, np_), dtype),
                   jax.ShapeDtypeStruct((kp, 1), dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(cols, x, data, s_out, t, v)
    return w[:kk, :n], alpha[:kk, 0]


# ---------------------------------------------------------------------------
# Dispatcher


def _flatten_lanes(x, batch, trailing):
    """Broadcast ``x`` against ``batch + trailing`` and flatten ``batch``."""
    x = jnp.broadcast_to(x, batch + trailing)
    return x.reshape((-1,) + trailing)


def gql_step_fused(op, st: _gql.GQLState, lam_min, lam_max,
                   basis=None, interpret: bool | None = None
                   ) -> _gql.GQLState:
    """Drop-in replacement for ``core.gql.gql_step`` that produces ``w``
    and ``alpha`` in the fused kernel when ``op`` admits the sandwich
    form, and runs the reference composition otherwise (bit-exact).
    ``interpret=None`` picks interpret mode off the TPU only.

    A lane-stacked base (one A per lane) runs the kernel once per lane
    through ``vmap``; a shared base runs it once for the whole pool."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    form = _operators.fused_operands(op)
    if form is None:
        return _gql.gql_step(op, st, lam_min, lam_max, basis=basis)
    base, s_out, s_in, t = form

    dtype = st.lz.v.dtype
    batch = st.lz.v.shape[:-1]
    n = st.lz.v.shape[-1]
    s_out, t, v = (_flatten_lanes(jnp.asarray(y, dtype), batch, (n,))
                   for y in (s_out, t, st.lz.v))
    x = _flatten_lanes(jnp.asarray(s_in, dtype) * st.lz.v, batch, (n,))

    if isinstance(base, _operators.Dense):
        mats, ranks = (base.a,), (2,)
        kernel = fused_step_dense
    else:
        mats, ranks = (base.data, base.cols), (4, 2)
        kernel = fused_step_bell
    step = functools.partial(kernel, interpret=interpret)
    if mats[0].ndim == ranks[0]:
        w, alpha = step(*mats, x, s_out, t, v)
    else:
        # one matrix per lane: each lane is a one-lane pool of its own
        mats = tuple(_flatten_lanes(m, batch, m.shape[m.ndim - rank:])
                     for m, rank in zip(mats, ranks))
        w, alpha = jax.vmap(
            lambda *args: tuple(o[0] for o in step(
                *args[:-4], *(y[None] for y in args[-4:]))))(
            *mats, x, s_out, t, v)

    lz = _lanczos.lanczos_finish(st.lz, w.reshape(batch + (n,)),
                                 alpha.reshape(batch), basis)
    return _gql.gql_finish(st, lz, lam_min, lam_max)
