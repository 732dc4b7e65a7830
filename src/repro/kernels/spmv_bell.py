"""Blocked-ELL sparse matvec Pallas kernel.

The paper's speedups live on *sparse* kernels (Table 1: densities 0.009%
to 11%). CSR gather/scatter is hostile to the MXU and to Pallas' static
shapes, so we store A as blocked-ELL (DESIGN.md Sec. 3 item 3):

    data: (R, K, bs, bs)   R = N/bs block-rows, K = max blocks per row
    cols: (R, K) int32     block-column index of each stored block
                           (padding blocks point at column 0 with zero data)

The kernel walks (r, k) with the block-column table scalar-prefetched so
the x tile for step (r, k) is fetched by index_map — dense 128x128 MXU
multiplies at FLOPs proportional to stored blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(cols_ref, d_ref, x_ref, o_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jax.lax.dot_general(
        d_ref[0, 0], x_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def bell_matvec(data: jax.Array, cols: jax.Array, x: jax.Array, *,
                interpret: bool = True) -> jax.Array:
    """y = A @ x for blocked-ELL A; x: (N,) with N = R * bs.

    One column of :func:`bell_matvec_mrhs`: Mosaic tiles a block's two
    minor dims by (8, 128), and a rank-1 ``(bs,)`` block does not lower,
    so x rides as an (N, 1) column."""
    return bell_matvec_mrhs(data, cols, x[:, None], interpret=interpret)[:, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def bell_matvec_mrhs(data: jax.Array, cols: jax.Array, x: jax.Array, *,
                     interpret: bool = True) -> jax.Array:
    """Y = A @ X for blocked-ELL A; X: (N, m) column-stacked right-hand
    sides, N = R * bs. The block-column table is scalar-prefetched, so
    each (r, k) step gathers the x rows of its stored block through the
    index map and runs one (bs, bs) @ (bs, m) MXU gemm — the m columns
    ride one pass over the stored blocks instead of m passes."""
    r, k, bs, _ = data.shape
    m = x.shape[-1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r, k),
        in_specs=[
            pl.BlockSpec((1, 1, bs, bs), lambda r, k, cols: (r, k, 0, 0)),
            pl.BlockSpec((bs, m), lambda r, k, cols: (cols[r, k], 0)),
        ],
        out_specs=pl.BlockSpec((bs, m), lambda r, k, cols: (r, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r * bs, m), jnp.float32),
        # the output block for step (r, k) accumulates over k: the
        # block-row axis is parallel, the block-column walk is not
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(cols, data, x)


def bell_matvec_ref(data: jax.Array, cols: jax.Array, x: jax.Array
                    ) -> jax.Array:
    """Reference blocked-ELL SpMV in pure jnp, batched over leading dims.

    ``data``/``cols`` may carry leading batch dims matching ``x``'s (a
    stacked operator), or none (one matrix shared across all lanes of
    ``x``). Computes in ``x.dtype`` (the Pallas kernel is fixed to f32).
    """
    r, k, bs, _ = data.shape[-4:]
    xb = x.reshape(x.shape[:-1] + (r, bs))
    if cols.ndim == 2:
        gathered = xb[..., cols, :]                     # (..., R, K, bs)
        y = jnp.einsum("rkij,...rkj->...ri", data.astype(x.dtype), gathered,
                       precision=jax.lax.Precision.HIGHEST)
    else:
        # stacked operator: gather each lane's x blocks by its own table
        # (x must carry the same leading lane dims as cols)
        flat_idx = cols.reshape(cols.shape[:-2] + (r * k,))
        gathered = jnp.take_along_axis(xb, flat_idx[..., None], axis=-2)
        gathered = gathered.reshape(cols.shape[:-2] + (r, k, bs))
        y = jnp.einsum("...rkij,...rkj->...ri", data.astype(x.dtype),
                       gathered, precision=jax.lax.Precision.HIGHEST)
    return y.reshape(x.shape[:-1] + (r * bs,))


def dense_to_bell(a, bs: int = 128, k_max: int | None = None,
                  dtype=np.float32):
    """Convert a dense (numpy) symmetric matrix to blocked-ELL arrays.

    Returns (data (R,K,bs,bs), cols (R,K) i32, n). Zero-pads N up to
    a multiple of ``bs``; rows with fewer than K non-zero blocks are
    padded with zero blocks pointing at column 0.
    """
    a = np.asarray(a, dtype)
    n = a.shape[0]
    npad = -n % bs
    if npad:
        a = np.pad(a, ((0, npad), (0, npad)))
    nn = a.shape[0]
    r = nn // bs
    blocks = a.reshape(r, bs, r, bs).transpose(0, 2, 1, 3)  # (R, R, bs, bs)
    nz = np.abs(blocks).max(axis=(2, 3)) > 0                # (R, R)
    per_row = nz.sum(axis=1)
    k = int(per_row.max()) if k_max is None else k_max
    k = max(k, 1)
    data = np.zeros((r, k, bs, bs), a.dtype)
    cols = np.zeros((r, k), np.int32)
    for i in range(r):
        js = np.nonzero(nz[i])[0][:k]
        data[i, :len(js)] = blocks[i, js]
        cols[i, :len(js)] = js
    return jnp.asarray(data), jnp.asarray(cols), n
