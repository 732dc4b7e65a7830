"""Linear-operator abstraction used by the quadrature core.

Every operator is a registered pytree (so it can cross ``jit``/``vmap``/
``scan`` boundaries) exposing:

  * ``matvec(x)``   -- y = A @ x, batched over leading dims of ``x``;
  * ``diag()``      -- the diagonal (for Jacobi preconditioning / Gershgorin);
  * ``n``           -- the (static) dimension N.

Operators compose: ``Masked(Dense(A), m)`` is the TPU-friendly fixed-shape
stand-in for the principal submatrix A_Y (mask semantics below), and
``Jacobi(...)`` applies the similarity transform of paper Sec. 5.4.
"""
from __future__ import annotations

import dataclasses
from functools import partial, wraps
from typing import Any

import jax
import jax.numpy as jnp

Array = jax.Array

# f32 matmuls on the TPU default to one bf16 pass (~3 significant digits);
# the brackets are only as good as the matvec, so every operator product
# asks for full f32 precision (a no-op on the CPU).
HIGHEST = jax.lax.Precision.HIGHEST


def _pass_scope(matvec):
    """Name the operator pass on the device: every operation a matvec
    emits, kernel or XLA, carries ``op.matvec`` in its name stack (HLO
    metadata, the profiler's framework op name). Metadata only: what is
    compiled and computed is unchanged."""
    @wraps(matvec)
    def scoped(*args, **kwargs):
        with jax.named_scope("op.matvec"):
            return matvec(*args, **kwargs)
    return scoped


def _register(cls, data_fields, meta_fields=()):
    jax.tree_util.register_dataclass(cls, data_fields=list(data_fields),
                                     meta_fields=list(meta_fields))
    return cls


@dataclasses.dataclass(frozen=True)
class Dense:
    """Explicit dense symmetric matrix, shape (..., N, N)."""
    a: Array

    @property
    def n(self) -> int:
        return self.a.shape[-1]

    @_pass_scope
    def matvec(self, x: Array) -> Array:
        return jnp.einsum("...ij,...j->...i", self.a, x, precision=HIGHEST)

    def diag(self) -> Array:
        return jnp.diagonal(self.a, axis1=-2, axis2=-1)


_register(Dense, ["a"])


@dataclasses.dataclass(frozen=True)
class SparseCOO:
    """Symmetric sparse matrix in padded COO form, fixed nnz (jit-stable).

    ``rows``/``cols``/``vals`` have shape (..., nnz); padding entries carry
    ``rows == n`` (scattered with drop semantics). With a shared pattern
    (1-D ``rows``/``cols``) any leading batch dims of ``x`` and/or ``vals``
    broadcast. A *stacked* operator (from ``stack_ops``) carries leading
    lane dims on the index arrays too; ``x`` must then match those dims.
    """
    rows: Array
    cols: Array
    vals: Array
    n_static: int
    diag_vals: Array  # (..., N) dense diagonal, kept explicitly

    @property
    def n(self) -> int:
        return self.n_static

    @_pass_scope
    def matvec(self, x: Array) -> Array:
        if self.rows.ndim == 1:
            # y[r] += v * x[c]; out-of-range rows dropped. The output
            # carries the broadcast batch dims of vals AND x.
            contrib = self.vals * jnp.take(x, self.cols, axis=-1,
                                           fill_value=0.0)
            y = jnp.zeros(contrib.shape[:-1] + (self.n_static,), x.dtype)
            return y.at[..., self.rows].add(contrib, mode="drop")
        # Batched sparsity pattern: per-lane scatter in lockstep.
        b = jnp.broadcast_shapes(self.rows.shape[:-1], x.shape[:-1])
        nnz = self.rows.shape[-1]
        n = self.n_static

        def flat(a, last):
            return jnp.broadcast_to(a, b + (last,)).reshape((-1, last))

        def one(r, c, v, xx):
            contrib = v * jnp.take(xx, c, fill_value=0.0)
            return jnp.zeros((n,), xx.dtype).at[r].add(contrib, mode="drop")

        y = jax.vmap(one)(flat(self.rows, nnz), flat(self.cols, nnz),
                          flat(self.vals, nnz), flat(x, x.shape[-1]))
        return y.reshape(b + (n,))

    def diag(self) -> Array:
        return self.diag_vals


_register(SparseCOO, ["rows", "cols", "vals", "diag_vals"], ["n_static"])


def sparse_from_dense(a, nnz: int | None = None) -> SparseCOO:
    """Build a padded-COO operator from a dense (numpy/jnp) matrix."""
    import numpy as np

    a = np.asarray(a)
    n = a.shape[-1]
    r, c = np.nonzero(a)
    v = a[r, c]
    cap = int(nnz) if nnz is not None else len(r)
    if len(r) > cap:
        raise ValueError(f"nnz={len(r)} exceeds capacity {cap}")
    pad = cap - len(r)
    r = np.concatenate([r, np.full(pad, n, dtype=r.dtype)])
    c = np.concatenate([c, np.zeros(pad, dtype=c.dtype)])
    v = np.concatenate([v, np.zeros(pad, dtype=v.dtype)])
    return SparseCOO(jnp.asarray(r), jnp.asarray(c), jnp.asarray(v), n,
                     jnp.asarray(np.diagonal(a, axis1=-2, axis2=-1)))


_BELL_MODES = ("reference", "pallas")


@dataclasses.dataclass(frozen=True)
class SparseBELL:
    """Symmetric sparse matrix in blocked-ELL form (DESIGN.md Sec. 6).

    The layout of ``kernels/spmv_bell.py``: ``data`` (..., R, K, bs, bs)
    holds up to K non-zero bs x bs blocks per block-row, ``cols``
    (..., R, K) their block-column indices (padding blocks point at
    column 0 with zero data). N may be smaller than R*bs; matvec
    zero-pads and slices at the boundary.

    ``mode`` picks the execution path: 'reference' is the pure-jnp einsum
    (CPU / oracle), 'pallas' the scalar-prefetch MXU kernel
    (``interpret=None`` auto-selects interpret mode off-TPU). The solver
    rebinds both from ``SolverConfig.backend`` via ``configure_backend``.

    Leading lane dims on ``data``/``cols`` (a ``stack_ops`` stack) batch
    the system; ``x`` must then carry matching lane dims.
    """
    data: Array
    cols: Array
    diag_vals: Array  # (..., N)
    n_static: int
    mode: str = "reference"
    interpret: bool | None = None

    def __post_init__(self):
        if self.mode not in _BELL_MODES:
            raise ValueError(f"mode must be one of {_BELL_MODES}, "
                             f"got {self.mode!r}")

    @property
    def n(self) -> int:
        return self.n_static

    def configured(self, backend: str, interpret: bool | None
                   ) -> "SparseBELL":
        mode = "pallas" if backend == "pallas" else "reference"
        if mode == self.mode and interpret == self.interpret:
            return self
        return dataclasses.replace(self, mode=mode, interpret=interpret)

    @_pass_scope
    def matvec(self, x: Array) -> Array:
        from ..kernels import spmv_bell as _sb  # deferred: pulls in pallas
        r, _, bs, _ = self.data.shape[-4:]
        pad = r * bs - x.shape[-1]
        xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x
        if self.mode == "reference":
            y = _sb.bell_matvec_ref(self.data, self.cols, xp)
        else:
            from ..kernels import ops as _kops
            lanes = jnp.broadcast_shapes(self.data.shape[:-4], xp.shape[:-1])
            xb = jnp.broadcast_to(xp, lanes + xp.shape[-1:])
            kern = lambda d, c, v: _kops.bell_matvec(  # noqa: E731
                d, c, v.astype(jnp.float32), interpret=self.interpret)
            if self.data.ndim == 4:
                # one shared matrix: every lane is a column of ONE
                # multi-RHS sweep, so each stored block is read once
                flat = xb.reshape((-1, xb.shape[-1])).astype(jnp.float32)
                y = _kops.bell_matvec_mrhs(self.data, self.cols, flat.T,
                                           interpret=self.interpret).T
            else:
                db = jnp.broadcast_to(self.data, lanes + self.data.shape[-4:])
                cb = jnp.broadcast_to(self.cols, lanes + self.cols.shape[-2:])
                y = jax.vmap(kern)(
                    db.reshape((-1,) + db.shape[-4:]),
                    cb.reshape((-1,) + cb.shape[-2:]),
                    xb.reshape((-1, xb.shape[-1])))
            y = y.reshape(lanes + y.shape[-1:]).astype(x.dtype)
        return y[..., :self.n_static] if pad else y

    def diag(self) -> Array:
        return self.diag_vals


_register(SparseBELL, ["data", "cols", "diag_vals"],
          ["n_static", "mode", "interpret"])


def bell_from_dense(a, bs: int = 128, k_max: int | None = None,
                    dtype=None, mode: str = "reference",
                    interpret: bool | None = None) -> SparseBELL:
    """Build a blocked-ELL operator from a dense (numpy/jnp) matrix.

    ``dtype=None`` keeps the input dtype (the Pallas kernel itself always
    accumulates in f32; pass f32 data for the TPU path).
    """
    import numpy as np

    from ..kernels import spmv_bell as _sb

    a = np.asarray(a)
    data, cols, n = _sb.dense_to_bell(
        a, bs=bs, k_max=k_max, dtype=a.dtype if dtype is None else dtype)
    return SparseBELL(data, cols, jnp.asarray(np.diagonal(a).copy(),
                                              data.dtype),
                      n, mode=mode, interpret=interpret)


@dataclasses.dataclass(frozen=True)
class Masked:
    """Fixed-shape principal-submatrix operator.

    With projector P = diag(mask), represents  P A P + (I - P).
    For any u supported on the mask, Lanczos on this operator is *exactly*
    Lanczos on the true submatrix A_Y (the identity block is invisible:
    Krylov vectors stay supported on the mask). Eigenvalue interlacing
    guarantees spec(A_Y) within [lam_min(A), lam_max(A)], so global
    spectral bounds on A remain valid for every Y.
    ``mask`` has shape (..., N) and may be batched.
    """
    base: Any
    mask: Array  # float {0.,1.} or bool

    @property
    def n(self) -> int:
        return self.base.n

    @_pass_scope
    def matvec(self, x: Array) -> Array:
        m = self.mask.astype(x.dtype)
        return m * self.base.matvec(m * x) + (1.0 - m) * x

    def diag(self) -> Array:
        m = self.mask.astype(self.base.diag().dtype)
        return m * self.base.diag() + (1.0 - m)


_register(Masked, ["base", "mask"])


@dataclasses.dataclass(frozen=True)
class Shifted:
    """A + sigma * I. ``sigma`` is a scalar, or (..., ) lane-batched (a
    ``stack_ops`` stack): batch dims pair with the batch dims of ``x``,
    never with the vector dim."""
    base: Any
    sigma: Array

    @property
    def n(self) -> int:
        return self.base.n

    def _sigma_col(self) -> Array:
        s = jnp.asarray(self.sigma)
        return s[..., None] if s.ndim else s

    @_pass_scope
    def matvec(self, x: Array) -> Array:
        return self.base.matvec(x) + self._sigma_col() * x

    def diag(self) -> Array:
        return self.base.diag() + self._sigma_col()


_register(Shifted, ["base", "sigma"])


@dataclasses.dataclass(frozen=True)
class Jacobi:
    """Jacobi-preconditioned similarity transform (paper Sec. 5.4).

    With C = diag(A)^(-1/2):   u^T A^-1 u = (Cu)^T (C A C)^-1 (Cu).
    This operator *is* C A C; use ``transform_vector`` for Cu. The
    transformed matrix has unit diagonal, typically shrinking kappa.
    """
    base: Any
    inv_sqrt_diag: Array  # (..., N)

    @classmethod
    def create(cls, base) -> "Jacobi":
        d = base.diag()
        return cls(base, jax.lax.rsqrt(jnp.maximum(d, 1e-30)))

    @property
    def n(self) -> int:
        return self.base.n

    @_pass_scope
    def matvec(self, x: Array) -> Array:
        return self.inv_sqrt_diag * self.base.matvec(self.inv_sqrt_diag * x)

    def diag(self) -> Array:
        return self.inv_sqrt_diag**2 * self.base.diag()

    def transform_vector(self, u: Array) -> Array:
        return self.inv_sqrt_diag * u


_register(Jacobi, ["base", "inv_sqrt_diag"])


@dataclasses.dataclass(frozen=True)
class MatvecFn:
    """Wrap a closure as an operator (used by the distributed monitor,
    where the matvec embeds psums over mesh axes)."""
    fn: Any  # static: callable (..., N) -> (..., N)
    n_static: int
    diag_vals: Array

    @property
    def n(self) -> int:
        return self.n_static

    @_pass_scope
    def matvec(self, x: Array) -> Array:
        return self.fn(x)

    def diag(self) -> Array:
        return self.diag_vals


_register(MatvecFn, ["diag_vals"], ["fn", "n_static"])


# ---------------------------------------------------------------------------
# Multi-vector right-hand sides (block-Krylov mode, DESIGN.md Sec. 13)


@_pass_scope
def matvec_mrhs(op, x: Array) -> Array:
    """y = A @ X for a row-stacked block X of shape (..., b, N) — the
    block-Lanczos workhorse. Row i of the output is ``op.matvec`` of row
    i of ``x``, but shaped so Dense and BELL backends see ONE gemm per
    operator application instead of b gemvs. Leading dims of ``x``
    before the block axis are lanes and pair with lane-stacked operator
    leaves exactly as in :meth:`matvec`; the block axis is always local
    to each lane.

    Semantics (not bit-level equality with b gemvs — a gemm may reduce
    in a different order) match ``matvec`` row by row; the b = 1 slot of
    every backend used by the solver reduces identically.
    """
    if isinstance(op, Dense):
        # lanes broadcast against op.a's batch dims; b rides the gemm
        return jnp.einsum("...ij,...bj->...bi", op.a, x, precision=HIGHEST)
    if isinstance(op, SparseCOO):
        if op.rows.ndim == 1:
            return op.matvec(x)  # shared pattern broadcasts over (..., b)
        # lane-stacked pattern: give the index arrays a length-1 block
        # axis so the lockstep scatter broadcasts over the block slots
        return dataclasses.replace(
            op, rows=op.rows[..., None, :], cols=op.cols[..., None, :],
            vals=op.vals[..., None, :],
            diag_vals=op.diag_vals[..., None, :]).matvec(x)
    if isinstance(op, SparseBELL):
        return _bell_mrhs(op, x)
    if isinstance(op, Masked):
        m = op.mask.astype(x.dtype)
        mb = m[..., None, :] if m.ndim > 1 else m
        return mb * matvec_mrhs(op.base, mb * x) + (1.0 - mb) * x
    if isinstance(op, Shifted):
        s = jnp.asarray(op.sigma)
        sb = s[..., None, None] if s.ndim else s
        return matvec_mrhs(op.base, x) + sb * x
    if isinstance(op, Jacobi):
        c = op.inv_sqrt_diag
        cb = c[..., None, :] if c.ndim > 1 else c
        return cb * matvec_mrhs(op.base, cb * x)
    # MatvecFn and anything else: closures take (..., N) batches, so the
    # block axis is just another batch dim (no gemm shaping available)
    return op.matvec(x)


def _bell_mrhs(op: SparseBELL, x: Array) -> Array:
    from ..kernels import spmv_bell as _sb  # deferred: pulls in pallas

    r, _, bs, _ = op.data.shape[-4:]
    pad = r * bs - x.shape[-1]
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x
    if op.mode == "reference":
        if op.cols.ndim == 2:
            y = _sb.bell_matvec_ref(op.data, op.cols, xp)
        else:
            # lane-stacked tables: length-1 block axis so the per-lane
            # gather broadcasts over the (..., b, N) block rows
            y = _sb.bell_matvec_ref(op.data[..., None, :, :, :, :],
                                    op.cols[..., None, :, :], xp)
    else:
        from ..kernels import ops as _kops
        lanes = jnp.broadcast_shapes(op.data.shape[:-4], xp.shape[:-2])
        xb = jnp.broadcast_to(xp, lanes + xp.shape[-2:])
        # kernel layout is column-stacked (N, b): one gemm per stored
        # block across all b columns of the lane's block
        xt = jnp.swapaxes(xb, -1, -2).astype(jnp.float32)
        kern = lambda d, c, v: _kops.bell_matvec_mrhs(  # noqa: E731
            d, c, v, interpret=op.interpret)
        if not lanes:
            y = kern(op.data, op.cols, xt)
        elif op.data.ndim == 4:
            flat = xt.reshape((-1,) + xt.shape[-2:])
            y = jax.vmap(lambda v: kern(op.data, op.cols, v))(flat)
        else:
            db = jnp.broadcast_to(op.data, lanes + op.data.shape[-4:])
            cb = jnp.broadcast_to(op.cols, lanes + op.cols.shape[-2:])
            y = jax.vmap(kern)(
                db.reshape((-1,) + db.shape[-4:]),
                cb.reshape((-1,) + cb.shape[-2:]),
                xt.reshape((-1,) + xt.shape[-2:]))
        y = jnp.swapaxes(y, -1, -2)
        y = y.reshape(lanes + y.shape[-2:]).astype(x.dtype)
    return y[..., :op.n_static] if pad else y


# ---------------------------------------------------------------------------
# Batched-system helpers (DESIGN.md Sec. 6)


def stack_ops(ops):
    """Stack K same-structure operators into ONE lane-batched operator.

    Every array leaf gains a leading lane axis (``Dense.a`` becomes
    (K, N, N), ``SparseBELL.data`` (K, R, Kb, bs, bs), ...); static
    metadata (n, mode, ...) must agree. The result is a single pytree the
    batched driver can ``matvec`` once per iteration over all K systems.
    For K masks of one shared base matrix prefer :func:`stack_masks`,
    which does not copy the base.
    """
    ops = list(ops)
    if not ops:
        raise ValueError("stack_ops needs at least one operator")
    treedef = jax.tree.structure(ops[0])
    for o in ops[1:]:
        if jax.tree.structure(o) != treedef:
            raise ValueError(
                f"stack_ops needs same-structure operators; got {treedef} "
                f"vs {jax.tree.structure(o)}")
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *ops)


def stack_masks(base, masks) -> Masked:
    """K candidate principal-submatrix masks of ONE base matrix as a
    single lane-batched ``Masked`` operator (the base is shared, not
    copied: only the (K, N) mask is materialized per lane).

    ``masks``: (K, N) array or a sequence of (N,) masks. Feed the result
    plus (K, N)-stacked query vectors to ``BIFSolver.solve_batch`` /
    ``judge_batch`` to score all K candidates in one driver.
    """
    if not isinstance(masks, jax.Array):
        masks = jnp.stack([jnp.asarray(m) for m in masks])
    if masks.ndim < 2:
        raise ValueError(f"stack_masks wants (K, N) masks, got shape "
                         f"{masks.shape}")
    return Masked(base, masks)


# ---------------------------------------------------------------------------
# Lane sharding (DESIGN.md Sec. 7)

# Rank of each array field on an UNBATCHED operator. Leaves whose rank
# exceeds this carry a leading lane axis (a stack_ops / stack_masks
# stack) and are sharded across devices; base-rank leaves are the shared
# problem data and stay replicated. Type-dispatched on purpose: shape
# heuristics would misfire when K == N (greedy MAP runs N lanes against
# an (N, N) base matrix).
_LANE_BASE_RANK = {
    Dense: {"a": 2},
    SparseCOO: {"rows": 1, "cols": 1, "vals": 1, "diag_vals": 1},
    SparseBELL: {"data": 4, "cols": 2, "diag_vals": 1},
    Masked: {"mask": 1},
    Shifted: {"sigma": 0},
    Jacobi: {"inv_sqrt_diag": 1},
    MatvecFn: {"diag_vals": 1},
}

_LANE_WRAPPERS = (Masked, Shifted, Jacobi)


def _lane_spec_for(leaf, base_rank: int, axis: str):
    from jax.sharding import PartitionSpec as P

    extra = jnp.ndim(leaf) - base_rank
    if extra == 0:
        return P()
    if extra == 1:
        return P(axis)  # leading lane dim sharded, trailing dims replicated
    raise ValueError(
        f"operator leaf has {extra} leading lane dims (shape "
        f"{jnp.shape(leaf)}, base rank {base_rank}); the sharded driver "
        f"supports exactly one lane axis")


def lane_specs(op, axis: str = "lanes"):
    """PartitionSpec pytree for ``op`` under lane sharding.

    Same treedef as ``op`` with a ``PartitionSpec`` per array leaf:
    lane-stacked leaves (one extra leading dim over the operator's
    unbatched rank) are sharded on ``axis``; shared leaves replicated.
    Feed to ``shard_map`` in_specs or :func:`shard_ops`.
    """
    cls = type(op)
    if cls not in _LANE_BASE_RANK:
        raise TypeError(f"lane_specs does not know operator type "
                        f"{cls.__name__}")
    ranks = _LANE_BASE_RANK[cls]
    fields = {name: _lane_spec_for(getattr(op, name), rank, axis)
              for name, rank in ranks.items()}
    if cls in _LANE_WRAPPERS:
        fields["base"] = lane_specs(op.base, axis)
    return dataclasses.replace(op, **fields)


def shard_ops(op, mesh, axis: str = "lanes"):
    """Place an operator pytree on a lane mesh: lane-stacked leaves
    sharded over ``axis``, shared leaves (the base matrix) replicated on
    every device. Purely a placement hint — ``shard_map`` in_specs from
    :func:`lane_specs` define the semantics either way."""
    from jax.sharding import NamedSharding

    specs = lane_specs(op, axis)
    return jax.tree.map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)),
        op, specs)


def configure_backend(op, backend: str, interpret: bool | None):
    """Rebind the execution mode of every ``SparseBELL`` inside ``op``
    (walking Masked/Shifted/Jacobi wrappers) to the solver's backend."""
    if isinstance(op, SparseBELL):
        return op.configured(backend, interpret)
    if isinstance(op, (Masked, Shifted, Jacobi)):
        new_base = configure_backend(op.base, backend, interpret)
        if new_base is op.base:
            return op
        return dataclasses.replace(op, base=new_base)
    return op


def fused_operands(op):
    """Flatten ``op`` into the diagonal-sandwich form consumed by the
    fused step kernel (``kernels/lanczos_step.py``):

        matvec(x) = s_out * base.matvec(s_in * x) + t * x

    with ``base`` a :class:`Dense` or :class:`SparseBELL` and ``s_out`` /
    ``s_in`` / ``t`` scalars or arrays broadcastable against ``(..., N)``.
    Every Masked/Shifted/Jacobi wrapper is closed under this form:

        Dense / BELL:  (base, 1, 1, 0)
        Shifted(F, s): t' = t + s
        Jacobi(F, c):  s_out' = c*s_out, s_in' = s_in*c, t' = c*t*c
        Masked(F, m):  s_out' = m*s_out, s_in' = s_in*m,
                       t' = m*t*m + (1 - m)

    Returns ``(base, s_out, s_in, t)`` or ``None`` when ``op`` bottoms
    out in an operator the fused kernel cannot stream (SparseCOO,
    MatvecFn, ...) — callers fall back to the reference composition.
    """
    if isinstance(op, (Dense, SparseBELL)):
        one = jnp.ones((), _dtype_of(op))
        return op, one, one, jnp.zeros((), _dtype_of(op))
    if isinstance(op, Shifted):
        inner = fused_operands(op.base)
        if inner is None:
            return None
        base, s_out, s_in, t = inner
        return base, s_out, s_in, t + op._sigma_col()
    if isinstance(op, Jacobi):
        inner = fused_operands(op.base)
        if inner is None:
            return None
        base, s_out, s_in, t = inner
        c = op.inv_sqrt_diag
        return base, c * s_out, s_in * c, c * t * c
    if isinstance(op, Masked):
        inner = fused_operands(op.base)
        if inner is None:
            return None
        base, s_out, s_in, t = inner
        m = op.mask.astype(_dtype_of(base))
        return base, m * s_out, s_in * m, m * t * m + (1.0 - m)
    return None


def _dtype_of(op):
    if isinstance(op, Dense):
        return op.a.dtype
    if isinstance(op, SparseBELL):
        return op.data.dtype
    return op.diag().dtype
