"""Unified retrospective-quadrature solver (the paper's Alg. 2, once).

Every workload in this package — adaptive brackets on ``u^T A^-1 u``,
threshold judges for DPP chains, swap judges for k-DPP chains, the
double-greedy gain comparison — is the same loop: iterate Gauss /
Gauss-Radau / Gauss-Lobatto quadrature until the bracket resolves the
caller's decision, freezing lanes that are done (DESIGN.md Sec. 5).
``BIFSolver`` is that loop, exactly once, behind a policy-carrying config:

    solver = BIFSolver(SolverConfig(max_iters=64, rtol=1e-3))
    res = solver.solve(op, u, lam_min=lmn, lam_max=lmx)   # SolveResult
    res = solver.solve(op, u, decide=lambda lo, hi: t < lo)

The loop is an explicit, resumable state machine (DESIGN.md Sec. 8):
``init_state`` / ``step_n`` / ``resume`` / ``finalize`` operate on a
checkpointable :class:`QuadState` pytree, and ``solve`` is just
``finalize(resume(init_state(...)))`` — a consumer can pause a solve at
any iteration, bank its bracket, ship the state, and resume later
bit-exactly (the serving engine's continuous batching and the warm-
started greedy chains are built on exactly this).

Config axes:

  * ``spectrum``     -- where [lam_min, lam_max] comes from when not given
                        explicitly: 'explicit' | 'gershgorin' | 'lanczos'
                        | 'ridge' (spectrum.py estimators, paper Sec. 4.1);
  * ``precondition`` -- 'none' | 'jacobi' (similarity transform, Sec. 5.4);
  * ``reorth``       -- full reorthogonalization of the Lanczos basis
                        (Sec. 5.4 'Instability');
  * ``backend``      -- 'reference' (pure-jnp ``gql.recurrence_update``)
                        | 'pallas' (fused ``kernels/gql_update.py`` VPU
                        kernel for the scalar recurrence only)
                        | 'fused' (``kernels/lanczos_step.py`` kernel:
                        the matvec and alpha in ONE pallas_call per
                        iteration, then an XLA-fused Lanczos/reorth/
                        recurrence tail; operators with no
                        sandwich form fall back to the reference
                        composition bit-exactly);
  * ``decide_every`` -- round cadence R of the stopping rule: the loop
                        runs R shard-local steps between decision rounds
                        (DESIGN.md Sec. 11). Sound by Thm. 4.2 bracket
                        nesting — costs at most R-1 extra contractions
                        per lane, never flips a certified decision.

``BIFSolver`` and ``SolverConfig`` are frozen, hashable, and registered as
static pytrees, so they cross ``jit`` / ``vmap`` / ``scan`` boundaries and
can be closure-captured or passed as arguments freely.

The PR-2 legacy entry points (``bounds.bif_bounds``, ``judge.*``,
``precond.preconditioned_bif_bounds``) that used to shim this driver
were removed per DESIGN.md Sec. 5; quadlint QL005 keeps them out.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from . import block as _block
from . import gql as _gql
from . import matfun as _matfun
from . import operators as _ops
from . import spectrum as _spectrum
from .loop_utils import tree_freeze
from ..obs import registry as _obs_registry

Array = jax.Array

_SPECTRA = ("explicit", "gershgorin", "lanczos", "ridge")
_PRECONDITIONS = ("none", "jacobi")
_BACKENDS = ("reference", "pallas", "fused")


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class SolverConfig:
    """Policy knobs for the retrospective driver (all static metadata)."""
    max_iters: int = 64
    rtol: float = 1e-2
    atol: float = 0.0
    spectrum: str = "explicit"       # 'explicit'|'gershgorin'|'lanczos'|'ridge'
    precondition: str = "none"       # 'none'|'jacobi'
    reorth: bool = False
    backend: str = "reference"       # 'reference'|'pallas'|'fused'
    decide_every: int = 1            # decision-round cadence R (>= 1):
    #                                  evaluate the stopping rule every R
    #                                  steps; states stay round-aligned
    #                                  (step_n quantizes to floor(n/R)*R)
    spectrum_iters: int = 16         # Lanczos steps for spectrum estimation
    ridge: float = 0.0               # known ridge for spectrum='ridge'
    pallas_interpret: bool | None = None  # None: auto (off-TPU -> interpret)
    fn: str = "inv"                  # spectral function (matfun.REGISTRY):
    #                                  'inv' = the legacy GQL recurrence,
    #                                  bit-exact; others bracket u^T f(A) u
    #                                  via the Jacobi-matrix eigensolve
    #                                  (DESIGN.md Sec. 9)
    block_size: int = 1              # block-Krylov width b (DESIGN.md
    #                                  Sec. 13): b > 1 runs the block
    #                                  three-term recurrence on (..., b, N)
    #                                  probe blocks, bracketing
    #                                  tr B^T f(A) B per lane; b = 1 IS
    #                                  the scalar driver (same code path,
    #                                  bit-exact)

    def __post_init__(self):
        if self.spectrum not in _SPECTRA:
            raise ValueError(f"spectrum must be one of {_SPECTRA}, "
                             f"got {self.spectrum!r}")
        if self.precondition not in _PRECONDITIONS:
            raise ValueError(f"precondition must be one of {_PRECONDITIONS}, "
                             f"got {self.precondition!r}")
        if self.backend not in _BACKENDS:
            raise ValueError(f"backend must be one of {_BACKENDS}, "
                             f"got {self.backend!r}")
        if self.max_iters < 1:
            raise ValueError("max_iters must be >= 1")
        if self.decide_every < 1:
            raise ValueError(
                f"decide_every must be >= 1, got {self.decide_every}")
        _matfun.fn_index(self.fn)  # raises on unknown fn tags
        if self.fn != "inv" and self.precondition != "none":
            raise ValueError(
                "precondition='jacobi' is an identity for u^T A^-1 u only "
                "(u^T f(A) u has no similarity-transform counterpart); "
                "fn != 'inv' requires precondition='none'")
        if self.block_size < 1:
            raise ValueError(
                f"block_size must be >= 1, got {self.block_size}")
        if self.block_size > 1:
            if self.reorth:
                raise NotImplementedError(
                    "reorthogonalization is not implemented for the block "
                    "recurrence; block_size > 1 requires reorth=False")
            if self.precondition != "none":
                raise NotImplementedError(
                    "preconditioning transforms each probe column "
                    "separately and would break the block bracket's "
                    "tr B^T f(A) B semantics; block_size > 1 requires "
                    "precondition='none'")


class SolveResult(NamedTuple):
    """Rich per-lane outcome of one retrospective solve."""
    lower: Array          # best lower bound (right Gauss-Radau, Thm. 4)
    upper: Array          # best upper bound (left Gauss-Radau, Thm. 6)
    gauss_lower: Array    # plain Gauss lower bound (Thm. 2)
    lobatto_upper: Array  # Gauss-Lobatto upper bound
    iterations: Array     # int32 quadrature iterations spent per lane
    converged: Array      # resolved by bounds OR Krylov space exhausted
    certified: Array      # resolved by the bounds alone (no exhaustion)
    state: Any            # final QuadState (resume()-able checkpoint)


class JudgeResult(NamedTuple):
    decision: Array     # bool
    certified: Array    # bool — True if resolved by bounds (not fallback)
    iterations: Array   # int32 total quadrature iterations spent


class ArgmaxResult(NamedTuple):
    """Outcome of a certified argmax race over K candidate lanes."""
    index: Array        # int32 — winning lane (last axis of the batch)
    certified: Array    # bool — winner's lower bound cleared every rival
    iterations: Array   # (..., K) int32 per-lane iterations spent
    lower: Array        # (..., K) final score lower bounds
    upper: Array        # (..., K) final score upper bounds


class QuadratureTrace(NamedTuple):
    gauss: Array        # (iters, ...) lower
    radau_lower: Array  # (iters, ...) right Gauss-Radau
    radau_upper: Array  # (iters, ...) left Gauss-Radau
    lobatto: Array      # (iters, ...) upper


class PairState(NamedTuple):
    a: Any  # GQLState for the first (u-side) system
    b: Any  # GQLState for the second (v-side) system


class QuadState(NamedTuple):
    """Checkpointable retrospective-solve state (DESIGN.md Sec. 8).

    The full resumable runtime state of one (batched) Alg.-2 drive: the
    *prepared* operator (backend-configured, preconditioned), the GQL
    recurrence state (Lanczos vectors + bracket + per-lane done/it
    flags), the spectral interval the recurrence was started with, the
    reorthogonalization basis (or None), and the global step counter
    (the basis write cursor). It is an ordinary pytree: it crosses
    ``jit`` boundaries, checkpoints, ships between processes, and —
    leaves sharded on their leading lane axis — lives on a lane mesh.

    Invariant: for any k, ``resume(step_n(state, k))`` is the SAME
    computation as ``resume(state)`` — interrupting and resuming a solve
    reproduces the uninterrupted drive (pinned in tests/test_runtime.py).

    ``coeffs`` (a :class:`~repro.core.matfun.CoeffHistory`, or None on
    the legacy f=1/x path) carries the per-lane alpha/beta Lanczos
    history plus the spectral-function index, making matfun states
    (``SolverConfig.fn != 'inv'``) exactly as checkpointable: the
    ``lower``/``upper`` views below reorient per the registry's
    derivative-sign table (DESIGN.md Sec. 9).
    """
    op: Any           # prepared operator (pytree)
    st: Any           # gql.GQLState — recurrence + bracket + done/it
    lam_min: Array
    lam_max: Array
    basis: Any        # (..., M, N) reorth storage, or None
    step: Array       # int32 — global steps taken since init
    coeffs: Any = None  # matfun.CoeffHistory, or None (fn='inv')

    # Convenience views (the banked bracket a consumer can act on any
    # time; `it`/`done` for budget accounting).
    def bracket(self) -> tuple[Array, Array]:
        """(lower, upper) in ONE pass — on matfun and block states the
        two sides share a single Jacobi-matrix eigensolve, so prefer
        this over reading ``.lower`` and ``.upper`` separately (each
        property alone re-runs it)."""
        if isinstance(self.st, _block.BlockState):
            lo, hi, _, _ = _block.bracket(self.st, self.lam_min,
                                          self.lam_max)
            return lo, hi
        if self.coeffs is None:
            return _gql.lower_bound(self.st), _gql.upper_bound(self.st)
        lo, hi, _, _ = _matfun.bracket(self.coeffs, self.st, self.lam_min,
                                       self.lam_max)
        return lo, hi

    @property
    def lower(self) -> Array:
        return self.bracket()[0]

    @property
    def upper(self) -> Array:
        return self.bracket()[1]

    @property
    def it(self) -> Array:
        return self.st.it

    @property
    def done(self) -> Array:
        return self.st.done


# The QuadState threading contract (DESIGN.md Sec. 10, enforced by
# quadlint QL001): every field lives in exactly ONE bucket, and the
# handler layers are checked against the buckets —
#   per-lane : advanced by the loop and frozen per lane as lanes resolve
#              (step_n/resume tree_freeze carries), sharded with the
#              lanes by core/sharded.py, banked/scattered per lane by
#              serve/engine.py's pool;
#   carried  : whole-state bookkeeping threaded through every drive's
#              _replace (no per-lane freeze semantics);
#   prepared : resolved once by init_state and read-only afterwards.
# A new QuadState field (block-Krylov buffers, rank-update caches, ...)
# that is not added to a bucket AND to every non-excluded handler is a
# CI failure, not a review catch.
QUADSTATE_PER_LANE = ("st", "basis", "coeffs")
QUADSTATE_CARRIED = ("step",)
QUADSTATE_PREPARED = ("op", "lam_min", "lam_max")


def _argmax_scores(lo: Array, hi: Array, shift, scale, valid,
                   prior_upper=None, prior_lower=None):
    """Per-lane score brackets ``shift + scale * [lo, hi]`` for the argmax
    race, with invalid lanes pinned at a large negative sentinel. Shared
    by ``judge_argmax`` and the sharded driver (core/sharded.py) so the
    two paths race on bit-identical values.

    ``prior_upper`` (optional, per-lane) is an externally-known valid
    upper bound on the score — e.g. a previous greedy round's bracket,
    valid by Schur-complement monotonicity (DESIGN.md Sec. 8.3). The
    effective upper bound is clamped to it (never below the lane's own
    lower bound, so a slightly-stale prior can only stop helping, never
    corrupt the race).

    ``prior_lower`` (optional, per-lane) is the dual: an externally-known
    valid lower bound on the score — e.g. the exact Schur complement read
    off a maintained factor (core/update.py, DESIGN.md Sec. 12). Clamped
    to never exceed the effective upper bound. With both priors exact the
    race resolves at its very first decide check."""
    big_neg = jnp.asarray(-1e30, lo.dtype)
    a = shift + scale * lo
    b = shift + scale * hi
    slo, shi = jnp.minimum(a, b), jnp.maximum(a, b)
    if prior_upper is not None:
        shi = jnp.maximum(jnp.minimum(shi, prior_upper), slo)
    if prior_lower is not None:
        slo = jnp.minimum(jnp.maximum(slo, prior_lower), shi)
    if valid is not None:
        slo = jnp.where(valid, slo, big_neg)
        shi = jnp.where(valid, shi, big_neg)
    return slo, shi


def _argmax_race(slo: Array, shi: Array):
    """(dominated, winner) per lane of the certified argmax race."""
    k = shi.shape[-1]
    if k == 1:
        return jnp.zeros_like(shi, bool), jnp.ones_like(shi, bool)
    best_lo = jnp.max(slo, axis=-1, keepdims=True)
    dominated = shi < best_lo
    order = jnp.sort(shi, axis=-1)
    top1, top2 = order[..., -1:], order[..., -2:-1]
    leader = jnp.argmax(shi, axis=-1, keepdims=True)
    rival_hi = jnp.where(jnp.arange(k) == leader, top2, top1)
    winner = slo >= rival_hi
    return dominated, winner


# Log-gain brackets for the greedy / double-greedy judges live in the
# matfun registry (one home for bound orientation); kept under the old
# private name for the judges below.
_log_gain_bounds = _matfun.log_gain_bounds


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class BIFSolver:
    """One retrospective quadrature driver; see module docstring."""
    config: SolverConfig = SolverConfig()

    # -- construction sugar -------------------------------------------------

    @classmethod
    def create(cls, **config_kwargs) -> "BIFSolver":
        return cls(SolverConfig(**config_kwargs))

    def replace(self, **config_kwargs) -> "BIFSolver":
        return BIFSolver(dataclasses.replace(self.config, **config_kwargs))

    # -- backend / problem preparation --------------------------------------

    def _stepper(self):
        """One-iteration GQL step implementation per ``config.backend``:
        ``stepfn(op, st, lam_min, lam_max, basis)``. 'fused' routes the
        matvec and alpha through the ``kernels/lanczos_step.py`` kernel
        and the rest through the reference tail; 'reference'/'pallas'
        compose ``gql.gql_step`` with the configured recurrence.

        With ``block_size > 1`` every backend steps the block recurrence
        (``block.block_step``): the per-iteration work is already
        gemm-shaped through ``operators.matvec_mrhs`` (the BELL pallas
        path uses the multi-RHS kernel), so there is no separate fused
        step kernel — the backend knob still picks the operator's matvec
        execution mode via ``configure_backend``."""
        if self.config.block_size > 1:
            def block_step(op, st, lam_min, lam_max, basis=None):
                return _block.block_step(op, st, lam_min, lam_max)

            return block_step
        if self.config.backend == "fused":
            from ..kernels import ops as _kops  # deferred: pulls in pallas
            interpret = self.config.pallas_interpret

            def fused_step(op, st, lam_min, lam_max, basis=None):
                return _kops.gql_step_fused(op, st, lam_min, lam_max,
                                            basis=basis, interpret=interpret)

            return fused_step
        rec = self._recurrence()

        def composed_step(op, st, lam_min, lam_max, basis=None):
            return _gql.gql_step(op, st, lam_min, lam_max, basis=basis,
                                 recurrence=rec)

        return composed_step

    def _recurrence(self):
        """Scalar-recurrence implementation per ``config.backend``."""
        if self.config.backend != "pallas":
            return None  # gql_step default: gql.recurrence_update
        from ..kernels import ops as _kops  # deferred: pulls in pallas
        interpret = self.config.pallas_interpret

        def pallas_recurrence(alpha_n, beta_n, beta_p, g, c, delta,
                              d_lr, d_rr, lam_min, lam_max):
            shape = g.shape

            def flat(x):
                return jnp.broadcast_to(jnp.asarray(x, g.dtype),
                                        shape).reshape((-1,))

            outs = _kops.gql_update(
                flat(alpha_n), flat(beta_n), flat(beta_p), flat(g), flat(c),
                flat(delta), flat(d_lr), flat(d_rr), flat(lam_min),
                flat(lam_max), interpret=interpret)
            return tuple(o.reshape(shape) for o in outs)

        return pallas_recurrence

    def prepare(self, op, u: Array, lam_min=None, lam_max=None, probe=None):
        """Apply preconditioning and resolve the spectral interval.

        Returns ``(op, u, lam_min, lam_max)`` ready for ``gql_init``.
        Explicitly passed ``lam_min``/``lam_max`` always win; missing ends
        are filled per ``config.spectrum``.

        With ``precondition='jacobi'`` the quadrature runs on the
        *transformed* operator ``D^-1/2 A D^-1/2``, so an explicitly
        passed interval must bound THAT spectrum (not A's — the two
        intervals differ in general). Prefer leaving the interval to an
        estimating spectrum mode, which runs on the transformed operator
        automatically.
        """
        cfg = self.config
        op = _ops.configure_backend(op, cfg.backend, cfg.pallas_interpret)
        if cfg.precondition == "jacobi":
            pop = _ops.Jacobi.create(op)
            u = pop.transform_vector(u)
            op = pop
        if lam_min is not None and lam_max is not None:
            return op, u, lam_min, lam_max

        if cfg.spectrum == "explicit":
            raise ValueError(
                "spectrum='explicit' requires lam_min and lam_max; pass "
                "them to solve()/judge_*() or pick an estimating spectrum "
                "mode ('gershgorin' | 'lanczos' | 'ridge')")
        if cfg.spectrum == "gershgorin":
            est = _spectrum.gershgorin_bounds_spd(op)
        else:
            if probe is None:
                probe = jnp.where(jnp.abs(u) > 0, u, jnp.ones_like(u))
            if cfg.spectrum == "ridge":
                est = _spectrum.ridge_bounds(op, cfg.ridge, probe,
                                             num_iters=cfg.spectrum_iters)
            else:  # 'lanczos'
                est = _spectrum.lanczos_extremal(
                    op, probe, num_iters=cfg.spectrum_iters)
        lam_min = est.lam_min if lam_min is None else lam_min
        lam_max = est.lam_max if lam_max is None else lam_max
        return op, u, lam_min, lam_max

    # -- the resumable runtime (DESIGN.md Sec. 8) -----------------------------
    #
    # init_state / step_n / resume / finalize are the single source of
    # truth for the retrospective loop: solve, solve_batch, trace, the
    # judges, the sharded driver (core/sharded.py), and the serving
    # engine (serve/engine.py) are all built on them. The state machine
    # is explicit so a consumer can pause a solve at any iteration, bank
    # its bracket, checkpoint/ship the QuadState, and resume later —
    # bit-exact with an uninterrupted run.

    def _bracket2(self, st, coeffs, lam_min, lam_max):
        """The (lower, upper) bracket the stopping rules act on:
        the legacy GQL Radau views for fn='inv' (coeffs is None,
        bit-exact with the pre-matfun solver), the block-quadrature
        trace bracket on block states (DESIGN.md Sec. 13), else the
        sign-aware matfun bracket (DESIGN.md Sec. 9)."""
        if isinstance(st, _block.BlockState):
            lo, hi, _, _ = _block.bracket(st, lam_min, lam_max)
            return lo, hi
        if coeffs is None:
            return _gql.lower_bound(st), _gql.upper_bound(st)
        lo, hi, _, _ = _matfun.bracket(coeffs, st, lam_min, lam_max)
        return lo, hi

    def _bracket4(self, st, coeffs, lam_min, lam_max):
        """(lower, upper, loose_lower, loose_upper): the tight Radau
        bracket plus the loose Gauss/Lobatto pair, oriented per fn."""
        if isinstance(st, _block.BlockState):
            return _block.bracket(st, lam_min, lam_max)
        if coeffs is None:
            return (_gql.lower_bound(st), _gql.upper_bound(st),
                    _gql.lower_bound_gauss(st), _gql.upper_bound_lobatto(st))
        return _matfun.bracket(coeffs, st, lam_min, lam_max)

    def _needs_more_fn(self, decide, it_cap=None, *, lam_min=None,
                       lam_max=None):
        """(needs_more(st, coeffs), resolved(st, coeffs)) for the loop:
        a lane keeps stepping while it is not done (breakdown), not
        resolved by ``decide`` (None = the tolerance rule), and below
        both the config's ``max_iters`` and the optional per-lane
        ``it_cap`` (the serving engine's per-request iteration budget).
        ``lam_min``/``lam_max`` feed the matfun bracket (unused on the
        fn='inv' path, where coeffs is None)."""
        local_ok = self._local_ok_fn(it_cap)

        if decide is None:
            def resolved(st, coeffs):
                return self.tolerance_resolved(
                    *self._bracket2(st, coeffs, lam_min, lam_max))
        else:
            def resolved(st, coeffs):
                return decide(*self._bracket2(st, coeffs, lam_min, lam_max))

        def needs_more(st, coeffs):
            return local_ok(st, coeffs) & ~resolved(st, coeffs)

        return needs_more, resolved

    def _local_ok_fn(self, it_cap=None):
        """The *decide-free* per-lane continuation conditions: not broken
        down, below ``max_iters``, within the coefficient history, and
        below the optional per-lane ``it_cap``. These freeze a lane
        immediately even inside a ``decide_every`` round (unlike the
        stopping rule, which is only consulted at round boundaries —
        deferring a decide costs at most R-1 extra contractions by
        Thm. 4.2, but overrunning a budget or the history buffer would
        be a correctness bug, not a latency trade)."""
        max_iters = self.config.max_iters

        def local_ok(st, coeffs):
            ok = ~st.done & (st.it < max_iters)
            if coeffs is not None:
                # never advance a lane past its recorded alpha/beta
                # history: an undersized ``coeff_rows`` buffer freezes
                # like an iteration budget (bracket stops tightening but
                # stays sound) instead of silently corrupting estimates
                ok = ok & (st.it < coeffs.alphas.shape[-1])
            elif isinstance(st, _block.BlockState):
                # same rule for the block A/B history buffer
                ok = ok & (st.it < st.a_hist.shape[-3])
            if it_cap is not None:
                ok = ok & (st.it < it_cap)
            return ok

        return local_ok

    def _advance(self, op, st, lam_min, lam_max, basis, coeffs, step,
                 stepfn):
        """One unconditional GQL step + reorth-basis / coefficient-
        history bookkeeping (no freezing — the caller applies its own
        rule). ``stepfn`` comes from :meth:`_stepper` (reference /
        pallas-recurrence / fused-step backends)."""
        st1 = stepfn(op, st, lam_min, lam_max, basis)
        if coeffs is not None:
            coeffs = _matfun.update_coeffs(coeffs, st, st1)
        if basis is None:
            return st1, None, coeffs
        basis1 = jax.lax.dynamic_update_index_in_dim(
            basis, st1.lz.v, step + 2, axis=-2)
        return st1, basis1, coeffs

    def init_state(self, op, u: Array, *, lam_min=None, lam_max=None,
                   probe=None, basis_rows: int | None = None,
                   coeff_rows: int | None = None) -> QuadState:
        """Prepare the problem and take iteration 1 (Alg. 5 init).

        The returned :class:`QuadState` is self-contained: it carries the
        prepared (backend-configured, preconditioned) operator and the
        resolved spectral interval, so ``step_n``/``resume`` need nothing
        else. ``basis_rows`` sizes the reorthogonalization storage when
        ``config.reorth`` (default ``max_iters + 1``); ``coeff_rows``
        the alpha/beta history when ``config.fn != 'inv'`` (default
        ``max_iters``).

        With ``config.block_size = b > 1`` the query is a row-stacked
        probe BLOCK ``u`` of shape (..., b, N) and the state brackets
        ``tr B^T f(A) B`` per lane via the block recurrence
        (``coeff_rows`` then sizes the block A/B history, in block
        iterations). b = 1 takes the scalar path below unchanged.
        """
        cfg = self.config
        if cfg.block_size > 1:
            u = jnp.asarray(u)
            if u.ndim < 2 or u.shape[-2] != cfg.block_size:
                raise ValueError(
                    f"block_size={cfg.block_size} wants (..., b, N) "
                    f"row-stacked probe blocks with b={cfg.block_size}, "
                    f"got shape {u.shape}")
            op, u, lam_min, lam_max = self.prepare(op, u, lam_min, lam_max,
                                                   probe)
            # estimating spectrum modes return per-probe bounds: take the
            # union interval over the lane's block slots
            lam_min = jnp.asarray(lam_min)
            lam_max = jnp.asarray(lam_max)
            if lam_min.ndim > u.ndim - 2:
                lam_min = jnp.min(lam_min, axis=-1)
            if lam_max.ndim > u.ndim - 2:
                lam_max = jnp.max(lam_max, axis=-1)
            st0 = _block.block_init(
                op, u, lam_min, lam_max, cfg.fn,
                cfg.max_iters if coeff_rows is None else coeff_rows)
            return QuadState(op=op, st=st0, lam_min=lam_min,
                             lam_max=lam_max, basis=None,
                             step=jnp.zeros((), jnp.int32), coeffs=None)
        op, u, lam_min, lam_max = self.prepare(op, u, lam_min, lam_max,
                                               probe)
        st0 = _gql.gql_init(op, u, lam_min, lam_max)
        if cfg.reorth:
            rows = cfg.max_iters + 1 if basis_rows is None else basis_rows
            basis = self._alloc_basis(st0, u, rows)
        else:
            basis = None
        if cfg.fn != "inv":
            coeffs = _matfun.init_coeffs(
                st0, cfg.fn,
                cfg.max_iters if coeff_rows is None else coeff_rows)
        else:
            coeffs = None
        return QuadState(op=op, st=st0, lam_min=jnp.asarray(lam_min),
                         lam_max=jnp.asarray(lam_max), basis=basis,
                         step=jnp.zeros((), jnp.int32), coeffs=coeffs)

    def _round_body(self, op, lam_min, lam_max, stepfn, local_ok):
        """One ``decide_every`` round: R substeps with *local-only*
        freezing (breakdown / max_iters / history / it_cap apply
        immediately; the stopping rule is deferred to the boundary).
        Returns ``round_fn((st, basis, coeffs, step, nm)) -> same`` with
        ``nm`` the entry round-boundary needs_more; the caller evaluates
        the next boundary's needs_more on the result. With R=1 this is
        exactly the historical one-step body (the single substep's
        freeze mask IS the boundary needs_more)."""
        r = self.config.decide_every

        def substep(i, carry):
            st, basis, coeffs, step, nm = carry
            st1, basis1, coeffs1 = self._advance(op, st, lam_min, lam_max,
                                                 basis, coeffs, step, stepfn)
            frozen = ~nm
            st1 = tree_freeze(st1, st, frozen)
            if basis is not None:
                basis1 = tree_freeze(basis1, basis, frozen)
            if coeffs is not None:
                coeffs1 = tree_freeze(coeffs1, coeffs, frozen)
            nm1 = nm & local_ok(st1, coeffs1)
            return st1, basis1, coeffs1, step + 1, nm1

        if r == 1:
            return lambda carry: substep(0, carry)
        return lambda carry: jax.lax.fori_loop(0, r, substep, carry)

    def step_n(self, state: QuadState, n: int, decide=None, *,
               it_cap=None, convergence_log=None) -> QuadState:
        """Advance ``state`` by at most ``n`` quadrature iterations.

        Lanes that already resolved ``decide`` (None = the tolerance
        rule), broke down, or hit ``max_iters`` / the optional per-lane
        ``it_cap`` budget are frozen bit-exactly — the same rule
        ``resume`` applies, so ``resume(step_n(state, k))`` reproduces
        ``resume(state)`` exactly. ``n`` is a static bound on this call's
        steps; the loop exits early once every lane is frozen.

        With ``decide_every = R > 1`` the stopping rule is evaluated
        every R steps and states stay *round-aligned*: ``step_n``
        advances at most ``floor(n / R) * R`` steps (``n < R`` is a
        no-op), keeping the resume invariant exact at every cadence.

        ``convergence_log`` (an :class:`repro.obs.health.ConvergenceLog`)
        records the returned state's bracket + iteration counts — a
        HOST-side read of the already-computed result, so the compiled
        loop above is untouched and logging is bit-invariant. Only legal
        outside a trace (under jit the views are tracers); the engine
        and other jitted callers simply never pass it.
        """
        if n < 0:
            raise ValueError(f"n must be >= 0, got {n}")
        r = self.config.decide_every
        rounds = n // r
        if rounds == 0:
            return state
        stepfn = self._stepper()
        op, lam_min, lam_max = state.op, state.lam_min, state.lam_max
        needs_more, _ = self._needs_more_fn(decide, it_cap,
                                            lam_min=lam_min, lam_max=lam_max)
        round_fn = self._round_body(op, lam_min, lam_max, stepfn,
                                    self._local_ok_fn(it_cap))

        # needs_more is carried through the loop (computed once per
        # round, like the sharded driver): for matfun states it is the
        # stacked Jacobi eigensolve — evaluating it in both cond and
        # body would double the dominant per-round cost
        def cond(carry):
            (_, _, _, _, nm), taken = carry
            return jnp.any(nm) & (taken < rounds)

        def body(carry):
            inner, taken = carry
            st, basis, coeffs, step, _ = round_fn(inner)
            nm = needs_more(st, coeffs)
            return (st, basis, coeffs, step, nm), taken + 1

        (st, basis, coeffs, step, _), _ = jax.lax.while_loop(
            cond, body,
            ((state.st, state.basis, state.coeffs, state.step,
              needs_more(state.st, state.coeffs)),
             jnp.zeros((), jnp.int32)))
        out = state._replace(st=st, basis=basis, coeffs=coeffs, step=step)
        if convergence_log is not None:
            convergence_log.record_state(out)
        return out

    def resume(self, state: QuadState, decide=None, *,
               it_cap=None) -> QuadState:
        """Run the retrospective loop (Alg. 2) from ``state`` until
        ``decide`` resolves on every lane (or breakdown / ``max_iters`` /
        the per-lane ``it_cap`` budget), freezing resolved lanes
        bit-exactly. Starting from a fresh ``init_state`` this IS the
        uninterrupted drive; starting from a ``step_n`` checkpoint it
        continues it bit-exactly (``step_n`` keeps states round-aligned,
        so the cadence-R decision schedule lines up too)."""
        stepfn = self._stepper()
        op, lam_min, lam_max = state.op, state.lam_min, state.lam_max
        needs_more, _ = self._needs_more_fn(decide, it_cap,
                                            lam_min=lam_min, lam_max=lam_max)
        round_fn = self._round_body(op, lam_min, lam_max, stepfn,
                                    self._local_ok_fn(it_cap))

        # nm carried through the loop — one bracket evaluation per round
        # (see step_n)
        def cond(carry):
            return jnp.any(carry[4])

        def body(carry):
            # trace-time probe: counts how often the loop is traced (an
            # eager call traces it afresh every time)
            _obs_registry.count("core.solver.resume")
            st, basis, coeffs, step, _ = round_fn(carry)
            return st, basis, coeffs, step, needs_more(st, coeffs)

        st, basis, coeffs, step, _ = jax.lax.while_loop(
            cond, body, (state.st, state.basis, state.coeffs, state.step,
                         needs_more(state.st, state.coeffs)))
        return state._replace(st=st, basis=basis, coeffs=coeffs, step=step)

    def resume_chunked(self, state: QuadState, decide=None, *,
                       chunk_iters: int, it_cap=None) -> QuadState:
        """``resume`` as repeated ``step_n(chunk_iters)`` decision rounds:
        each round continues from the banked state of the still-unresolved
        lanes instead of re-solving. Bit-exact with ``resume`` (same step
        computation, same freezing) — this is the jit-side skeleton of
        the serving engine's scheduler and the chunked chain judges.

        Matfun cost note: the chunk-boundary check here re-evaluates the
        bracket that ``step_n`` also evaluates for its own carry — one
        extra eigensolve per round (chunk_iters+2 instead of
        chunk_iters+1). Accepted: deduplicating would mean threading
        precomputed freeze flags through ``step_n``'s public signature."""
        if chunk_iters < 1:
            raise ValueError(f"chunk_iters must be >= 1, got {chunk_iters}")
        # align the round size up to the decision cadence: a chunk below
        # ``decide_every`` would make step_n a round-aligned no-op (and
        # this loop livelock); rounding up preserves "at most chunk_iters
        # per round" spirit at the configured cadence granularity
        r = self.config.decide_every
        chunk_iters = -(-chunk_iters // r) * r
        needs_more, _ = self._needs_more_fn(decide, it_cap,
                                            lam_min=state.lam_min,
                                            lam_max=state.lam_max)

        def cond(s):
            return jnp.any(needs_more(s.st, s.coeffs))

        def body(s):
            return self.step_n(s, chunk_iters, decide, it_cap=it_cap)

        return jax.lax.while_loop(cond, body, state)

    def finalize(self, state: QuadState, decide=None) -> SolveResult:
        """Read a :class:`SolveResult` off a (partial or completed) state.

        ``certified`` re-evaluates ``decide`` (None = tolerance rule) on
        the banked bracket, so finalizing a budget-interrupted state
        reports honestly whether the decision already resolved.

        For matfun states (``config.fn != 'inv'``) the result fields
        are oriented per the registry's sign table: ``lower``/``upper``
        hold the tight Radau bracket and ``gauss_lower``/
        ``lobatto_upper`` the loose Gauss/Lobatto pair as lower/upper
        respectively (for log-like f the underlying rules swap sides —
        DESIGN.md Sec. 9)."""
        _, resolved = self._needs_more_fn(decide, lam_min=state.lam_min,
                                          lam_max=state.lam_max)
        st = state.st
        certified = resolved(st, state.coeffs)
        lower, upper, loose_lo, loose_hi = self._bracket4(
            st, state.coeffs, state.lam_min, state.lam_max)
        return SolveResult(
            lower=lower, upper=upper,
            gauss_lower=loose_lo, lobatto_upper=loose_hi,
            iterations=st.it, converged=st.done | certified,
            certified=certified, state=state)

    def _alloc_basis(self, st0, u: Array, num_rows: int):
        """Reorthogonalization storage: rows 0..num_rows-1 hold v_0..v_M."""
        basis = jnp.zeros(u.shape[:-1] + (num_rows, u.shape[-1]), u.dtype)
        basis = jax.lax.dynamic_update_index_in_dim(
            basis, st0.lz.v_prev, 0, axis=-2)  # v_0
        return jax.lax.dynamic_update_index_in_dim(
            basis, st0.lz.v, 1, axis=-2)       # v_1

    def tolerance_resolved(self, lower: Array, upper: Array) -> Array:
        """The ``decide=None`` stopping rule: bracket gap within the
        configured ``atol``/``rtol`` of the lower bound. The single
        definition shared by ``solve`` and ``serve.BIFEngine`` so the
        serving path can't drift from the solver's rule."""
        return (upper - lower) <= jnp.maximum(
            self.config.atol, self.config.rtol * jnp.abs(lower))

    @staticmethod
    def threshold_decision(t: Array, lower: Array, upper: Array) -> Array:
        """Alg. 4 decision from a bracket: certified when ``t`` clears
        [lower, upper); bracket-midpoint tie-break when it doesn't."""
        return jnp.where(t < lower, True,
                         jnp.where(t >= upper, False,
                                   t < 0.5 * (lower + upper)))

    def solve(self, op, u: Array,
              decide: Callable[[Array, Array], Array] | None = None, *,
              lam_min=None, lam_max=None, probe=None) -> SolveResult:
        """Retrospective solve for ``u^T A^-1 u``: iterate quadrature until
        ``decide(lower, upper)`` is True on every lane (or exhaustion).

        ``decide`` gets the current scaled bracket and must return a bool
        array (True = this lane's decision is resolved).  With
        ``decide=None`` the driver brackets to the configured
        ``rtol``/``atol`` tolerance (legacy ``bif_bounds`` behavior).

        Sugar for ``finalize(resume(init_state(...), decide), decide)``;
        callers that need to pause/checkpoint/resume use the runtime
        methods directly (``SolveResult.state`` is the final QuadState).
        """
        state = self.init_state(op, u, lam_min=lam_min, lam_max=lam_max,
                                probe=probe)
        state = self.resume(state, decide)
        return self.finalize(state, decide)

    def trace(self, op, u: Array, num_iters: int, *, lam_min=None,
              lam_max=None, probe=None,
              convergence_log=None) -> QuadratureTrace:
        """Run exactly ``num_iters`` iterations, recording all four estimate
        sequences (paper Fig. 1).  Honors spectrum/precondition/backend and
        ``reorth`` from the config.

        With ``config.fn != 'inv'`` the fields are oriented per the
        matfun sign table: ``radau_lower``/``radau_upper`` are the tight
        oriented Radau bracket and ``gauss``/``lobatto`` the loose
        lower/upper (for log-like f those are the Lobatto/Gauss rules
        respectively — DESIGN.md Sec. 9).

        ``convergence_log`` (an :class:`repro.obs.health.ConvergenceLog`)
        records the returned Radau bracket per iteration — read off the
        finished trace HOST-side, bit-identical to the returned fields.
        Only legal outside a trace (see ``step_n``)."""
        if num_iters < 1:
            raise ValueError(f"num_iters must be >= 1, got {num_iters}")
        # Rows 0..num_iters of the reorth basis hold v_0..v_{num_iters}.
        state = self.init_state(op, u, lam_min=lam_min, lam_max=lam_max,
                                probe=probe, basis_rows=num_iters + 1,
                                coeff_rows=num_iters)
        stepfn = self._stepper()

        def estimates(st, coeffs):
            lo, hi, loose_lo, loose_hi = self._bracket4(
                st, coeffs, state.lam_min, state.lam_max)
            return (loose_lo, lo, hi, loose_hi)

        first = estimates(state.st, state.coeffs)
        if num_iters == 1:
            # No scan: a zero-length jnp.arange trips older jax versions and
            # buys nothing.
            tr = QuadratureTrace(*(f[None] for f in first))
            if convergence_log is not None:
                convergence_log.record_trace(tr)
            return tr

        def body(carry, _):
            st, basis, coeffs, step = carry
            st1, basis1, coeffs1 = self._advance(state.op, st, state.lam_min,
                                                 state.lam_max, basis,
                                                 coeffs, step, stepfn)
            return (st1, basis1, coeffs1, step + 1), estimates(st1, coeffs1)

        _, rest = jax.lax.scan(body, (state.st, state.basis, state.coeffs,
                                      state.step),
                               None, length=num_iters - 1)
        seqs = [jnp.concatenate([f[None], r], axis=0)
                for f, r in zip(first, rest)]
        tr = QuadratureTrace(*seqs)
        if convergence_log is not None:
            convergence_log.record_trace(tr)
        return tr

    # -- single-system judges -----------------------------------------------

    def judge_threshold(self, op, u: Array, t: Array, *, lam_min=None,
                        lam_max=None, probe=None) -> JudgeResult:
        """Alg. 4 (DPPJUDGE): True iff  t < u^T A^-1 u."""
        res = self.solve(op, u, decide=lambda lo, hi: (t < lo) | (t >= hi),
                         lam_min=lam_min, lam_max=lam_max, probe=probe)
        decision = self.threshold_decision(t, res.lower, res.upper)
        return JudgeResult(decision=decision, certified=res.certified,
                           iterations=res.iterations)

    # -- the batched driver (K candidate systems, one loop) ------------------

    def solve_batch(self, op, u: Array,
                    decide: Callable[[Array, Array], Array] | None = None, *,
                    lam_min=None, lam_max=None, probe=None) -> SolveResult:
        """Retrospective solve over K candidate systems as lockstep lanes
        of ONE driver (DESIGN.md Sec. 6).

        ``u`` is (..., K, N): one query vector per lane. ``op`` is either
        a single operator shared by every lane, a lane-batched operator
        from ``operators.stack_ops``, or a stacked-mask operator from
        ``operators.stack_masks`` (K principal submatrices of one base).
        The matvec runs once over the whole stack per iteration; lanes
        whose decision resolves are frozen bit-exactly
        (``loop_utils.tree_freeze``) while the rest continue.

        ``decide(lower, upper)`` receives the full (..., K) brackets and
        returns per-lane resolution flags — it may reduce *across* lanes
        (the argmax race in ``judge_argmax`` does). ``decide=None``
        brackets every lane to the configured rtol/atol. Per-lane results
        are identical to running ``solve`` on each lane alone.
        """
        u = jnp.asarray(u)
        min_ndim = 3 if self.config.block_size > 1 else 2
        if u.ndim < min_ndim:
            if self.config.block_size > 1:
                raise ValueError(
                    f"solve_batch with block_size={self.config.block_size} "
                    f"wants (..., K, b, N) stacked probe blocks, got shape "
                    f"{u.shape}; use solve() for a single block")
            raise ValueError(
                f"solve_batch wants (..., K, N) stacked queries, got shape "
                f"{u.shape}; use solve() for a single system")
        return self.solve(op, u, decide, lam_min=lam_min, lam_max=lam_max,
                          probe=probe)

    def judge_batch(self, op, u: Array, t: Array, *, lam_min=None,
                    lam_max=None, probe=None) -> JudgeResult:
        """K threshold judges (Alg. 4) in one batched driver:
        ``decision[k] = t[k] < u_k^T A_k^-1 u_k`` with per-lane early exit.
        ``t`` broadcasts against the (..., K) lane shape."""
        u = jnp.asarray(u)
        if u.ndim < 2:
            raise ValueError(
                f"judge_batch wants (..., K, N) stacked queries, got shape "
                f"{u.shape}; use judge_threshold() for a single system")
        return self.judge_threshold(op, u, jnp.asarray(t), lam_min=lam_min,
                                    lam_max=lam_max, probe=probe)

    def judge_argmax(self, op, u: Array, *, shift=None, scale=None,
                     valid=None, prior_upper=None, prior_lower=None,
                     lam_min=None, lam_max=None, probe=None) -> ArgmaxResult:
        """Certified argmax over K candidate scores
        ``shift_k + scale_k * u_k^T A_k^-1 u_k`` (greedy MAP's inner loop).

        Lanes race: a lane freezes as soon as it is *dominated* (its score
        upper bound is below the best lower bound — it cannot win) and the
        loop ends once the surviving lane's lower bound clears every
        rival's upper bound (or exhaustion; then the bracket midpoints
        pick, with ``certified=False``). ``valid`` (bool, (..., K))
        excludes lanes from the race (e.g. already-selected candidates).

        ``prior_upper`` (per-lane) banks externally-known valid upper
        bounds on the scores — e.g. a previous greedy round's brackets,
        still valid by Schur-complement monotonicity — so lanes a stale
        bound already rules out freeze after their very first bracket
        (lazy greedy, DESIGN.md Sec. 8.3). The certificate stays exact.

        ``prior_lower`` (per-lane) banks externally-known valid LOWER
        bounds — e.g. exact scores read off a maintained selection
        factor (core/update.py): with exact priors on both sides every
        lane resolves at its first decide check, so the whole race costs
        one iteration per lane (DESIGN.md Sec. 12).
        """
        u = jnp.asarray(u)
        if u.ndim < 2:
            raise ValueError(f"judge_argmax wants (..., K, N) stacked "
                             f"queries, got shape {u.shape}")
        shift = jnp.zeros((), u.dtype) if shift is None else \
            jnp.asarray(shift, u.dtype)
        scale = jnp.ones((), u.dtype) if scale is None else \
            jnp.asarray(scale, u.dtype)

        def scores(lo, hi):
            return _argmax_scores(lo, hi, shift, scale, valid, prior_upper,
                                  prior_lower)

        def resolved(lo, hi):
            dominated, winner = _argmax_race(*scores(lo, hi))
            return dominated | winner

        res = self.solve_batch(op, u, decide=resolved, lam_min=lam_min,
                               lam_max=lam_max, probe=probe)
        slo, shi = scores(res.lower, res.upper)
        _, winner = _argmax_race(slo, shi)
        certified = jnp.any(winner, axis=-1)
        mid = 0.5 * (slo + shi)
        index = jnp.where(certified, jnp.argmax(winner, axis=-1),
                          jnp.argmax(mid, axis=-1)).astype(jnp.int32)
        return ArgmaxResult(index=index, certified=certified,
                            iterations=res.iterations, lower=slo, upper=shi)

    # -- device-sharded batched driver (lanes over a mesh axis) --------------

    def solve_batch_sharded(self, op, u: Array, decide=None, *, mesh,
                            axis: str = "lanes", lam_min=None, lam_max=None,
                            probe=None, decide_args=()) -> SolveResult:
        """``solve_batch`` with the K lanes data-parallel over ``mesh``'s
        ``axis`` via ``shard_map`` (core/sharded.py, DESIGN.md Sec. 7).
        Per-lane results match the single-device batched path exactly."""
        from . import sharded as _sharded
        return _sharded.solve_batch_sharded(
            self, op, u, decide, mesh=mesh, axis=axis, lam_min=lam_min,
            lam_max=lam_max, probe=probe, decide_args=decide_args)

    def judge_batch_sharded(self, op, u: Array, t: Array, *, mesh,
                            axis: str = "lanes", lam_min=None, lam_max=None,
                            probe=None) -> JudgeResult:
        """``judge_batch`` over a lane mesh (DESIGN.md Sec. 7)."""
        from . import sharded as _sharded
        return _sharded.judge_batch_sharded(
            self, op, u, t, mesh=mesh, axis=axis, lam_min=lam_min,
            lam_max=lam_max, probe=probe)

    def judge_argmax_sharded(self, op, u: Array, *, mesh,
                             axis: str = "lanes", shift=None, scale=None,
                             valid=None, prior_upper=None, prior_lower=None,
                             lam_min=None, lam_max=None,
                             probe=None) -> ArgmaxResult:
        """``judge_argmax`` over a lane mesh: the race's cross-lane
        reductions become cross-device collectives (DESIGN.md Sec. 7)."""
        from . import sharded as _sharded
        return _sharded.judge_argmax_sharded(
            self, op, u, mesh=mesh, axis=axis, shift=shift, scale=scale,
            valid=valid, prior_upper=prior_upper, prior_lower=prior_lower,
            lam_min=lam_min, lam_max=lam_max, probe=probe)

    def judge_kdpp_swap_batch(self, op, u: Array, v: Array, t: Array,
                              p: Array, *, lam_min=None, lam_max=None,
                              chunk_iters: int | None = None) -> JudgeResult:
        """Alg. 7 with both systems as two lanes of the batched driver.

        The gap-weighted pair driver (``judge_kdpp_swap``) computes both
        matvecs every loop step and discards one; here the (..., 2, N)
        stack advances both sides per step in a single matvec, so the
        decision resolves in no more loop steps for the same per-step
        cost. Decisions remain certified-exact; per-side iteration counts
        differ from the pair driver's refinement schedule.

        ``chunk_iters`` runs the judge through the resumable runtime in
        fixed-size decision rounds (``resume_chunked``): each round
        carries the unresolved systems' banked :class:`QuadState` forward
        instead of re-solving — bit-exact with the monolithic drive.
        """
        if self.config.block_size > 1:
            raise NotImplementedError(
                "judge_kdpp_swap_batch stacks two scalar query systems; "
                "block_size > 1 brackets tr B^T f(A) B and has no swap-"
                "judge semantics — use block_size=1")
        uv = jnp.stack([jnp.asarray(u), jnp.asarray(v)], axis=-2)

        def bounds(lo, hi):
            return (p * lo[..., 1] - hi[..., 0],
                    p * hi[..., 1] - lo[..., 0])

        def resolved(lo, hi):
            blo, bhi = bounds(lo, hi)
            done = (t < blo) | (t >= bhi)
            return jnp.broadcast_to(done[..., None], lo.shape)

        if chunk_iters is None:
            res = self.solve_batch(op, uv, decide=resolved, lam_min=lam_min,
                                   lam_max=lam_max)
        else:
            state = self.init_state(op, uv, lam_min=lam_min,
                                    lam_max=lam_max)
            state = self.resume_chunked(state, resolved,
                                        chunk_iters=chunk_iters)
            res = self.finalize(state, resolved)
        blo, bhi = bounds(res.lower, res.upper)
        decision = self.threshold_decision(t, blo, bhi)
        return JudgeResult(decision=decision,
                           certified=(t < blo) | (t >= bhi),
                           iterations=jnp.sum(res.iterations, axis=-1,
                                              dtype=res.iterations.dtype))

    def judge_double_greedy_batch(self, op2, uv: Array, t: Array, p: Array,
                                  *, lam_min=None,
                                  lam_max=None) -> JudgeResult:
        """Alg. 9 with the X- and Y-side systems as two lanes of the
        batched driver. ``op2`` is a 2-lane stacked operator (use
        ``operators.stack_masks(base, [x_mask, y_mask])``), ``uv`` the
        (..., 2, N) stacked queries. Same decision formulas as
        ``judge_double_greedy``; one stacked matvec per loop step."""
        if self.config.block_size > 1:
            raise NotImplementedError(
                "judge_double_greedy_batch stacks two scalar query "
                "systems; block_size > 1 brackets tr B^T f(A) B and has "
                "no gain-judge semantics — use block_size=1")

        def gain_bounds(lo, hi):
            lo_p, hi_p = _log_gain_bounds(t, lo[..., 0], hi[..., 0])
            lo_log_y, hi_log_y = _log_gain_bounds(t, lo[..., 1], hi[..., 1])
            lo_m, hi_m = -hi_log_y, -lo_log_y
            relu = lambda x: jnp.maximum(x, 0.0)  # noqa: E731
            return relu(lo_p), relu(hi_p), relu(lo_m), relu(hi_m)

        def safety(lo, hi):
            lo_p, hi_p, lo_m, hi_m = gain_bounds(lo, hi)
            add_safe = p * hi_m <= (1 - p) * lo_p
            rem_safe = p * lo_m > (1 - p) * hi_p
            return add_safe, rem_safe

        def resolved(lo, hi):
            add_safe, rem_safe = safety(lo, hi)
            return jnp.broadcast_to((add_safe | rem_safe)[..., None],
                                    lo.shape)

        res = self.solve_batch(op2, uv, decide=resolved, lam_min=lam_min,
                               lam_max=lam_max)
        lo_p, hi_p, lo_m, hi_m = gain_bounds(res.lower, res.upper)
        add_safe = p * hi_m <= (1 - p) * lo_p
        rem_safe = p * lo_m > (1 - p) * hi_p
        mid = (p * 0.5 * (lo_m + hi_m)) <= ((1 - p) * 0.5 * (lo_p + hi_p))
        decision = jnp.where(add_safe, True, jnp.where(rem_safe, False, mid))
        return JudgeResult(decision=decision, certified=add_safe | rem_safe,
                           iterations=jnp.sum(res.iterations, axis=-1,
                                              dtype=res.iterations.dtype))

    # -- the pair driver (gap-weighted two-system refinement) ----------------

    def _prepare_pair(self, op_a, u, op_b, v, lam_min, lam_max):
        if self.config.block_size > 1:
            raise NotImplementedError(
                "the gap-weighted pair driver refines two scalar systems; "
                "block_size > 1 has no pair-judge semantics — use "
                "block_size=1")
        if self.config.fn != "inv":
            raise NotImplementedError(
                "the gap-weighted pair driver scores u^T A^-1 u only; "
                "matfun judges go through the batched driver "
                "(judge_kdpp_swap_batch / solve_batch with fn set)")
        if self.config.precondition != "none":
            raise NotImplementedError(
                "preconditioning is per-operator and would shift the two "
                "systems' quadrature scales differently; pair judges "
                "require precondition='none'")
        if self.config.reorth:
            raise NotImplementedError(
                "reorth is not implemented for the two-system driver; "
                "pair judges require reorth=False")
        if self.config.decide_every != 1:
            raise NotImplementedError(
                "the gap-weighted pair driver re-picks which side to "
                "refine from the bracket every iteration, so its decision "
                "rule cannot be deferred; pair judges require "
                "decide_every=1 (the batched kdpp/double-greedy judges "
                "support any cadence)")
        if lam_min is None or lam_max is None:
            _, _, lmn_a, lmx_a = self.prepare(op_a, u, lam_min, lam_max)
            _, _, lmn_b, lmx_b = self.prepare(op_b, v, lam_min, lam_max)
            lam_min = jnp.minimum(jnp.asarray(lmn_a), jnp.asarray(lmn_b))
            lam_max = jnp.maximum(jnp.asarray(lmx_a), jnp.asarray(lmx_b))
        return lam_min, lam_max

    def solve_pair(self, op_a, u: Array, op_b, v: Array, *,
                   resolved: Callable[[PairState], Array],
                   pick_a: Callable[[PairState], Array],
                   lam_min=None, lam_max=None) -> PairState:
        """Generic two-system retrospective loop (Alg. 7/9 skeleton).

        Per step, exactly one side of each lane advances: side a if
        ``pick_a(state)`` (and side a can still move), else side b — the
        gap-weighted refinement of paper Sec. 5.1.  Stops when
        ``resolved(state)`` everywhere or both sides are exhausted.

        A missing ``lam_min``/``lam_max`` is estimated per the config's
        spectrum mode on both operators (the union interval is used).
        """
        lam_min, lam_max = self._prepare_pair(op_a, u, op_b, v, lam_min,
                                              lam_max)
        max_iters = self.config.max_iters
        stepfn = self._stepper()
        cfg = self.config
        op_a = _ops.configure_backend(op_a, cfg.backend, cfg.pallas_interpret)
        op_b = _ops.configure_backend(op_b, cfg.backend, cfg.pallas_interpret)
        st0 = PairState(a=_gql.gql_init(op_a, u, lam_min, lam_max),
                        b=_gql.gql_init(op_b, v, lam_min, lam_max))

        def exhausted(st):
            return (st.a.done | (st.a.it >= max_iters)) & \
                   (st.b.done | (st.b.it >= max_iters))

        def needs_more(st):
            return ~resolved(st) & ~exhausted(st)

        def cond(st):
            return jnp.any(needs_more(st))

        def body(st):
            pick = pick_a(st)
            pick = (pick & ~st.a.done & (st.a.it < max_iters)) | \
                   (st.b.done | (st.b.it >= max_iters))
            a1 = stepfn(op_a, st.a, lam_min, lam_max, None)
            b1 = stepfn(op_b, st.b, lam_min, lam_max, None)
            nm = needs_more(st)
            return PairState(a=tree_freeze(a1, st.a, ~(nm & pick)),
                             b=tree_freeze(b1, st.b, ~(nm & ~pick)))

        return jax.lax.while_loop(cond, body, st0)

    def judge_kdpp_swap(self, op_a, u: Array, op_b, v: Array, t: Array,
                        p: Array, *, lam_min=None,
                        lam_max=None) -> JudgeResult:
        """Alg. 7 (kDPP-JudgeGauss): True iff t < p * v^T B^-1 v - u^T A^-1 u."""
        def bounds(st):
            # accept-safe requires t < p*lower_v - upper_u;
            # reject-safe requires t >= p*upper_v - lower_u.
            lo = p * _gql.lower_bound(st.b) - _gql.upper_bound(st.a)
            hi = p * _gql.upper_bound(st.b) - _gql.lower_bound(st.a)
            return lo, hi

        def resolved(st):
            lo, hi = bounds(st)
            return (t < lo) | (t >= hi)

        st = self.solve_pair(
            op_a, u, op_b, v, resolved=resolved,
            pick_a=lambda st: _gql.gap(st.a) > p * _gql.gap(st.b),
            lam_min=lam_min, lam_max=lam_max)
        lo, hi = bounds(st)
        decision = self.threshold_decision(t, lo, hi)
        return JudgeResult(decision=decision, certified=resolved(st),
                           iterations=st.a.it + st.b.it)

    def judge_double_greedy(self, op_x, u: Array, op_y, v: Array, t: Array,
                            p: Array, *, lam_min=None,
                            lam_max=None) -> JudgeResult:
        """Alg. 9 (DG-JudgeGauss): True (add element) iff

            p * [Delta^-]_+ <= (1 - p) * [Delta^+]_+

        with Delta^+ = log(t - u^T A_X^-1 u)   (gain of adding to X)
             Delta^- = -log(t - v^T A_Y'^-1 v) (gain of removing from Y)

        (Sec. 5.2 of the paper swaps the +/- formulas relative to its own
        Sec. 2 definitions; we follow Sec. 2 / Buchbinder et al., which the
        exact-baseline tests verify.)
        """
        def gain_bounds(st):
            lo_p, hi_p = _log_gain_bounds(t, _gql.lower_bound(st.a),
                                          _gql.upper_bound(st.a))
            lo_log_y, hi_log_y = _log_gain_bounds(
                t, _gql.lower_bound(st.b), _gql.upper_bound(st.b))
            # Delta^- = -log(...): bounds swap
            lo_m, hi_m = -hi_log_y, -lo_log_y
            relu = lambda x: jnp.maximum(x, 0.0)  # noqa: E731
            return relu(lo_p), relu(hi_p), relu(lo_m), relu(hi_m)

        def resolved(st):
            lo_p, hi_p, lo_m, hi_m = gain_bounds(st)
            add_safe = p * hi_m <= (1 - p) * lo_p
            rem_safe = p * lo_m > (1 - p) * hi_p
            return add_safe | rem_safe

        def pick_a(st):
            lo_p, hi_p, lo_m, hi_m = gain_bounds(st)
            # tighten the Delta^+ side if its weighted gap dominates
            return (1 - p) * (hi_p - lo_p) >= p * (hi_m - lo_m)

        st = self.solve_pair(op_x, u, op_y, v, resolved=resolved,
                             pick_a=pick_a, lam_min=lam_min, lam_max=lam_max)
        lo_p, hi_p, lo_m, hi_m = gain_bounds(st)
        add_safe = p * hi_m <= (1 - p) * lo_p
        rem_safe = p * lo_m > (1 - p) * hi_p
        mid = (p * 0.5 * (lo_m + hi_m)) <= ((1 - p) * 0.5 * (lo_p + hi_p))
        decision = jnp.where(add_safe, True, jnp.where(rem_safe, False, mid))
        return JudgeResult(decision=decision, certified=add_safe | rem_safe,
                           iterations=st.a.it + st.b.it)
