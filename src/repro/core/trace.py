"""Stochastic trace estimation on the quadrature runtime (DESIGN.md Sec. 9).

``tr f(A) = E[z^T f(A) z]`` for Rademacher probes z (Hutchinson), and
each probe's bilinear form gets a RETROSPECTIVE quadrature bracket from
the matfun drive (core/matfun.py) — so the estimator inherits the
paper's machinery wholesale: probes run as lanes of the batched (or
device-sharded) driver, tighten monotonically, freeze per-lane the
moment their bracket resolves, and are resumable probe-by-probe.

Two probe regimes:

  * ``num_probes=None`` — EXACT mode: the N unit vectors e_i. The
    probe sum IS ``tr f(A)`` (no stochastic error), so the combined
    bracket is a deterministic certificate containing the true trace.
    This is what ``dpp.log_likelihood`` uses for bracketed logdet
    normalizers.
  * ``num_probes=P`` — Hutchinson mode: P Rademacher probes, drawn as
    ``fold_in(key, i)`` per probe index so the stream is reproducible
    and EXTENDABLE (resuming with a larger ``num_probes`` adds probes
    without re-running the old ones). The deterministic bracket then
    contains the probe-sample mean (not the trace itself); the
    statistical interval widens it by a normal-approximation
    confidence-interval half-width over the probe midpoints.

Interval semantics (the ``TraceQuadResult`` fields):

    lower/upper            deterministic quadrature bracket on the
                           CURRENT probe-sample mean — retrospective,
                           tightens with more quadrature iterations,
                           contains tr f(A) exactly in exact mode
    estimate               mean of the per-probe bracket midpoints
    stat_lower/stat_upper  [lower, upper] widened by the CI half-width
                           z_conf * std(mid)/sqrt(P) — covers BOTH error
                           sources (quadrature + sampling); collapses to
                           the deterministic bracket in exact mode
"""
from __future__ import annotations

import statistics
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import solver as _solver
from ..obs import registry as _obs_registry
from ..obs import spans as obs_spans

Array = jax.Array


class TraceQuadState(NamedTuple):
    """Probe-by-probe resume handle: which probes ran, and their banked
    brackets. Host-side bookkeeping (numpy), cheap to checkpoint.
    ``key_fp``/``interval`` fingerprint the probe stream and the
    spectral interval so a resume with a different key or lam bounds is
    rejected instead of silently mixing incompatible probes."""
    fn: str
    count: int                 # probes consumed so far
    exact: bool                # unit-vector mode (num_probes=None)
    probe_lower: np.ndarray    # (lanes,) per-lane bracket lowers; a lane
    #                            is one probe (block_size=1) or one
    #                            b-probe block (block_size=b)
    probe_upper: np.ndarray    # (lanes,)
    iterations: np.ndarray     # (lanes,) quadrature iterations per lane
    key_fp: tuple = ()         # PRNG-key fingerprint (empty in exact mode)
    interval: tuple = ()       # (lam_min, lam_max) the brackets used
    block_size: int = 1        # probes per lane (DESIGN.md Sec. 13);
    #                            resumes must match — banked lane
    #                            brackets are tr over b-probe blocks


class TraceQuadResult(NamedTuple):
    lower: float               # deterministic bracket on the probe mean
    upper: float
    estimate: float            # mean of per-probe bracket midpoints
    stat_lower: float          # det bracket widened by the CI half-width
    stat_upper: float
    std_error: float           # std(mid) / sqrt(P)  (0.0 in exact mode)
    num_probes: int
    iterations: int            # total quadrature iterations spent
    state: TraceQuadState      # resume handle (probe-by-probe)


def _rademacher_probe(key: Array, index: int, n: int, dtype) -> Array:
    """Probe ``index`` of the reproducible Hutchinson stream (tests and
    resumed runs re-derive the identical probe from (key, index))."""
    return jax.random.rademacher(jax.random.fold_in(key, index), (n,),
                                 dtype)


@partial(jax.jit, static_argnames=("count", "exact", "block"))
def _probe_chunk(key, start, op, *, count: int, exact: bool, block: int):
    """Probes ``start .. start + count`` of ``op``'s size and dtype as one
    compiled call: ``start`` is traced, so equal-length chunks share one
    compile. Rows are row-stacked into ``block``-wide probe blocks when
    ``block > 1``. The dtype is that of ``op.diag()``, read at trace
    time; the diagonal itself is never computed."""
    n, dtype = op.n, op.diag().dtype
    idx = start + jnp.arange(count)
    if exact:
        # only the chunk's rows of I_N — never the full (N, N) identity,
        # which would defeat probe_chunk's memory bounding at large N.
        # Indices >= n (block-mode padding of the last block) produce
        # exact-zero rows, which the block init QR deflates: dead slots
        # contribute exactly 0 to the block trace.
        us = jax.nn.one_hot(idx, n, dtype=dtype)
    else:
        # bit-identical to per-index _rademacher_probe calls (fold_in
        # per index)
        us = jax.vmap(lambda i: _rademacher_probe(key, i, n, dtype))(idx)
    if block > 1:
        us = us.reshape(count // block, block, n)
    return us


@partial(jax.jit, static_argnames=("mesh", "axis"))
def _solve_chunk(solver, op, us, lam_min, lam_max, *, mesh=None,
                 axis: str = "lanes"):
    """One probe chunk's whole solve (set-up, loop, finalize) as one
    compiled call, returning its per-lane ``(lower, upper,
    iterations)``. Module-level like ``serve.engine._flush_run``: the
    cache keys on (solver config, op treedef, shapes, mesh), so a fresh
    ``BIFSolver`` and a new probe key per job reuse one compile;
    ``lam_min``/``lam_max`` ride along as runtime values."""
    _obs_registry.count("core.trace.solve_chunk")
    if mesh is None:
        res = solver.solve_batch(op, us, lam_min=lam_min, lam_max=lam_max)
    else:
        res = solver.solve_batch_sharded(op, us, mesh=mesh, axis=axis,
                                         lam_min=lam_min, lam_max=lam_max)
    return res.lower, res.upper, res.iterations


def trace_quad(op, fn: str = "log", num_probes: Optional[int] = None, *,
               lam_min, lam_max, solver: _solver.BIFSolver | None = None,
               max_iters: int = 64, rtol: float = 1e-4, atol: float = 1e-8,
               key: Array | None = None, probe_chunk: int | None = None,
               confidence: float = 0.95, mesh=None,
               lane_axis: str = "lanes", block_size: int = 1,
               state: TraceQuadState | None = None) -> TraceQuadResult:
    """Bracketed stochastic (or exact-probe) estimate of ``tr f(A)``.

    Probes run as lanes of the batched matfun driver — one stacked
    matvec per quadrature iteration across the whole probe block, lanes
    frozen as their brackets resolve — sharded over ``mesh`` when given
    (the multi-device trace-probe path of tests/sharded_check.py).

    ``state`` resumes probe-by-probe: pass a previous result's
    ``.state`` with a larger ``num_probes`` and only the NEW probes are
    solved; the accumulated per-probe brackets merge deterministically
    (the probe stream is keyed by index). ``fn``/mode must match the
    banked state.

    ``block_size = b > 1`` groups consecutive probes into b-wide blocks
    and runs each block as ONE lane of the block-Krylov driver
    (DESIGN.md Sec. 13): a lane brackets ``tr Z^T f(A) Z`` over its b
    probes — one gemm-shaped stacked matvec per iteration instead of b
    gemvs — and near-parallel probe directions deflate instead of
    burning separate Krylov chains. ``num_probes`` must be a multiple
    of b (whole blocks); the probe STREAM is unchanged (probe i is
    still ``fold_in(key, i)``), so extending a banked state adds whole
    blocks bit-identically. The CI is over the per-block means
    (block bracket midpoint / b), each an unbiased ``tr f(A)``
    estimate. In exact mode the last block zero-pads past N; zero
    columns deflate at the init QR and contribute exactly 0.

    ``lam_min``/``lam_max`` must bound the operator's spectrum (the
    Radau bounds need true outer estimates — the usual contract). Note
    the trace is of the operator AS GIVEN: for a ``Masked`` operator
    the identity block contributes ``(N - |Y|) * f(1)`` — zero for
    f=log, which is exactly why masked logdets need no correction.
    """
    b = int(block_size)
    if b < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    if solver is None:
        solver = _solver.BIFSolver.create(max_iters=max_iters, rtol=rtol,
                                          atol=atol, fn=fn,
                                          block_size=b)
    else:
        if solver.config.fn != fn:
            solver = solver.replace(fn=fn)  # SolverConfig validates the tag
        if solver.config.block_size != b:
            solver = solver.replace(block_size=b)

    n = op.n
    exact = num_probes is None
    total = n if exact else int(num_probes)
    if total < 1:
        raise ValueError(f"num_probes must be >= 1, got {num_probes}")
    if b > 1 and not exact and total % b:
        raise ValueError(
            f"num_probes={total} is not a multiple of block_size={b}; "
            f"block mode consumes whole probe blocks (the banked probe "
            f"stream stays extendable only on block boundaries)")
    if key is None:
        key = jax.random.key(0)
    key_fp = () if exact else \
        tuple(np.asarray(jax.random.key_data(key)).ravel().tolist())
    interval = tuple(np.asarray(x, np.float64).ravel().tolist()
                     for x in (lam_min, lam_max))

    if state is not None:
        if state.fn != fn or state.exact != exact:
            raise ValueError(
                f"resume state banks fn={state.fn!r} (exact={state.exact}); "
                f"got fn={fn!r} (exact={exact}) — trace states resume the "
                f"estimator they were started as")
        if state.key_fp != key_fp:
            raise ValueError(
                "resume state banks probes drawn from a different key; "
                "extending with a new key would mix incompatible probe "
                "streams — pass the original key (or state=None)")
        if state.interval != interval:
            raise ValueError(
                f"resume state banks brackets for the spectral interval "
                f"{state.interval}, got {interval} — mixed intervals "
                f"would mix incomparable brackets (pass state=None)")
        if state.block_size != b:
            raise ValueError(
                f"resume state banks block_size={state.block_size} lane "
                f"brackets; got block_size={b} — block traces are "
                f"tr over b-probe blocks and cannot be re-bucketed "
                f"(pass state=None)")
        if total < state.count:
            raise ValueError(
                f"num_probes={total} < {state.count} probes already banked; "
                f"resuming can only extend")
        done_lo = [state.probe_lower]
        done_hi = [state.probe_upper]
        done_it = [state.iterations]
        start = state.count
    else:
        done_lo, done_hi, done_it = [], [], []
        start = 0

    # block mode walks padded probe indices (whole blocks; exact mode's
    # final block zero-pads past N) and rounds the chunk up to blocks
    walk_total = -(-total // b) * b
    chunk = walk_total - start if probe_chunk is None \
        else max(int(probe_chunk), 1)
    if b > 1:
        chunk = -(-chunk // b) * b
    pos = -(-start // b) * b   # banked lanes end on a block boundary
    while pos < walk_total:
        stop = min(pos + chunk, walk_total)
        with obs_spans.span("trace.probes"):
            us = _probe_chunk(key, pos, op, count=stop - pos,
                              exact=exact, block=b)
        with obs_spans.span("trace.solve"):
            res = _solve_chunk(solver, op, us, lam_min, lam_max,
                               mesh=mesh, axis=lane_axis)
        # the host waits here for the chunk's device work
        with obs_spans.span("trace.readback"):
            lo, hi, it = jax.device_get(res)   # one overlapped copy
            done_lo.append(lo)
            done_hi.append(hi)
            done_it.append(it)
        pos = stop

    with obs_spans.span("trace.reduce"):
        # never empty: num_probes >= 1, banked or solved in a chunk
        lo, hi, it = (np.concatenate(d)
                      for d in (done_lo, done_hi, done_it))

        # deterministic bracket: in exact mode the SUM over the N unit
        # probes is tr f(A) (a true certificate; block lanes sum their b
        # slots already, padding slots contribute exactly 0); in Hutchinson
        # mode the MEAN over the lanes, divided by the probes-per-lane b,
        # is the sample estimate of it. The CI is over the per-lane means
        # mid/b — each an unbiased tr f(A) estimate (the variance-reduced
        # block estimator: a lane averages b probes).
        mid = 0.5 * (lo + hi)
        if exact:
            mean_lo, mean_hi = float(lo.sum()), float(hi.sum())
            estimate = float(mid.sum())
            se = 0.0
        else:
            lane_mid = mid / b
            mean_lo, mean_hi = float(lo.mean() / b), float(hi.mean() / b)
            estimate = float(lane_mid.mean())
            se = float(np.std(lane_mid, ddof=1) / np.sqrt(len(lane_mid))) \
                if len(lane_mid) > 1 else 0.0
        z = statistics.NormalDist().inv_cdf(0.5 + 0.5 * confidence) \
            if se > 0.0 else 0.0
        half = z * se

        new_state = TraceQuadState(fn=fn, count=total, exact=exact,
                                   probe_lower=lo, probe_upper=hi,
                                   iterations=it, key_fp=key_fp,
                                   interval=interval, block_size=b)
        return TraceQuadResult(
            lower=mean_lo, upper=mean_hi, estimate=estimate,
            stat_lower=mean_lo - half, stat_upper=mean_hi + half,
            std_error=se, num_probes=total,
            iterations=int(it.sum()), state=new_state)


def logdet_quad(op, num_probes: Optional[int] = None, *, lam_min, lam_max,
                **kwargs) -> TraceQuadResult:
    """Bracketed ``logdet(A) = tr log(A)``  (Bai & Golub 1996, on the
    retrospective runtime): sugar for ``trace_quad(op, 'log', ...)``."""
    return trace_quad(op, "log", num_probes, lam_min=lam_min,
                      lam_max=lam_max, **kwargs)
