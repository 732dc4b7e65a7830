"""Batched serving engines.

``Engine``: prefill + decode with KV caches — request queue, padded
batching, greedy/temperature sampling, per-request stop handling, int8
KV option. The heavy lifting (sharded steps) comes from launch.steps; on
CPU tests this runs the same code unsharded.

``BIFEngine``: the quadrature-serving counterpart (DESIGN.md Sec. 8) —
a continuous-batching scheduler over a fixed pool of ``max_batch``
quadrature lanes. Requests queue via ``submit``; ``flush`` admits them
into free lanes, steps the whole pool in fixed-size chunks through the
resumable runtime (``BIFSolver.step_n``), retires lanes the moment
their decision resolves (or their iteration/deadline budget runs out),
and backfills the vacated lanes from the queue mid-flight — no
pad-to-``max_batch`` lockstep flushes, so one straggler bracket no
longer stalls a whole chunk of fast judges. Budget-interrupted requests
come back as partial results carrying their banked bracket and
:class:`~repro.core.solver.QuadState`; resubmitting them resumes the
solve bit-exactly where it stopped.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from functools import partial

from jax.sharding import PartitionSpec as P

from ..core import matfun as core_matfun
from ..core import operators as core_ops
from ..core import sharded as core_sharded
from ..core import spectrum as core_spectrum
from ..core.loop_utils import tree_freeze
from ..core.solver import BIFSolver, QuadState
from ..models import model as M
from ..obs import metrics as obs_metrics
from ..obs import registry as _obs_registry
from ..obs import spans as obs_spans


@dataclasses.dataclass
class Request:
    prompt: np.ndarray          # (T,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    out_tokens: Optional[np.ndarray] = None


# Trace-time counters for the shared generation drivers (prefill +
# decode), reported through the central obs.registry (one
# ``retrace_counts()`` snapshot covers every serve/ jit): each
# ``count()`` call runs at trace time only, so it increments once per
# fresh compile. The jit cache keys on (cfg, shapes), so two Engines
# around the same reduced arch reuse one compile — instance-level jits
# here used to rebuild the cache per Engine.
_GEN_TRACE_KEYS = ("serve.engine.prefill", "serve.engine.decode")


def generate_trace_count() -> int:
    """How many times the shared prefill/decode drivers have been traced
    (== compiled) in this process."""
    return sum(_obs_registry.value(k) for k in _GEN_TRACE_KEYS)


@partial(jax.jit, static_argnames=("cfg",))
def _prefill_run(cfg, params, batch, caches):
    _obs_registry.count("serve.engine.prefill")
    return M.prefill(cfg, params, batch, caches)


@partial(jax.jit, static_argnames=("cfg",))
def _decode_run(cfg, params, caches, batch):
    _obs_registry.count("serve.engine.decode")
    return M.decode_step(cfg, params, caches, batch)


class Engine:
    def __init__(self, cfg, params, *, max_batch: int = 8,
                 max_seq: int = 256, kv_dtype=jnp.float32,
                 quantized_kv: bool = False, seed: int = 0):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.kv_dtype = kv_dtype
        self.quantized_kv = quantized_kv
        self.key = jax.random.key(seed)

    def _prefill(self, params, batch, caches):
        return _prefill_run(self.cfg, params, batch, caches)

    def _decode(self, params, caches, batch):
        return _decode_run(self.cfg, params, caches, batch)

    def _sample(self, logits: jax.Array, temperature: float) -> jax.Array:
        if temperature <= 0.0:
            return jnp.argmax(logits[:, -1, :], axis=-1)
        self.key, k = jax.random.split(self.key)
        return jax.random.categorical(k, logits[:, -1, :] / temperature)

    def generate(self, requests: List[Request]) -> List[Request]:
        """Serve a batch of requests (padded to a common prompt length)."""
        assert len(requests) <= self.max_batch
        b = len(requests)
        t = max(len(r.prompt) for r in requests)
        prompts = np.zeros((b, t), np.int32)
        for i, r in enumerate(requests):
            prompts[i, t - len(r.prompt):] = r.prompt  # left-pad
        caches = M.make_caches(self.cfg, b, self.max_seq, self.kv_dtype,
                               quantized_kv=self.quantized_kv)
        batch = {"tokens": jnp.asarray(prompts)}
        caches, logits = self._prefill(self.params, batch, caches)
        max_new = max(r.max_new_tokens for r in requests)
        outs = np.zeros((b, max_new), np.int32)
        tok = self._sample(logits, requests[0].temperature)
        outs[:, 0] = np.asarray(tok)
        for step in range(1, max_new):
            dec = {"tokens": jnp.asarray(tok)[:, None],
                   "position": jnp.asarray([t + step - 1], jnp.int32)}
            caches, logits = self._decode(self.params, caches, dec)
            tok = self._sample(logits, requests[0].temperature)
            outs[:, step] = np.asarray(tok)
        for i, r in enumerate(requests):
            r.out_tokens = outs[i, :r.max_new_tokens]
        return requests


@dataclasses.dataclass
class BIFRequest:
    """One bilinear-inverse-form query against the engine's matrix.

    ``t`` set: threshold judge (decision = t < u^T f(A) u, Alg. 4);
    ``t`` None: adaptive bracket to the solver's rtol/atol.
    ``fn``: spectral function tag (matfun registry; None = the engine
    solver's ``config.fn``). A matfun engine (solver ``fn != 'inv'``)
    serves MIXED spectral functions per-lane in one pool — the Jacobi-
    matrix eigensolve is fn-independent, lanes just select their f on
    the shared Ritz values; a legacy f=1/x engine only takes
    'inv'-tagged (or untagged) requests and stays bit-exact with the
    pre-matfun scheduler.
    ``mask``: optional principal-submatrix mask (the A_Y of a chain).
    ``max_iters``: per-submission quadrature-iteration budget (on top of
    the solver's ``max_iters`` ceiling); ``deadline``: wall-clock cutoff
    (a ``time.monotonic()`` instant, checked at admission — an already-
    expired request retires immediately with zero iterations — and at
    chunk boundaries). A
    request whose budget/deadline expires before its decision resolves
    comes back PARTIAL: ``resolved=False``, the banked bracket in
    ``lower``/``upper``, and the lane's quadrature state in ``state`` —
    resubmit it (optionally with a new budget) to resume the solve
    bit-exactly where it stopped instead of starting over.
    """
    u: np.ndarray
    t: Optional[float] = None
    mask: Optional[np.ndarray] = None
    fn: Optional[str] = None
    max_iters: Optional[int] = None
    deadline: Optional[float] = None
    # filled by BIFEngine.flush():
    lower: Optional[float] = None
    upper: Optional[float] = None
    decision: Optional[bool] = None
    certified: Optional[bool] = None
    iterations: Optional[int] = None      # cumulative across resubmissions
    resolved: Optional[bool] = None       # decision/tolerance resolved OR
    #                                       Krylov-exhausted (bracket exact)
    state: Optional[Any] = None           # banked per-lane QuadState (partial)
    # set when a flush failed on this request's chunk (the request is
    # dropped from the queue; resubmit to retry a transient failure)
    error: Optional[Exception] = None


# Trace-time counters for the shared flush drivers (lockstep _flush_run
# + continuous-batching _pool_admit_run/_pool_scatter_run/
# _pool_step_run), one obs.registry key per driver: each increments once
# per fresh compile (jit cache miss), never on cache hits. Tests pin the
# bucketed-padding contract of serve.kv_select.rank_blocks with the
# aggregate (flush_trace_count below).
_FLUSH_TRACE_KEYS = ("serve.engine.pool_admit", "serve.engine.pool_scatter",
                     "serve.engine.pool_step", "serve.engine.flush")

# QuadState threading contract (quadlint QL001): per-lane fields the
# continuous-batching pool does NOT merge/bank. `basis` (reorth storage)
# never reaches the scheduler — _flush_continuous falls back to the
# lockstep path for reorth configs — so admission and banking
# legitimately skip it (banked states carry basis=None).
ENGINE_ADMIT_EXCLUDED = ("basis",)


def flush_trace_count() -> int:
    """How many times the shared BIFEngine flush drivers have been traced
    (== compiled) in this process."""
    return sum(_obs_registry.value(k) for k in _FLUSH_TRACE_KEYS)


def _mixed_decide(solver, lo, hi, ts, has_t):
    """The engine's per-lane resolution rule: judge lanes resolve on
    their threshold, bracket lanes on the solver's tolerance rule."""
    thr = (ts < lo) | (ts >= hi)
    return jnp.where(has_t, thr, solver.tolerance_resolved(lo, hi))


@partial(jax.jit, static_argnames=("mesh",))
def _pool_admit_run(solver, op, st, coeffs, us, masks, fresh, fnidx,
                    lam_min, lam_max, *, mesh=None):
    """Seed the ``fresh`` lanes of the pool from (pre-masked) ``us`` /
    ``masks``; every other lane's quadrature state passes through
    untouched. ``st=None`` initializes the whole pool (unoccupied lanes
    carry zero queries, which ``gql_init`` marks done at iteration one —
    the usual dummy-lane rule). On a matfun pool (tracking solver)
    ``fnidx`` is the authoritative per-lane spectral-function index and
    ``coeffs`` the prior pool coefficient history, frozen the same way.
    Module-level jit shared across engines, keyed on (solver config, op
    treedef, pool shapes, mesh).

    With ``mesh`` every device seeds the whole pool (replicated): the
    init then runs on the same shapes as on one device, so a lane's
    numbers do not depend on how the compiler would have split the pool
    (an XLA gemm rounds differently at different lane counts), and a
    kernel in the init needs no partitioning. The step shards the
    lanes."""
    _obs_registry.count("serve.engine.pool_admit")

    def admit(op, st, coeffs, us, masks, fresh, fnidx, lam_min, lam_max):
        state = solver.init_state(core_ops.Masked(op, masks), us,
                                  lam_min=lam_min, lam_max=lam_max)
        if st is not None:
            state = state._replace(st=tree_freeze(state.st, st, ~fresh))
            if coeffs is not None:
                state = state._replace(
                    coeffs=tree_freeze(state.coeffs, coeffs, ~fresh))
        if state.coeffs is not None and fnidx is not None:
            state = state._replace(
                coeffs=dataclasses.replace(state.coeffs, fnidx=fnidx))
        return state

    args = (op, st, coeffs, us, masks, fresh, fnidx, lam_min, lam_max)
    if mesh is None:
        return admit(*args)
    return jax.shard_map(admit, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(*args)


@jax.jit
def _pool_scatter_run(st, lane_st, idx):
    """Insert one banked lane state (GQLState, and the lane's coeff
    history on matfun pools) at pool slot ``idx`` (warm admission of a
    resubmitted partial request)."""
    _obs_registry.count("serve.engine.pool_scatter")
    return jax.tree.map(lambda pool, lane: pool.at[idx].set(lane),
                        st, lane_st)


@partial(jax.jit, static_argnames=("n", "mesh", "axis"))
def _pool_step_run(solver, state, ts, has_t, it_cap, *, n, mesh=None,
                   axis: str = "lanes"):
    """One scheduler round: advance the pool by at most ``n`` quadrature
    iterations through the resumable runtime (``BIFSolver.step_n``, or
    its sharded twin when the engine is mesh-bound), freezing lanes the
    moment they resolve or exhaust their per-request ``it_cap`` budget.
    Returns the stepped state plus everything the host scheduler needs
    to retire lanes."""
    _obs_registry.count("serve.engine.pool_step")
    if mesh is None:
        state = solver.step_n(
            state, n, lambda lo, hi: _mixed_decide(solver, lo, hi, ts,
                                                   has_t),
            it_cap=it_cap)
    else:
        state = core_sharded.step_n_sharded(
            solver, state, n,
            lambda lo, hi, ts_, ht_: _mixed_decide(solver, lo, hi, ts_,
                                                   ht_),
            decide_args=(ts, has_t), it_cap=it_cap, mesh=mesh, axis=axis)
    lo, hi = solver._bracket2(state.st, state.coeffs, state.lam_min,
                              state.lam_max)
    resolved = _mixed_decide(solver, lo, hi, ts, has_t)
    decision = BIFSolver.threshold_decision(ts, lo, hi)
    return state, lo, hi, resolved, decision, state.st.done, state.st.it


@partial(jax.jit, static_argnames=("mesh", "axis"))
def _flush_run(solver, op, us, masks, ts, has_t, lam_min, lam_max, *,
               mesh=None, axis: str = "lanes"):
    """ONE shared jitted flush driver for every BIFEngine.

    Module-level on purpose: the jit cache keys on (solver config, op
    treedef, shapes, mesh), so two engines around same-shaped systems —
    e.g. consecutive ``rank_blocks`` calls whose block counts fall in
    the same padding bucket — reuse one compile instead of tracing a
    fresh per-engine closure each time. ``lam_min``/``lam_max`` ride
    along as runtime scalars for the same reason.
    """
    _obs_registry.count("serve.engine.flush")
    mop = core_ops.Masked(op, masks)

    def decide(lo, hi, ts, has_t):
        # judge lanes resolve on their threshold, bracket lanes on the
        # solver's own tolerance rule
        thr = (ts < lo) | (ts >= hi)
        return jnp.where(has_t, thr, solver.tolerance_resolved(lo, hi))

    if mesh is None:
        res = solver.solve_batch(mop, us,
                                 decide=lambda lo, hi: decide(lo, hi, ts,
                                                              has_t),
                                 lam_min=lam_min, lam_max=lam_max)
    else:
        res = core_sharded.solve_batch_sharded(
            solver, mop, us, decide, decide_args=(ts, has_t), mesh=mesh,
            axis=axis, lam_min=lam_min, lam_max=lam_max)
    decision = BIFSolver.threshold_decision(ts, res.lower, res.upper)
    return (res.lower, res.upper, decision,
            decide(res.lower, res.upper, ts, has_t), res.iterations,
            res.converged)


class BIFEngine:
    """Continuous-batching scheduler for BIF requests (DESIGN.md Sec. 8).

    Requests accumulate via ``submit``; ``flush`` serves them through a
    fixed pool of ``max_batch`` quadrature lanes. Each scheduler round
    admits queued requests into free lanes (FIFO), steps the WHOLE pool
    by ``chunk_iters`` quadrature iterations (aligned up to a whole
    number of ``decide_every`` rounds) through the resumable runtime
    (one stacked matvec per iteration; resolved lanes frozen
    bit-exactly), then retires every lane whose decision resolved — or
    whose per-request iteration/deadline budget ran out — and backfills
    the vacated lanes from the queue mid-flight. A straggler bracket
    therefore occupies one lane while fast judges stream through the
    rest, instead of stalling a padded lockstep chunk behind it
    (``flush(mode='lockstep')`` keeps the old pad-to-``max_batch``
    behavior for comparison; ``benchmarks/engine_throughput.py`` tracks
    the gap). Completion is FIFO-preserving: ``flush`` returns requests
    in submission order regardless of retirement order.

    Mixed traffic is fine: judge lanes resolve on their threshold,
    bracket lanes on tolerance. Unoccupied lanes (zero query) resolve at
    iteration one and cost only their share of the stacked matvec.

    With ``mesh`` set (a 1-D lane mesh from
    ``launch.mesh.make_lane_mesh``), pool steps run the sharded stepping
    driver of DESIGN.md Sec. 7/8: ``max_batch`` is rounded up to a whole
    number of lanes per device and the pool's lanes split across the
    mesh (the pool state shards with them).
    """

    def __init__(self, op, *, solver: BIFSolver | None = None,
                 max_batch: int = 64, lam_min: float | None = None,
                 lam_max: float | None = None, mesh=None,
                 lane_axis: str = "lanes", chunk_iters: int = 8,
                 metrics: bool = True, convergence_log=None):
        self.op = op
        self.solver = solver if solver is not None \
            else BIFSolver.create(max_iters=64, rtol=1e-3)
        if self.solver.config.block_size > 1:
            raise NotImplementedError(
                "the serving engine batches scalar (u, mask) queries; "
                "block_size > 1 brackets tr B^T f(A) B probe blocks and "
                "has no per-request semantics — use a block_size=1 "
                "solver (block traces go through trace_quad)")
        self.mesh = mesh
        self.lane_axis = lane_axis
        # step_n quantises to whole decide_every rounds — align the
        # serving chunk UP to the cadence so every flush makes progress
        # (a chunk smaller than one round would be a no-op and livelock
        # the pool)
        r = self.solver.config.decide_every
        self.chunk_iters = -(-max(1, int(chunk_iters)) // r) * r
        max_batch = int(max_batch)
        if mesh is not None:
            # padded flushes must round up to num_devices x lanes_per_device
            ndev = mesh.shape[lane_axis]
            max_batch = -(-max_batch // ndev) * ndev
        self.max_batch = max_batch
        if lam_min is None or lam_max is None:
            # one-time certified interval, valid for every request mask
            # by interlacing (DESIGN.md Sec. 3.2)
            est = core_spectrum.gershgorin_bounds_spd(op)
            if lam_min is None:
                lam_min = float(est.lam_min)
            if lam_max is None:
                lam_max = float(est.lam_max)
        self.lam_min, self.lam_max = float(lam_min), float(lam_max)
        self._queue: List[BIFRequest] = []
        self._dtype = np.dtype(np.asarray(self.op.diag()).dtype)
        # Observability (DESIGN.md Sec. 14): per-engine metric registry,
        # written HOST-SIDE only — every observation below reads values
        # the scheduler already materialized with np.asarray, so metrics
        # on/off cannot perturb a single compiled computation (pinned by
        # tests/test_obs.py bit-parity). `convergence_log` (an
        # obs.health.ConvergenceLog) records per-round per-lane brackets
        # off the same host copies.
        self._metrics_on = bool(metrics)
        self._metrics = obs_metrics.MetricsRegistry()
        # flush sequence number: the `flush` arg that ties one flush's
        # program spans (bif.flush, bif.round, ...) together in a trace
        self._flushes = 0
        self.convergence_log = convergence_log

        def run(us, masks, ts, has_t):
            return _flush_run(
                self.solver, self.op, us, masks, ts, has_t,
                jnp.asarray(self.lam_min, us.dtype),
                jnp.asarray(self.lam_max, us.dtype),
                mesh=self.mesh, axis=self.lane_axis)

        self._run = run

    def submit(self, req: BIFRequest) -> BIFRequest:
        """Queue one request. Shapes are validated here so a malformed
        request is rejected at the door instead of poisoning a flush."""
        n = self.op.n
        u = np.asarray(req.u)
        if u.shape != (n,):
            raise ValueError(
                f"BIFRequest.u must have shape ({n},), got {u.shape}")
        if req.mask is not None and np.asarray(req.mask).shape != (n,):
            raise ValueError(
                f"BIFRequest.mask must have shape ({n},), got "
                f"{np.asarray(req.mask).shape}")
        cfg_fn = self.solver.config.fn
        fn = cfg_fn if req.fn is None else req.fn
        core_matfun.fn_index(fn)  # raises on unknown tags
        if cfg_fn == "inv" and fn != "inv":
            raise ValueError(
                f"this engine's solver runs the legacy f=1/x recurrence; "
                f"fn={fn!r} requests need an engine built with a matfun "
                f"solver (BIFSolver.create(fn=...), any registry fn — "
                f"mixed-fn pools are fine there)")
        if req.state is not None:
            # a banked state continues the ORIGINAL (u, mask) query: the
            # Lanczos recurrence is only valid for the system it was
            # started on. A mutated query must re-solve from scratch.
            mask = np.ones((n,), self._dtype) if req.mask is None \
                else np.asarray(req.mask, self._dtype)
            banked = getattr(req, "_banked_query", None)
            if banked is None \
                    or not np.array_equal(u.astype(self._dtype) * mask,
                                          banked) \
                    or not np.array_equal(mask,
                                          np.asarray(req.state.op.mask,
                                                     self._dtype)):
                raise ValueError(
                    "BIFRequest.state banks the solve of the originally "
                    "submitted (u, mask); changing either invalidates "
                    "the banked recurrence — set state=None to re-solve "
                    "the new query from scratch")
            banked_fn = "inv" if req.state.coeffs is None else \
                core_matfun.fn_name(int(req.state.coeffs.fnidx))
            if banked_fn != fn:
                raise ValueError(
                    f"BIFRequest.state banks a fn={banked_fn!r} solve; "
                    f"resubmitting it as fn={fn!r} would misread the "
                    f"banked history — set state=None to re-solve")
            if (req.state.coeffs is None) != (cfg_fn == "inv"):
                # a matfun pool scatters CoeffHistory lanes, a legacy
                # pool coeff-free ones; a presence mismatch would blow
                # up mid-flush and poison the in-flight requests
                raise ValueError(
                    "BIFRequest.state was banked by a "
                    f"{'legacy f=1/x' if req.state.coeffs is None else 'matfun'} "
                    f"engine pool and cannot resume on this one "
                    f"(solver fn={cfg_fn!r}) — set state=None to re-solve")
        if req.t is not None:
            try:
                req.t = float(req.t)
            except (TypeError, ValueError) as e:
                raise ValueError(
                    f"BIFRequest.t must be a scalar, got {req.t!r}") from e
        # Clear EVERY stale result field, not just the error: a request
        # resubmitted for refinement must not let a failed flush leave
        # the previous round's lower/upper/decision readable as if they
        # were current. The banked state/query stay — they are what a
        # resubmission resumes from (cumulative iteration counts live in
        # state.it and are restored at retirement).
        req.lower = req.upper = None
        req.decision = None
        req.certified = None
        req.iterations = None
        req.resolved = None
        req.error = None
        self._queue.append(req)
        req._obs_submit_t = time.monotonic()
        self._count("requests.submitted")
        if req.state is not None:
            self._count("requests.resubmitted")
        return req

    def pending(self) -> int:
        return len(self._queue)

    # -- observability (host-side only; see DESIGN.md Sec. 14) ------------

    def _count(self, name: str, n: int = 1) -> None:
        if self._metrics_on:
            self._metrics.counter(name).inc(n)

    def _observe(self, name: str, value: float) -> None:
        if self._metrics_on:
            self._metrics.histogram(name).observe(value)

    def _retire_obs(self, r: BIFRequest, now: float, *,
                    expired: bool = False) -> None:
        """Record one retirement. `now` is the scheduler's own clock
        read for this round — reused, never re-read, so the metrics see
        exactly the instants the scheduling decisions saw."""
        if not self._metrics_on:
            return
        self._count("requests.retired")
        sub_t = getattr(r, "_obs_submit_t", None)
        adm_t = getattr(r, "_obs_admit_t", None)
        if adm_t is not None:
            self._observe("request.latency_s", now - adm_t)
        elif sub_t is not None:
            # expired at the door: never admitted, queue-wait only
            self._observe("request.queue_wait_s", now - sub_t)
        if r.deadline is not None:
            self._observe("request.deadline_slack_s", r.deadline - now)
        if r.iterations is not None:
            self._observe("request.iterations", float(r.iterations))
        if r.resolved:
            self._count("requests.resolved")
        else:
            self._count("requests.partial")
            if expired:
                self._count("requests.expired")

    def stats(self) -> dict:
        """Plain-dict snapshot of this engine's request metrics:
        ``{"counters": {...}, "gauges": {...}, "histograms": {name:
        {count, sum, min, max, mean, p50, p90, p99, buckets}}}`` —
        queue-wait / admission-to-retire latency / deadline-slack /
        iteration histograms, submitted / resolved / partial / expired /
        errored / resubmitted counters, per-round pool occupancy."""
        return self._metrics.snapshot()

    def reset_stats(self) -> None:
        self._metrics.reset()

    def lower_step(self):
        """Lower one scheduler round at this engine's pool shape — the
        program every continuous-flush round runs. ``.compile()`` of the
        result shows which kernels the round holds (``tpu_custom_call``
        on the chip), so a caller can tell a kernel route from a silent
        reference fallback. Seeds a pool of zero queries to get there."""
        p, n, dt = self.max_batch, self.op.n, self._dtype
        cfg = self.solver.config
        fnidx = None if cfg.fn == "inv" else \
            jnp.full((p,), core_matfun.fn_index(cfg.fn), jnp.int32)
        state = _pool_admit_run(
            self.solver, self.op, None, None, jnp.zeros((p, n), dt),
            jnp.ones((p, n), dt), jnp.ones((p,), bool), fnidx,
            jnp.asarray(self.lam_min, dt), jnp.asarray(self.lam_max, dt),
            mesh=self.mesh)
        return _pool_step_run.lower(
            self.solver, state, jnp.zeros((p,), dt), jnp.zeros((p,), bool),
            jnp.zeros((p,), jnp.int32), n=self.chunk_iters, mesh=self.mesh,
            axis=self.lane_axis)

    def _step(self, state, ts, has_t, it_cap):
        """One pool decision round (seam for tests / fault injection)."""
        return _pool_step_run(self.solver, state, ts, has_t, it_cap,
                              n=self.chunk_iters, mesh=self.mesh,
                              axis=self.lane_axis)

    def flush(self, *, mode: str = "continuous") -> List[BIFRequest]:
        """Serve every queued request; returns them in submission order
        (FIFO-preserving completion — retirement order never reorders the
        returned list). Budget/deadline-interrupted requests come back
        partial (``resolved=False``) with their banked bracket + state.

        ``mode='lockstep'`` keeps the legacy padded chunk flushes (no
        backfill, budgets and deadlines ignored) — the benchmark
        baseline. Solver configs the scheduler does not take (reorth,
        preconditioning) fall back to it automatically.

        If the driver fails, the in-flight requests get their ``error``
        set and are dropped (resubmit to retry), the unadmitted tail
        stays queued in order, and the exception propagates; requests
        that already retired keep their results.
        """
        if mode not in ("continuous", "lockstep"):
            raise ValueError(f"mode must be 'continuous' or 'lockstep', "
                             f"got {mode!r}")
        self._count("flush.count")
        self._flushes += 1
        fid = self._flushes
        with obs_spans.span("bif.flush", flush=fid, queued=len(self._queue)):
            if mode == "continuous":
                return self._flush_continuous(fid)
            return self._flush_lockstep()

    # -- the continuous-batching scheduler --------------------------------

    def _flush_continuous(self, fid: int) -> List[BIFRequest]:
        cfg = self.solver.config
        if cfg.reorth or cfg.precondition != "none":
            # the stepping scheduler banks/merges plain lane states;
            # reorth bases and preconditioned transforms keep the legacy
            # lockstep path (per-request budgets/deadlines don't apply
            # there, but such configs never could use them before)
            return self._flush_lockstep()
        queue, self._queue = self._queue, []
        if not queue:
            return queue
        solver = self.solver
        n, p = self.op.n, self.max_batch
        dt = self._dtype
        max_iters = cfg.max_iters
        tracking = cfg.fn != "inv"          # matfun pool (per-lane fns)

        # host-side pool bookkeeping; device-side state in `state`
        us = np.zeros((p, n), dt)
        masks = np.ones((p, n), dt)
        ts = np.zeros((p,), dt)
        has_t = np.zeros((p,), bool)
        caps = np.zeros((p,), np.int32)   # 0 = vacated/dead lane (frozen)
        fnidx = np.full((p,), core_matfun.fn_index(cfg.fn), np.int32)
        slots: List[Optional[BIFRequest]] = [None] * p
        pending = list(queue)
        state = None
        lam_min = jnp.asarray(self.lam_min, dt)
        lam_max = jnp.asarray(self.lam_max, dt)

        try:
            rnd = 0
            while pending or any(r is not None for r in slots):
                with obs_spans.span("bif.round", flush=fid,
                                    round=rnd) as round_span:
                    rnd += 1
                    # --- admit: backfill free lanes from the queue (FIFO) ---
                    with obs_spans.span("bif.admit"):
                        fresh = np.zeros((p,), bool)
                        warm = []
                        dirty = state is None
                        now = time.monotonic()
                        for i in range(p):
                            if slots[i] is not None:
                                continue
                            r = None
                            while pending:
                                cand = pending.pop(0)
                                if cand.deadline is not None \
                                        and now >= cand.deadline:
                                    # already expired at the door: retire
                                    # with ZERO pool rounds burned — no
                                    # lane, no banked state, results stay
                                    # cleared; FIFO order is preserved
                                    # because the queue list itself is
                                    # returned in submission order
                                    cand.certified = False
                                    cand.resolved = False
                                    cand.iterations = 0
                                    cand.state = None
                                    self._retire_obs(cand, now, expired=True)
                                    continue
                                r = cand
                                break
                            if r is None:
                                continue
                            slots[i] = r
                            r._obs_admit_t = now
                            self._observe("request.queue_wait_s",
                                          now - getattr(r, "_obs_submit_t",
                                                        now))
                            m = np.ones((n,), dt) if r.mask is None \
                                else np.asarray(r.mask, dt)
                            masks[i] = m
                            # restrict the query to the mask: Masked is
                            # only the true submatrix system for u
                            # supported on it (Sec. 3.2)
                            us[i] = np.asarray(r.u, dt) * m
                            ts[i] = 0.0 if r.t is None else r.t
                            has_t[i] = r.t is not None
                            fnidx[i] = core_matfun.fn_index(
                                cfg.fn if r.fn is None else r.fn)
                            budget = max_iters if r.max_iters is None \
                                else max(int(r.max_iters), 0)
                            if r.state is not None:
                                # warm admission: resume the banked state
                                warm.append((i, (r.state.st,
                                                 r.state.coeffs)))
                                caps[i] = min(int(r.state.it) + budget,
                                              max_iters)
                            else:
                                fresh[i] = True
                                caps[i] = min(budget, max_iters)
                            dirty = True
                    if all(r is None for r in slots):
                        # every queued request expired at admission —
                        # there is nothing to step (a pool round here
                        # would burn chunk_iters x pool work on dead lanes)
                        break
                    if dirty:
                        with obs_spans.span("bif.init"):
                            if state is None or fresh.any():
                                # fresh lanes seed from a POOL-SHAPED init
                                # on purpose: per-lane (1, N) inits would
                                # be cheaper (~1 pool matvec per backfill
                                # round) but change the matvec shape, and
                                # gemv-vs-gemm rounding noise can flip
                                # marginal iteration counts vs the
                                # lockstep baseline (the Sec. 6.1 caveat)
                                state = _pool_admit_run(
                                    solver, self.op,
                                    None if state is None else state.st,
                                    None if state is None else state.coeffs,
                                    jnp.asarray(us), jnp.asarray(masks),
                                    jnp.asarray(fresh),
                                    jnp.asarray(fnidx) if tracking else None,
                                    lam_min, lam_max, mesh=self.mesh)
                            else:
                                # warm-only round: every admitted lane
                                # scatters a banked state in, so skip the
                                # pool init matvec and just rebind the
                                # masks on the pool operator
                                state = state._replace(
                                    op=dataclasses.replace(
                                        state.op,
                                        mask=jnp.asarray(masks, dt)))
                        if warm:
                            with obs_spans.span("bif.scatter"):
                                for i, lane_sc in warm:
                                    st_new, coeffs_new = _pool_scatter_run(
                                        (state.st, state.coeffs), lane_sc,
                                        jnp.asarray(i))
                                    state = state._replace(
                                        st=st_new, coeffs=coeffs_new)

                    # --- one decision round over the whole pool ---
                    occupied = sum(1 for s in slots if s is not None)
                    round_span.set_metadata(occupied=occupied)
                    self._count("flush.rounds")
                    self._observe("pool.occupancy", occupied / p)
                    with obs_spans.span("bif.step"):
                        state, lo, hi, res, dec, done, its = self._step(
                            state, jnp.asarray(ts), jnp.asarray(has_t),
                            jnp.asarray(caps))
                    # the host waits here for the round's device work
                    with obs_spans.span("bif.readback"):
                        lo_h, hi_h = np.asarray(lo), np.asarray(hi)
                        res_h, dec_h = np.asarray(res), np.asarray(dec)
                        done_h, it_h = np.asarray(done), np.asarray(its)
                    now = time.monotonic()

                    # --- retire: resolved lanes + expired budgets and
                    # deadlines ---
                    with obs_spans.span("bif.retire"):
                        if self.convergence_log is not None:
                            # host-side copies the retire loop reads
                            # anyway — logging cannot perturb the round
                            self.convergence_log.record(lo_h, hi_h, it_h)
                        for i in range(p):
                            r = slots[i]
                            if r is None:
                                continue
                            resolved = bool(res_h[i]) or bool(done_h[i])
                            capped = int(it_h[i]) >= min(int(caps[i]),
                                                         max_iters)
                            timed_out = r.deadline is not None \
                                and now >= r.deadline
                            if not (resolved or capped or timed_out):
                                continue
                            r.lower, r.upper = float(lo_h[i]), float(hi_h[i])
                            r.decision = bool(dec_h[i]) \
                                if r.t is not None else None
                            r.certified = bool(res_h[i])
                            r.resolved = resolved
                            r.iterations = int(it_h[i])
                            if not resolved and int(it_h[i]) < max_iters:
                                # interrupted with headroom left: bank a
                                # per-lane QuadState so resubmission
                                # resumes bit-exactly (plus the premasked
                                # query, so submit() can reject a mutated
                                # u/mask at the door)
                                r.state = QuadState(
                                    op=dataclasses.replace(
                                        state.op, mask=state.op.mask[i]),
                                    st=jax.tree.map(lambda l: l[i],
                                                    state.st),
                                    lam_min=state.lam_min,
                                    lam_max=state.lam_max,
                                    basis=None, step=state.step,
                                    coeffs=None if state.coeffs is None
                                    else jax.tree.map(lambda l: l[i],
                                                      state.coeffs))
                                r._banked_query = us[i].copy()
                            else:
                                r.state = None
                            self._retire_obs(
                                r, now, expired=timed_out and not resolved)
                            slots[i] = None
                            caps[i] = 0  # frozen until backfill
        except Exception as e:
            # In-flight requests carry the error and are dropped (a
            # poison request must not wedge everything behind it); the
            # unadmitted tail stays queued IN ORDER; already-retired
            # requests keep their results.
            for r in slots:
                if r is not None:
                    r.error = e
                    self._count("requests.errored")
            self._queue = pending + self._queue
            raise
        return queue

    # -- the legacy lockstep flush (benchmark baseline) --------------------

    def _flush_lockstep(self) -> List[BIFRequest]:
        cfg_fn = self.solver.config.fn
        for r in self._queue:
            if r.fn is not None and r.fn != cfg_fn:
                # lockstep chunks run ONE solve_batch under the solver's
                # static fn; per-lane mixing is a continuous-pool feature
                raise ValueError(
                    f"flush(mode='lockstep') serves the solver's "
                    f"fn={cfg_fn!r} only (got a fn={r.fn!r} request); "
                    f"mixed-fn traffic needs mode='continuous'")
        queue, self._queue = self._queue, []
        n, b = self.op.n, self.max_batch
        for start in range(0, len(queue), b):
            chunk = queue[start:start + b]
            try:
                with obs_spans.span("bif.admit"):
                    now = time.monotonic()
                    self._count("flush.rounds")
                    self._observe("pool.occupancy", len(chunk) / b)
                    for r in chunk:
                        r._obs_admit_t = now
                        self._observe("request.queue_wait_s",
                                      now - getattr(r, "_obs_submit_t", now))
                    us = np.zeros((b, n), self._dtype)
                    masks = np.ones((b, n), self._dtype)
                    ts = np.zeros((b,), self._dtype)
                    has_t = np.zeros((b,), bool)
                    for i, r in enumerate(chunk):
                        if r.mask is not None:
                            masks[i] = r.mask
                        # restrict the query to the mask: Masked is only
                        # the true submatrix system for u supported on it
                        # (Sec. 3.2)
                        us[i] = np.asarray(r.u) * masks[i]
                        if r.t is not None:
                            ts[i] = r.t
                            has_t[i] = True
                with obs_spans.span("bif.step"):
                    out = self._run(jnp.asarray(us), jnp.asarray(masks),
                                    jnp.asarray(ts), jnp.asarray(has_t))
                with obs_spans.span("bif.readback"):
                    lo, hi, dec, cert, it, conv = (np.asarray(x)
                                                   for x in out)
            except Exception as e:
                # keep the un-served tail, but NOT the failing chunk: a
                # poison request requeued at the head would re-raise on
                # every flush and wedge everything behind it. The chunk's
                # requests carry the error so callers can tell "dropped
                # by a failed flush" from "never flushed" and resubmit
                # the innocent ones after a transient driver failure.
                for r in chunk:
                    r.error = e
                    self._count("requests.errored")
                self._queue = queue[start + len(chunk):] + self._queue
                raise
            now = time.monotonic()
            with obs_spans.span("bif.retire"):
                for i, r in enumerate(chunk):
                    r.lower, r.upper = float(lo[i]), float(hi[i])
                    r.decision = bool(dec[i]) if r.t is not None else None
                    r.certified = bool(cert[i])
                    r.iterations = int(it[i])
                    # same rule as the scheduler: resolved by the decision
                    # OR by Krylov exhaustion (the bracket is then exact)
                    r.resolved = bool(conv[i])
                    self._retire_obs(r, now)
        return queue
