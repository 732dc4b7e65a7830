"""Device-sharded batched quadrature: lanes over the mesh (DESIGN.md Sec. 7).

The K candidate systems of the batched Alg.-2 driver (solver.py,
``solve_batch``) are embarrassingly parallel in everything but the
decision rule: each lane's Lanczos recurrence touches only its own query
vector, so the per-iteration (K, N) stacked matvec splits cleanly into
(K/D, N) shards, one per device of a 1-D ``lanes`` mesh
(``launch.mesh.make_lane_mesh``). This module runs exactly that split
via ``shard_map``:

  * stacked queries / masks / thresholds are sharded on their leading
    lane axis (the ``lanes`` logical axis of ``sharding.api.lane_plan``;
    ``operators.lane_specs`` derives the per-leaf specs, with shared
    operator leaves — the base matrix — replicated on every device);
  * the ONE retrospective loop runs per device on its lane shard, with
    lanes frozen bit-exactly as they resolve, just like the
    single-device driver;
  * the loop is *round-cadenced* (``SolverConfig.decide_every = R``,
    DESIGN.md Sec. 11): each ``lax.while_loop`` trip runs R shard-local
    steps (zero collectives — within-round freezing uses only per-lane
    local conditions) and then evaluates the decision rule once, at the
    round boundary;
  * the round boundary pays exactly ONE collective: the per-lane
    brackets and the lane's local can-continue flag travel together in a
    single packed ``all_gather`` (``_round_gather``). Every device then
    computes the SAME global resolution flags — cross-lane rules like
    the ``judge_argmax`` race see every lane — AND the same global
    continue flag from the gathered data, so while_loop trip counts stay
    lockstep with no separate ``psum``: a pool whose lanes all resolved
    exits after one last gather instead of paying a collective pair per
    iteration.

K that does not divide the device count is padded with zero-query lanes,
which ``gql_init`` marks done at iteration one (the same dummy-lane rule
the serving engine uses); padded results are sliced off before returning.

Per-lane outcomes (decisions, iteration counts, certification) are
exactly those of the single-device ``solve_batch``; bracket floats are
bit-exact for ``SparseCOO`` and agree to ~1e-12 on gemm-backed operators
(XLA reduces gemms of different shapes in different orders — the same
caveat as batched-vs-single-lane, DESIGN.md Sec. 6.1).

Everything here runs on CPU under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for local testing
(tests/test_sharded.py) — the mesh does not care that the devices are
virtual.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import block as _block
from . import gql as _gql
from . import matfun as _matfun
from . import operators as _ops
from .solver import ArgmaxResult, BIFSolver, JudgeResult, QuadState, \
    SolveResult, _argmax_race, _argmax_scores

Array = jax.Array

# it_cap sentinel when no per-lane budget applies: `st.it < cap` is then
# always True and the needs_more rule reduces to the unbudgeted one.
_NO_CAP = jnp.iinfo(jnp.int32).max

# QuadState threading contract (quadlint QL001): per-lane fields the
# sharded driver does NOT thread. `basis` (reorthogonalization storage)
# is rejected up front by _check_state — reorth is unsupported sharded —
# so _drive_sharded legitimately never carries or freezes it.
SHARDED_STATE_EXCLUDED = ("basis",)


def _pad_lane_arg(a, k: int, kp: int):
    """Zero-pad the leading lane dim of a (K, ...) decide argument to Kp;
    scalars and non-lane arrays pass through untouched."""
    a = jnp.asarray(a)
    if kp == k or a.ndim == 0 or a.shape[0] != k:
        return a
    return jnp.pad(a, [(0, kp - k)] + [(0, 0)] * (a.ndim - 1))


def _pad_lane_lam(lam, k: int, kp: int):
    """Pad a per-lane spectrum bound to the padded lane count with ones
    (a harmless positive interval for the done-at-init dummy lanes);
    scalar bounds pass through untouched."""
    lam = jnp.asarray(lam)
    if kp == k or lam.ndim == 0:
        return lam
    return jnp.pad(lam, (0, kp - k), constant_values=1.0)


def _pad_lane_op(op, k: int, kp: int, axis: str):
    """Zero-pad the lane axis of every lane-stacked operator leaf (stacked
    masks / stacked-op pytrees) to the padded lane count. Zeros keep the
    dead lanes' matvecs finite (A_pad @ x = 0), which is all the
    done-at-init padding lanes need."""
    if kp == k:
        return op
    specs = _ops.lane_specs(op, axis)

    def pad(leaf, spec):
        if len(spec) and spec[0] == axis:
            return jnp.pad(leaf,
                           [(0, kp - k)] + [(0, 0)] * (leaf.ndim - 1))
        return leaf

    return jax.tree.map(pad, op, specs)


# ---------------------------------------------------------------------------
# The resumable sharded runtime (DESIGN.md Sec. 8): the QuadState of
# core/solver.py with its per-lane leaves sharded over the mesh.
# init_state_sharded / step_n_sharded / resume_sharded / finalize_sharded
# mirror the single-device stepping API; solve_batch_sharded (and every
# judge on top of it) is rebuilt on them.


def _lam_specs(lam_min, lam_max, axis: str):
    """Per-lane spectrum bounds (estimating modes return (K,) arrays from
    prepare()) shard with the lanes; scalar bounds replicate."""
    return tuple(P(axis) if jnp.ndim(lam) else P()
                 for lam in (lam_min, lam_max))


def _check_state(solver: BIFSolver, state: QuadState, what: str):
    if solver.config.reorth or state.basis is not None:
        raise NotImplementedError(
            f"reorth is not implemented for the sharded driver; "
            f"{what} requires reorth=False")
    if state.st.it.ndim != 1:
        raise ValueError(
            f"{what} wants a (K,)-lane state, got lane shape "
            f"{state.st.it.shape}")


def init_state_sharded(solver: BIFSolver, op, u: Array, *, mesh,
                       axis: str = "lanes", lam_min=None, lam_max=None,
                       probe=None) -> QuadState:
    """Prepare + iteration 1 with the K lanes sharded over ``mesh``.

    Spectrum estimation / preconditioning run globally before sharding
    (so resolved intervals match the single-device path bit-for-bit);
    ``gql_init`` then runs per-device on each lane shard, exactly like
    the drive's steps. K that does not divide the device count pads with
    zero-query done-at-init lanes (Sec. 7.3); the returned state is the
    PADDED (K',) state — ``finalize_sharded(..., nlanes=K)`` slices back.

    With ``config.block_size = b > 1`` the queries are (K, b, N)
    row-stacked probe blocks and each lane carries a
    :class:`block.BlockState` (DESIGN.md Sec. 13) — same padding rule
    (zero blocks deflate fully at the init QR, so padding lanes are done
    at iteration one), same per-leaf lane sharding.
    """
    cfg = solver.config
    if cfg.reorth:
        raise NotImplementedError(
            "reorth is not implemented for the sharded driver; "
            "init_state_sharded requires reorth=False")
    u = jnp.asarray(u)
    if cfg.block_size > 1:
        if u.ndim != 3 or u.shape[-2] != cfg.block_size:
            raise ValueError(
                f"init_state_sharded with block_size={cfg.block_size} "
                f"wants (K, b, N) stacked probe blocks with "
                f"b={cfg.block_size}, got shape {u.shape}")
    elif u.ndim != 2:
        raise ValueError(
            f"init_state_sharded wants (K, N) stacked queries, got shape "
            f"{u.shape}")
    op, u, lam_min, lam_max = solver.prepare(op, u, lam_min, lam_max, probe)
    lam_min = jnp.asarray(lam_min)
    lam_max = jnp.asarray(lam_max)
    if cfg.block_size > 1:
        # estimating spectrum modes return per-probe bounds: union over
        # the lane's block slots (same rule as the single-device init)
        if lam_min.ndim > 1:
            lam_min = jnp.min(lam_min, axis=-1)
        if lam_max.ndim > 1:
            lam_max = jnp.max(lam_max, axis=-1)
    k = u.shape[0]
    ndev = mesh.shape[axis]
    kp = -(-k // ndev) * ndev
    if kp != k:
        u = jnp.pad(u, [(0, kp - k)] + [(0, 0)] * (u.ndim - 1))
        op = _pad_lane_op(op, k, kp, axis)
        lam_min = _pad_lane_lam(lam_min, k, kp)
        lam_max = _pad_lane_lam(lam_max, k, kp)

    # the init is lane-local and needs no collective: run it on the
    # global lanes exactly as the single-device init does (bit for bit),
    # then shard its lanes
    if cfg.block_size > 1:
        st = _block.block_init(op, u, lam_min, lam_max, cfg.fn,
                               cfg.max_iters)
    else:
        st = _gql.gql_init(op, u, lam_min, lam_max)
    st = jax.device_put(st, NamedSharding(mesh, P(axis)))
    # the coefficient history is elementwise over lanes; allocated
    # globally (like spectrum resolution) and sharded by the next drive.
    # Block states carry fn in the state itself (fnidx) — no coeffs.
    coeffs = _matfun.init_coeffs(st, cfg.fn, cfg.max_iters) \
        if cfg.fn != "inv" and cfg.block_size == 1 else None
    return QuadState(op=op, st=st, lam_min=lam_min, lam_max=lam_max,
                     basis=None, step=jnp.zeros((), jnp.int32),
                     coeffs=coeffs)


def _round_gather(x, axis: str):
    """The cadence collective: the ONE ``all_gather`` a decision round is
    allowed to pay.  Packs per-lane round-boundary scalars (brackets plus
    the folded can-continue flag) into a single tiled gather so the
    sharded drive needs no separate ``psum`` for its loop-lockstep
    continue flag — every device derives it from the gathered data.
    """
    return jax.lax.all_gather(x, axis, axis=0, tiled=True)  # quadlint: disable=QL007 -- the cadence helper itself: the single sanctioned per-round collective


def _drive_sharded(solver: BIFSolver, state: QuadState, decide,
                   decide_args, it_cap, mesh, axis: str,
                   n: int | None):
    """Advance the sharded state: ``n`` bounded steps (step_n) or to
    completion (``n=None``, resume).

    ``decide(lo, hi, *decide_args)`` sees the GLOBAL (K',) brackets and
    returns per-lane resolution flags; ``decide_args`` are replicated on
    every device, ``it_cap`` (per-lane iteration budgets) shards with
    the lanes.

    The loop is round-cadenced: each ``lax.while_loop`` trip runs
    ``R = solver.config.decide_every`` shard-local steps (collective-
    free; within-round freezing reuses the single-device local rule via
    ``BIFSolver._round_body``) and then pays exactly one collective —
    ``_round_gather`` of ``stack([lo, hi, can], -1)``.  Every device
    evaluates ``decide`` on the same gathered brackets and derives the
    same global continue flag ``any(can & ~resolved)``, so while_loop
    trip counts stay lockstep with no psum, and an all-resolved pool
    exits after one final gather instead of a collective pair per
    iteration.  ``n`` is quantised to whole rounds (``n // R``), exactly
    like the single-device ``step_n``, so sharded and single-device
    states stay round-aligned and bit-identical.
    """
    _check_state(solver, state, "the sharded stepping driver")
    cfg = solver.config
    r = cfg.decide_every
    stepfn = solver._stepper()
    kp = state.st.it.shape[0]
    kd = kp // mesh.shape[axis]
    if decide is None:
        def decide(lo, hi):  # noqa: F811 — tolerance rule, no extra args
            return solver.tolerance_resolved(lo, hi)
    rounds = None if n is None else n // r
    if rounds == 0:
        return state
    cap = jnp.full((kp,), _NO_CAP, jnp.int32) if it_cap is None \
        else jnp.broadcast_to(jnp.asarray(it_cap, jnp.int32), (kp,))

    def local_fn(op_loc, st_coeffs_loc, lmn, lmx, cap_loc, *dargs):
        st_loc, coeffs_loc = st_coeffs_loc
        idx = jax.lax.axis_index(axis)
        local_ok = solver._local_ok_fn(cap_loc)
        round_fn = solver._round_body(op_loc, lmn, lmx, stepfn, local_ok)

        def boundary(st, coeffs):
            # ONE collective per round: brackets and the local
            # can-continue flag travel together.  The gather's result
            # feeds only the next round's freeze masks — not the matvec
            # inputs — so the compiler is free to overlap it with the
            # first shard-local matvec of the following round.
            # (fn-aware brackets — the matfun eigensolve — run
            # shard-local; only the scalars travel)
            lo, hi = solver._bracket2(st, coeffs, lmn, lmx)
            can = local_ok(st, coeffs)
            packed = _round_gather(
                jnp.stack([lo, hi, can.astype(lo.dtype)], axis=-1), axis)
            res = decide(packed[..., 0], packed[..., 1], *dargs)
            nm_glob = packed[..., 2].astype(bool) & ~res
            nm = jax.lax.dynamic_slice_in_dim(nm_glob, idx * kd, kd)
            # global "any lane anywhere still needs work" — computed
            # identically on every device from the gathered flags, so
            # while_loop trip counts stay lockstep without a psum.
            return nm, jnp.any(nm_glob)

        nm0, cont0 = boundary(st_loc, coeffs_loc)

        def cond(carry):
            cont = carry[2]
            return cont if rounds is None else cont & (carry[3] < rounds)

        def body(carry):
            (st, coeffs), nm, _, taken = carry

            def run_round(sc):
                st1, _, coeffs1, _, _ = round_fn(
                    (sc[0], None, sc[1], jnp.zeros((), jnp.int32), nm))
                return st1, coeffs1

            # a device whose local lanes are ALL frozen skips its dead
            # shard-local matvecs for the round (the frozen substep is
            # the identity, so the branch is bit-exact); it still reaches
            # the boundary gather, keeping trip counts lockstep.
            st, coeffs = jax.lax.cond(jnp.any(nm), run_round,
                                      lambda sc: sc, (st, coeffs))
            nm1, cont1 = boundary(st, coeffs)
            return (st, coeffs), nm1, cont1, taken + 1

        (st, coeffs), _, _, taken = jax.lax.while_loop(
            cond, body,
            ((st_loc, coeffs_loc), nm0, cont0, jnp.zeros((), jnp.int32)))
        return st, coeffs, jnp.full((kd,), taken, jnp.int32)

    # called eagerly, shard_map compiles its body op by op; jitted, the
    # drive is one compile
    fn = jax.jit(jax.shard_map(  # quadlint: disable=QL003 -- the closure is per call; one compile per drive instead of one per op
        local_fn, mesh=mesh,
        in_specs=(_ops.lane_specs(state.op, axis),
                  jax.tree.map(lambda _: P(axis),
                               (state.st, state.coeffs)))
        + _lam_specs(state.lam_min, state.lam_max, axis)
        + (P(axis),) + tuple(P() for _ in decide_args),
        out_specs=P(axis), check_vma=False))
    st, coeffs, taken = fn(state.op, (state.st, state.coeffs),
                           state.lam_min, state.lam_max, cap, *decide_args)
    # basis-free states use `step` only as bookkeeping; rounds-taken is
    # replicated across devices, so its max IS the shared trip count,
    # and `step` advances by a whole round per trip — matching the
    # single-device round accounting exactly.
    return state._replace(st=st, coeffs=coeffs,
                          step=state.step + r * jnp.max(taken))


def step_n_sharded(solver: BIFSolver, state: QuadState, n: int,
                   decide=None, *, decide_args=(), it_cap=None, mesh,
                   axis: str = "lanes") -> QuadState:
    """Advance a sharded :class:`QuadState` by at most ``n`` iterations —
    the sharded twin of ``BIFSolver.step_n`` (same freezing rule, so
    resume-after-step_n is bit-exact with the uninterrupted drive).

    Like the single-device ``step_n``, ``n`` is quantised down to whole
    decision rounds: with ``decide_every = R`` this advances
    ``(n // R) * R`` iterations, a no-op when ``n < R``."""
    if n < 0:
        raise ValueError(f"n must be >= 0, got {n}")
    if n == 0:
        return state
    return _drive_sharded(solver, state, decide, decide_args, it_cap,
                          mesh, axis, n)


def resume_sharded(solver: BIFSolver, state: QuadState, decide=None, *,
                   decide_args=(), it_cap=None, mesh,
                   axis: str = "lanes") -> QuadState:
    """Run a sharded :class:`QuadState` to completion — the sharded twin
    of ``BIFSolver.resume``."""
    return _drive_sharded(solver, state, decide, decide_args, it_cap,
                          mesh, axis, None)


def finalize_sharded(solver: BIFSolver, state: QuadState, decide=None, *,
                     decide_args=(), nlanes: int | None = None
                     ) -> SolveResult:
    """Read a :class:`SolveResult` off a (partial or completed) sharded
    state, slicing padding lanes back to ``nlanes``. ``certified``
    re-evaluates ``decide`` on the full padded brackets first (cross-lane
    rules like the argmax race see every lane), then slices."""
    st = state.st
    lo, hi, loose_lo, loose_hi = solver._bracket4(
        st, state.coeffs, state.lam_min, state.lam_max)
    if decide is None:
        certified = solver.tolerance_resolved(lo, hi)
    else:
        certified = decide(lo, hi, *decide_args)
    k = lo.shape[0] if nlanes is None else nlanes
    certified = certified[:k]
    return SolveResult(
        lower=lo[:k], upper=hi[:k],
        gauss_lower=loose_lo[:k],
        lobatto_upper=loose_hi[:k],
        iterations=st.it[:k], converged=st.done[:k] | certified,
        certified=certified, state=state)


def solve_batch_sharded(solver: BIFSolver, op, u: Array, decide=None, *,
                        mesh, axis: str = "lanes", lam_min=None,
                        lam_max=None, probe=None,
                        decide_args=()) -> SolveResult:
    """``BIFSolver.solve_batch`` with the K lanes sharded over ``mesh``.

    ``u`` is (K, N) — exactly one lane axis (the sharded path does not
    take extra leading batch dims). ``decide`` receives the global (K',)
    brackets (K' = K rounded up to a device multiple; padding lanes
    carry zero queries and resolve at iteration one) plus
    ``decide_args``, each of which is zero-padded on a leading lane dim
    and replicated across devices. ``decide=None`` brackets each lane to
    the solver's rtol/atol. Spectrum estimation / preconditioning run
    globally before sharding, so resolved intervals match the
    single-device path bit-for-bit.

    Sugar for ``finalize_sharded(resume_sharded(init_state_sharded(...)))``
    — callers that pause/checkpoint/resume use the stepping API directly.
    Returns a :class:`SolveResult` over the original K lanes whose
    ``state`` is the final PADDED :class:`QuadState` (resume it with
    ``resume_sharded``; per-lane GQL leaves stay sharded on their
    devices).
    """
    u = jnp.asarray(u)
    b = solver.config.block_size
    if b > 1:
        if u.ndim != 3 or u.shape[-2] != b:
            raise ValueError(
                f"solve_batch_sharded with block_size={b} wants (K, b, N) "
                f"stacked probe blocks with b={b}, got shape {u.shape}")
    elif u.ndim != 2:
        raise ValueError(
            f"solve_batch_sharded wants (K, N) stacked queries, got shape "
            f"{u.shape}")
    k = u.shape[0]
    state = init_state_sharded(solver, op, u, mesh=mesh, axis=axis,
                               lam_min=lam_min, lam_max=lam_max,
                               probe=probe)
    kp = state.st.it.shape[0]
    args = tuple(_pad_lane_arg(a, k, kp) for a in decide_args) \
        if decide is not None else ()
    state = resume_sharded(solver, state, decide, decide_args=args,
                           mesh=mesh, axis=axis)
    return finalize_sharded(solver, state, decide, decide_args=args,
                            nlanes=k)


def judge_batch_sharded(solver: BIFSolver, op, u: Array, t: Array, *,
                        mesh, axis: str = "lanes", lam_min=None,
                        lam_max=None, probe=None) -> JudgeResult:
    """K threshold judges (Alg. 4) sharded over the lane mesh. With
    ``block_size = b > 1`` the lanes are (K, b, N) probe blocks and the
    thresholds apply to the per-lane ``tr B^T f(A) B`` brackets."""
    u = jnp.asarray(u)
    b = solver.config.block_size
    if b > 1:
        if u.ndim != 3 or u.shape[-2] != b:
            raise ValueError(
                f"judge_batch_sharded with block_size={b} wants (K, b, N) "
                f"stacked probe blocks with b={b}, got shape {u.shape}")
    elif u.ndim != 2:
        raise ValueError(
            f"judge_batch_sharded wants (K, N) stacked queries, got shape "
            f"{u.shape}")
    lane_shape = u.shape[:-2] if b > 1 else u.shape[:-1]
    ts = jnp.broadcast_to(jnp.asarray(t), lane_shape)

    def decide(lo, hi, ts):
        return (ts < lo) | (ts >= hi)

    res = solve_batch_sharded(solver, op, u, decide, mesh=mesh, axis=axis,
                              lam_min=lam_min, lam_max=lam_max, probe=probe,
                              decide_args=(ts,))
    decision = BIFSolver.threshold_decision(ts, res.lower, res.upper)
    return JudgeResult(decision=decision, certified=res.certified,
                       iterations=res.iterations)


def judge_argmax_sharded(solver: BIFSolver, op, u: Array, *, mesh,
                         axis: str = "lanes", shift=None, scale=None,
                         valid=None, prior_upper=None, prior_lower=None,
                         lam_min=None, lam_max=None,
                         probe=None) -> ArgmaxResult:
    """Certified argmax race over K sharded lanes.

    The race itself is the cross-device reduction of the tentpole: each
    iteration every device gathers ALL lane brackets, computes the same
    dominance / winner flags as the single-device race (best lower bound
    = a max over the full lane set; the winner's certificate = its lower
    bound clearing every rival's upper bound), and freezes its local
    dominated lanes. Padding lanes ride along with ``valid=False`` and
    the usual -1e30 score sentinel, so they can neither win nor keep the
    loop alive.
    """
    u = jnp.asarray(u)
    bsz = solver.config.block_size
    if bsz > 1:
        if u.ndim != 3 or u.shape[-2] != bsz:
            raise ValueError(
                f"judge_argmax_sharded with block_size={bsz} wants "
                f"(K, b, N) stacked probe blocks with b={bsz}, got shape "
                f"{u.shape}")
    elif u.ndim != 2:
        raise ValueError(f"judge_argmax_sharded wants (K, N) stacked "
                         f"queries, got shape {u.shape}")
    k = u.shape[0]
    shift = jnp.zeros((), u.dtype) if shift is None else \
        jnp.asarray(shift, u.dtype)
    scale = jnp.ones((), u.dtype) if scale is None else \
        jnp.asarray(scale, u.dtype)
    shift_k = jnp.broadcast_to(shift, (k,))
    scale_k = jnp.broadcast_to(scale, (k,))
    valid_k = jnp.ones((k,), bool) if valid is None else \
        jnp.broadcast_to(jnp.asarray(valid, bool), (k,))
    ndev = mesh.shape[axis]
    kp = -(-k // ndev) * ndev
    # padding lanes enter the race invalid: score sentinel -1e30, done at
    # iteration one — they change neither dominance nor the certificate
    valid_p = jnp.pad(valid_k, (0, kp - k)) if kp != k else valid_k
    prior_k = None if prior_upper is None else \
        jnp.broadcast_to(jnp.asarray(prior_upper, u.dtype), (k,))
    prior_lo_k = None if prior_lower is None else \
        jnp.broadcast_to(jnp.asarray(prior_lower, u.dtype), (k,))

    if prior_k is None and prior_lo_k is None:
        def decide(lo, hi, shift, scale, valid):
            dominated, winner = _argmax_race(
                *_argmax_scores(lo, hi, shift, scale, valid))
            return dominated | winner

        dargs = (shift_k, scale_k, valid_p)
    else:
        # either prior alone rides as a no-op sentinel (+/-inf clamps to
        # the lane's own bracket); padding lanes are pinned by `valid`
        # AFTER the prior clamps, so zero-padded prior args are harmless
        pu_k = jnp.full((k,), jnp.inf, u.dtype) if prior_k is None \
            else prior_k
        pl_k = jnp.full((k,), -jnp.inf, u.dtype) if prior_lo_k is None \
            else prior_lo_k

        def decide(lo, hi, shift, scale, valid, pu, pl):
            dominated, winner = _argmax_race(
                *_argmax_scores(lo, hi, shift, scale, valid, pu, pl))
            return dominated | winner

        dargs = (shift_k, scale_k, valid_p, pu_k, pl_k)

    res = solve_batch_sharded(
        solver, op, u, decide, mesh=mesh, axis=axis, lam_min=lam_min,
        lam_max=lam_max, probe=probe, decide_args=dargs)
    slo, shi = _argmax_scores(res.lower, res.upper, shift_k, scale_k,
                              valid_k, prior_k, prior_lo_k)
    _, winner = _argmax_race(slo, shi)
    certified = jnp.any(winner, axis=-1)
    mid = 0.5 * (slo + shi)
    index = jnp.where(certified, jnp.argmax(winner, axis=-1),
                      jnp.argmax(mid, axis=-1)).astype(jnp.int32)
    return ArgmaxResult(index=index, certified=certified,
                        iterations=res.iterations, lower=slo, upper=shi)


def judge_kdpp_swap_batch_sharded(solver: BIFSolver, op, u: Array,
                                  v: Array, t: Array, p: Array, *, mesh,
                                  axis: str = "lanes", lam_min=None,
                                  lam_max=None) -> JudgeResult:
    """Alg. 7 with the two systems as two sharded lanes (the remaining
    devices carry padding lanes; with D > 2 devices this trades idle
    devices for API uniformity — worth it only inside a larger sharded
    pipeline such as a mesh-resident k-DPP chain)."""
    if solver.config.block_size > 1:
        raise NotImplementedError(
            "judge_kdpp_swap_batch_sharded stacks two scalar query "
            "systems; block_size > 1 brackets tr B^T f(A) B and has no "
            "swap-judge semantics — use block_size=1")
    uv = jnp.stack([jnp.asarray(u), jnp.asarray(v)], axis=0)
    t = jnp.asarray(t)
    p = jnp.asarray(p)

    def bounds(lo, hi):
        return (p * lo[..., 1] - hi[..., 0],
                p * hi[..., 1] - lo[..., 0])

    def decide(lo, hi, t, p):
        blo, bhi = bounds(lo, hi)
        done = (t < blo) | (t >= bhi)
        return jnp.broadcast_to(done[..., None], lo.shape)

    res = solve_batch_sharded(solver, op, uv, decide, mesh=mesh, axis=axis,
                              lam_min=lam_min, lam_max=lam_max,
                              decide_args=(t, p))
    blo, bhi = bounds(res.lower, res.upper)
    decision = BIFSolver.threshold_decision(t, blo, bhi)
    return JudgeResult(decision=decision,
                       certified=(t < blo) | (t >= bhi),
                       iterations=jnp.sum(res.iterations, axis=-1,
                                          dtype=res.iterations.dtype))


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class ShardedBIFSolver:
    """A :class:`BIFSolver` bound to a lane mesh.

        mesh = make_lane_mesh()                     # all local devices
        sh = ShardedBIFSolver(BIFSolver.create(max_iters=64), mesh)
        res = sh.judge_argmax(op, us, shift=d, scale=-1.0)

    Static like the solver itself (``Mesh`` is hashable), so it crosses
    jit boundaries and can be closure-captured freely.
    """
    solver: BIFSolver
    mesh: object
    axis: str = "lanes"

    def solve_batch(self, op, u: Array, decide=None, **kw) -> SolveResult:
        return solve_batch_sharded(self.solver, op, u, decide,
                                   mesh=self.mesh, axis=self.axis, **kw)

    def judge_batch(self, op, u: Array, t: Array, **kw) -> JudgeResult:
        return judge_batch_sharded(self.solver, op, u, t, mesh=self.mesh,
                                   axis=self.axis, **kw)

    def judge_argmax(self, op, u: Array, **kw) -> ArgmaxResult:
        return judge_argmax_sharded(self.solver, op, u, mesh=self.mesh,
                                    axis=self.axis, **kw)

    def judge_kdpp_swap_batch(self, op, u: Array, v: Array, t: Array,
                              p: Array, **kw) -> JudgeResult:
        return judge_kdpp_swap_batch_sharded(
            self.solver, op, u, v, t, p, mesh=self.mesh, axis=self.axis,
            **kw)
