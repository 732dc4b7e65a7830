"""Runtime observability (DESIGN.md Sec. 14).

Host-side-only telemetry for the quadrature serving stack:

``obs.metrics``
    process-local counters / gauges / log-scale histograms with exact
    p50/p90/p99 readout (stdlib-only).
``obs.spans``
    program spans as ``jax.profiler.TraceAnnotation`` events: one
    clock, the profiler's, shared with the device's operations. An
    active profiler session is the only switch; a span never syncs on
    device work (the trace shows device time directly). Names:
    ``bif.*`` (engine flush, rounds and their phases), ``trace.*``
    (stochastic trace job phases). On the device, ``jax.named_scope``
    tags the operator pass (``op.matvec``, core/operators.py) and the
    quadrature eigensolve (``quad.eigh``, core/matfun.py).
``obs.registry``
    the central retrace-count registry every module-level jit in
    ``serve/`` reports through (``registry.count("name")`` at trace
    time; ``retrace_counts()`` for one snapshot); each count also marks
    the profiler's timeline with a zero-length ``retrace.<name>``.
``obs.health``
    online convergence-health checks on recorded bracket gaps against
    the Thm. 4.2 contraction rate (the reorth-off failure mode).

THE CONTRACT (enforced by quadlint QL008): ``obs.metrics`` and
``obs.spans`` are written from HOST code only — never inside
jit/while_loop/scan/shard_map scopes. Telemetry therefore cannot change
what gets compiled: solver brackets, decisions, iteration counts, and
engine flush order are bit-identical with observability on or off and
with the profiler on or off (pinned by tests/test_obs.py, single-device
and sharded). The one sanctioned trace-time side effect is
``obs.registry.count`` — a compile-count probe, same role as the legacy
``*_TRACES[0] += 1``.
"""
from . import health, metrics, registry, spans
from .health import ContractionMonitor, ConvergenceLog, rate_bound
from .metrics import MetricsRegistry
from .registry import retrace_counts
from .spans import span


def enable() -> None:
    """Turn on metrics recording (spans follow the profiler session)."""
    metrics.set_enabled(True)


def disable() -> None:
    """Turn off metrics recording. Never affects ``obs.registry`` —
    retrace accounting is a correctness signal, not telemetry — nor
    spans, which record exactly while a profiler session is active."""
    metrics.set_enabled(False)


__all__ = [
    "ContractionMonitor",
    "ConvergenceLog",
    "MetricsRegistry",
    "disable",
    "enable",
    "health",
    "metrics",
    "rate_bound",
    "registry",
    "retrace_counts",
    "span",
    "spans",
]
