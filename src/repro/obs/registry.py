"""Central retrace-count registry.

Every module-level jit in ``serve/`` reports compiles through one
``count("name")`` call placed INSIDE the jitted function body: python
side effects run at trace time only, so the count increments once per
fresh compile (jit cache miss) and never on cache hits. This is the one
sanctioned trace-time side effect in the tree — quadlint QL003 requires
it on serve/ module-level jits, and QL008 (which bans obs.metrics /
obs.spans in traced scopes) explicitly allows it.

Each count also leaves a zero-length ``retrace.<name>`` annotation on
the profiler's timeline (recorded only while a profiler session is
active), so a trace shows every retrace at the instant it happened.

Deliberately NOT gated by ``obs.metrics.set_enabled``: retrace counts
are a correctness/perf-contract signal (tests pin padding-bucket reuse
with them), not optional telemetry.
"""
from __future__ import annotations

import threading

from jax.profiler import TraceAnnotation

_LOCK = threading.Lock()
_COUNTS: dict = {}


def count(name: str) -> int:
    """Record one (re)trace of ``name``; returns the new count."""
    with TraceAnnotation(f"retrace.{name}"):
        pass
    with _LOCK:
        c = _COUNTS.get(name, 0) + 1
        _COUNTS[name] = c
        return c


def value(name: str) -> int:
    """Current count for ``name`` (0 if never traced)."""
    return _COUNTS.get(name, 0)


def retrace_counts() -> dict:
    """One snapshot of every registered retrace counter."""
    with _LOCK:
        return dict(sorted(_COUNTS.items()))


def reset() -> None:
    with _LOCK:
        _COUNTS.clear()
