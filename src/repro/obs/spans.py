"""Program spans on the profiler's clock.

``with span("bif.round", flush=3, round=7):`` enters a
``jax.profiler.TraceAnnotation``: while a profiler session is active
(``jax.profiler.trace`` / ``start_trace``) the span lands in the trace
as a host event on the same clock as the device's operations, with its
keyword arguments as event stats; with no session it records nothing
and costs one annotation object. The profiler session is the switch:
there is no flag, no buffer and no exporter here.

Device time needs no help: the trace shows each operation's interval on
the device directly, so a span never syncs on the values it launched.
Names carry the layer as a prefix: ``bif.`` for the serving engine's
flush and scheduler rounds, ``trace.`` for the phases of a stochastic
trace job; ``obs.registry.count`` adds zero-length ``retrace.<name>``
markers. Keep arguments to small ints and short strings.

Host-side only (quadlint QL008): under a jit trace a span would mark
trace time once per compile, not run time.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **args) -> TraceAnnotation:
    """``with span("bif.flush", flush=k, queued=n): ...``"""
    return TraceAnnotation(name, **args)
