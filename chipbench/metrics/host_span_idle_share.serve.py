"""Share of the traced window in which the device idled while the host
was in the engine's own round phases (``bif.admit``, ``bif.init``,
``bif.scatter``, ``bif.step``, ``bif.retire``; program spans in the
profiler trace). The wait in ``bif.readback`` is left out."""
from chipbench import program_trace

PHASES = ("bif.admit", "bif.init", "bif.scatter", "bif.step", "bif.retire")


def read(run):
    pt = program_trace.load(run) if run.kind == "serve" else None
    if pt is None or not pt.has("bif."):
        return None
    return 100.0 * pt.idle_in(PHASES) / pt.window_s
