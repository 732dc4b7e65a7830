"""Share of the traced window in which the device idled while the host
was in a trace job's own phases (``trace.probes``, ``trace.solve``,
``trace.reduce``; program spans in the profiler trace). The wait in
``trace.readback`` is left out."""
from chipbench import program_trace

PHASES = ("trace.probes", "trace.solve", "trace.reduce")


def read(run):
    pt = program_trace.load(run) if run.kind == "job" else None
    if pt is None or not pt.has("trace."):
        return None
    return 100.0 * pt.idle_in(PHASES) / pt.window_s
