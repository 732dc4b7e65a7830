"""Traces of jitted functions per job: the ``retrace.*`` markers in the
window (``repro.obs.registry``, one at each trace) over the window's
jobs; a count. A program whose trace holds no ``trace.*`` span writes
no markers either, and reads None."""
from chipbench import program_trace


def read(run):
    pt = program_trace.load(run) if run.kind == "job" else None
    if pt is None or not pt.has("trace.") or not run.records:
        return None
    return sum(pt.markers().values()) / len(run.records)
