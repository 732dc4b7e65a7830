"""Host time of one engine scheduler round: the mean over the window's
``bif.round`` spans of the round's duration less the part its
``bif.readback`` (the host's wait for the round's device work, then the
copy) covers (program spans in the profiler trace)."""
from chipbench import program_trace


def read(run):
    pt = program_trace.load(run) if run.kind == "serve" else None
    host_s = pt.round_host_s() if pt is not None else None
    return None if host_s is None else 1e3 * host_s
