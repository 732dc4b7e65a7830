"""Share of the traced window in which no operation ran on the device,
in a served cell (profiler trace, ``trace_reduce``)."""
from chipbench.readers import idle_share


def read(run):
    return idle_share(run) if run.kind == "serve" else None
