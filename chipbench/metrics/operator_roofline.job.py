"""Least time of the operator passes the window's probes needed, over
the device's busy time, in a job cell. Passes needed = the probes'
iterations / the probes per job (the lanes of one pass)."""
from chipbench.readers import roofline_share


def read(run):
    if run.kind != "job":
        return None
    its = sum(sum(r["probe_iterations"]) for r in run.records)
    return roofline_share(run, its, int(run.mix["probes"]))
