"""Least time of the operator passes the window's answers needed, over
the device's busy time, in a served cell. Passes needed = the answered
requests' iterations / the pool's lanes; a pass is counted by
``work.py`` from the deployment's stated storage."""
from chipbench.readers import roofline_share


def read(run):
    if run.kind != "serve":
        return None
    its = sum(r["iterations"] or 0 for r in run.records
              if r.get("lower") is not None)
    return roofline_share(run, its, int(run.cfg["max_batch"]))
