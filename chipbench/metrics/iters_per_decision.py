"""Mean quadrature iterations of the window's resolved requests: a count
of the solver drive's work, never a speed."""


def read(run):
    its = [r["iterations"] for r in run.records
           if run.kind == "serve" and r.get("resolved")]
    return sum(its) / len(its) if its else None
