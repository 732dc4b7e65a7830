"""Share of the device's busy time that the quadrature step's batched
eigensolve takes in a job cell: device self-time of the framework ops
under the ``quad.eigh`` named scope over that of every device op (xprof
framework-op table, idle left out)."""
from chipbench import program_trace


def read(run):
    rows = program_trace.framework_ops(run) if run.kind == "job" else None
    mine = program_trace.scoped(rows or [], "quad.eigh")
    total = sum(r["total_self_time"] for r in rows or [])
    if not mine or total <= 0.0:
        return None
    self_us = sum(r["total_self_time"] for r in mine)
    run.say(f"quad.eigh: {len(mine)} ops, {self_us!r} us self of "
            f"{total!r} us")
    return 100.0 * self_us / total
