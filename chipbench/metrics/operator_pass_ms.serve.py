"""Device time of one operator pass in a served cell: the device
self-time of the framework ops under the ``op.matvec`` named scope (xprof
framework-op table) over the passes, the largest occurrence count among
those ops (each runs once per pass)."""
from chipbench import program_trace


def read(run):
    rows = program_trace.framework_ops(run) if run.kind == "serve" else None
    mine = program_trace.scoped(rows or [], "op.matvec")
    if not mine:
        return None
    passes = max(r["occurrences"] for r in mine)
    self_us = sum(r["total_self_time"] for r in mine)
    for r in sorted(mine, key=lambda r: -r["total_self_time"])[:10]:
        run.say(f"op.matvec: {r['operation']} {r['total_self_time']!r} us "
                f"self, {r['occurrences']!r} occurrences")
    run.say(f"operator pass: {len(mine)} ops under op.matvec, "
            f"{self_us!r} us self, {passes!r} passes")
    return self_us / 1e3 / passes if passes else None
