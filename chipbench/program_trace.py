"""The program's own records in a cell's profiler trace, for the
per-layer readers.

The program writes host spans with ``jax.profiler.TraceAnnotation``
(``repro.obs.spans``): ``bif.*`` for the engine's flush, its scheduler
rounds and their phases, ``trace.*`` for the phases of a stochastic
trace job, and zero-length ``retrace.<name>`` markers each time a
jitted function is traced (``repro.obs.registry``). It names device
work with ``jax.named_scope`` (``op.matvec``, ``quad.eigh``), which
xprof's framework-op table groups device self-time by.

`read_xplane` pulls device-op intervals, the harness's spans and the
program's events out of one trace; `ProgramTrace` puts them on the
window ``trace_reduce.reduce`` uses (first harness span's start to the
last one's end) and splits the device's idle time by program span.
`framework_ops` reads xprof's table. A program without these records
gives empty results here, and the readers None.
"""
from __future__ import annotations

import json
from collections import defaultdict

from chipbench.common import SPANS
from chipbench.harness import TRACE_DIR
from chipbench.trace_reduce import (OPS_LINE, _complement, _union,
                                    find_xplane)

PREFIXES = ("bif.", "trace.", "retrace.")
# program spans that hold other program spans: a flush and its rounds
CONTAINERS = ("bif.flush", "bif.round")
# columns of xprof's framework_op_stats table that the readers use
COLUMNS = ("host_or_device", "type", "operation", "occurrences",
           "total_self_time")


def read_xplane(path: str):
    """(device_ops, harness_spans, program_events): ``device_ops`` maps
    each device plane to a list of (start_ns, end_ns); the other two are
    lists of (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData

    device_ops, harness, program = {}, [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            ops = [(e.start_ns, e.end_ns) for line in plane.lines
                   if line.name == OPS_LINE for e in line.events]
            if ops:
                device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        harness.append((e.name, e.start_ns, e.end_ns))
                    elif e.name.startswith(PREFIXES):
                        program.append((e.name, e.start_ns, e.end_ns))
    return device_ops, harness, program


def _measure(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _overlap(a, b):
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


class ProgramTrace:
    """Device idle time and program events over the harness's window;
    times in seconds."""

    def __init__(self, device_ops: dict, harness: list, program: list):
        self.lo = min(s for _, s, _ in harness)
        self.hi = max(e for _, _, e in harness)
        self.window_s = (self.hi - self.lo) / 1e9
        self.harness, self.program = harness, program
        self.idle = [_complement(_union(ops, self.lo, self.hi),
                                 self.lo, self.hi)
                     for ops in device_ops.values()]

    def intervals(self, names, events=None) -> list:
        """The union of the events named in ``names``, in the window."""
        return _union([(s, e) for n, s, e in
                       (self.program if events is None else events)
                       if n in names], self.lo, self.hi)

    def has(self, prefix: str) -> bool:
        return any(n.startswith(prefix) for n, _, _ in self.program)

    def idle_s(self, spans) -> float:
        """Device idle time inside ``spans`` (sorted, disjoint),
        averaged over the devices."""
        return sum(_measure(_overlap(gaps, spans))
                   for gaps in self.idle) / len(self.idle) / 1e9

    def idle_in(self, names) -> float:
        return self.idle_s(self.intervals(names))

    def round_host_s(self):
        """Mean over the window's ``bif.round`` of its duration less the
        part ``bif.readback`` covers; None without rounds."""
        count = sum(1 for n, s, e in self.program if n == "bif.round"
                    and min(e, self.hi) > max(s, self.lo))
        if not count:
            return None
        host = _complement(self.intervals(("bif.readback",)),
                           self.lo, self.hi)
        rounds = self.intervals(("bif.round",))
        return _measure(_overlap(rounds, host)) / count / 1e9

    def markers(self) -> dict:
        """Counts of ``retrace.<name>`` markers in the window, by name."""
        out = defaultdict(int)
        for n, s, _ in self.program:
            if n.startswith("retrace.") and self.lo <= s <= self.hi:
                out[n[len("retrace."):]] += 1
        return dict(out)

    def split(self) -> list:
        """Lines for the record: device idle per program span name, and
        per harness span the idle time that falls outside every leaf
        program span (all but ``CONTAINERS``)."""
        names = sorted({n for n, _, _ in self.program
                        if not n.startswith("retrace.")})
        lines = [f"idle in {n}: {self.idle_in((n,))!r} s" for n in names]
        leaves = [n for n in names if n not in CONTAINERS]
        outside = _complement(self.intervals(leaves), self.lo, self.hi)
        for h in sorted({n for n, _, _ in self.harness}):
            inside = self.intervals((h,), self.harness)
            lines.append(f"idle in {h}: {self.idle_s(inside)!r} s, of it "
                         f"outside every leaf span: "
                         f"{self.idle_s(_overlap(inside, outside))!r} s")
        lines.append(f"retrace markers in the window: {self.markers()}")
        return lines


def load(run):
    """The run's ProgramTrace, read once, kept on the run and its split
    printed; None when the run has no trace reduction, or its trace no
    harness span or device operation."""
    if not hasattr(run, "program_trace"):
        run.program_trace = None
        path = _xplane(run)
        if path is not None:
            device_ops, harness, program = read_xplane(path)
            if harness and device_ops:
                run.program_trace = ProgramTrace(device_ops, harness,
                                                 program)
                for line in run.program_trace.split():
                    run.say(f"program trace: {line}")
    return run.program_trace


def _xplane(run):
    """The run's trace file; None when the run made no trace reduction
    (untraced, failed, or no device in the trace)."""
    if not run.trace_summary:
        return None
    return find_xplane(str(TRACE_DIR / run.workload))


def framework_table(path: str):
    """Device rows of xprof's framework_op_stats table (IDLE left out),
    as dicts of ``COLUMNS``; None when xprof cannot be imported or the
    table lacks a column."""
    try:
        from xprof.convert import raw_to_tool_data
    except ImportError:
        return None
    data, _ = raw_to_tool_data.xspace_to_tool_data(
        [path], "framework_op_stats", {})
    return device_rows(json.loads(data)[0])


def device_rows(table: dict):
    """The device rows of one framework_op_stats table (a Google Charts
    DataTable: ``cols`` with ``id``, ``rows`` of ``c`` cells), IDLE left
    out; None when a column in ``COLUMNS`` is missing."""
    ids = [c["id"] for c in table["cols"]]
    if not set(COLUMNS) <= set(ids):
        return None
    rows = [dict(zip(ids, (c.get("v") for c in r["c"])))
            for r in table["rows"]]
    return [{k: r[k] for k in COLUMNS} for r in rows
            if r["host_or_device"] == "Device" and r["type"] != "IDLE"]


def framework_ops(run, top: int = 10):
    """The run's framework-op rows, read once and kept on the run, with
    the ``top`` rows by device self-time printed; None without a trace
    reduction or a readable table."""
    if not hasattr(run, "framework_ops"):
        run.framework_ops = None
        path = _xplane(run)
        if path is not None:
            try:
                run.framework_ops = framework_table(path)
            except Exception as e:  # noqa: BLE001 - nothing to read
                run.say(f"framework op table not read: "
                        f"{type(e).__name__}: {e}")
        for r in sorted(run.framework_ops or [],
                        key=lambda r: -r["total_self_time"])[:top]:
            run.say(f"framework op: {r['operation']} "
                    f"{r['total_self_time']!r} us self, "
                    f"{r['occurrences']!r} occurrences")
    return run.framework_ops


def scoped(rows, scope: str) -> list:
    """The rows whose framework op name holds the named scope ``scope``."""
    return [r for r in rows if f"/{scope}/" in f"/{r['operation']}"]
