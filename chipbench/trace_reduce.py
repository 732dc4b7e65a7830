"""Profiler trace (.xplane.pb) -> device busy and idle over the window.

`read_xplane` pulls two things out of a trace written by
``jax.profiler.start_trace``: the operations that ran on each device
(the "XLA Ops" line of every ``/device:...`` plane) and the harness's own
host spans (``jax.profiler.TraceAnnotation`` events named in
``common.SPANS``). `reduce` turns them into:

* ``window_s``: from the first harness span's start to the last one's end;
* ``busy_s``: the union of the operation intervals inside the window,
  averaged over the devices;
* ``device_ops``: the ten operation names with the most device time;
* ``idle_gaps``: the device's idle time inside the window, split by the
  harness span that the host was in meanwhile ("host.other" outside
  every span), the ten largest.

All times in the trace are nanoseconds on the profiler's one clock.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

from chipbench.common import SPANS

OPS_LINE = "XLA Ops"


def op_name(event_name: str) -> str:
    """An XLA op event is named by its whole HLO instruction; keep the
    instruction's name ("%fusion.3 = f32[...] fusion(...)" -> "%fusion.3")."""
    return event_name.split(" = ", 1)[0]


def read_xplane(path: str):
    """(device_ops, host_spans): ``device_ops`` maps each device plane to
    a list of (name, start_ns, end_ns); ``host_spans`` is a list of
    (name, start_ns, end_ns) for the harness's spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops, host_spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((op_name(e.name), e.start_ns, e.end_ns)
                               for e in line.events)
            if ops:
                device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans.extend((e.name, e.start_ns, e.end_ns)
                                  for e in line.events if e.name in SPANS)
    return device_ops, host_spans


def find_xplane(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def _union(intervals, lo, hi):
    """Merged, clipped intervals (sorted, disjoint) within [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _complement(merged, lo, hi):
    gaps, cur = [], lo
    for s, e in merged:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    return gaps


def reduce(device_ops: dict, host_spans: list, top: int = 10) -> dict | None:
    """Busy, idle and the breakdown over the harness's window. None when
    the trace holds no harness span or no device operation."""
    if not host_spans or not device_ops:
        return None
    lo = min(s for _, s, _ in host_spans)
    hi = max(e for _, _, e in host_spans)
    if hi <= lo:
        return None
    window_ns = hi - lo
    busy, per_op = [], defaultdict(float)
    idle = defaultdict(float)
    for ops in device_ops.values():
        merged = _union([(s, e) for _, s, e in ops], lo, hi)
        busy.append(sum(e - s for s, e in merged))
        for name, s, e in ops:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                per_op[name] += (e - s) / 1e9
        # split each idle gap by the harness span the host was in; spans
        # of one host thread do not overlap, so the split is exact
        spans = _union([(s, e) for _, s, e in host_spans], lo, hi)
        for gs, ge in _complement(merged, lo, hi):
            covered = 0.0
            for name, s, e in host_spans:
                ov = min(e, ge) - max(s, gs)
                if ov > 0:
                    idle[name] += ov / 1e9 / len(device_ops)
            for s, e in spans:
                ov = min(e, ge) - max(s, gs)
                if ov > 0:
                    covered += ov
            if ge - gs > covered:
                idle["host.other"] += (ge - gs - covered) / 1e9 \
                    / len(device_ops)
    n_dev = len(device_ops)
    ops_sorted = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps_sorted = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "devices": n_dev,
        "device_ops": [[k, v / n_dev] for k, v in ops_sorted],
        "idle_gaps": [[k, v] for k, v in gaps_sorted],
    }


def reduce_dir(trace_dir: str) -> dict | None:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce(*read_xplane(path))
