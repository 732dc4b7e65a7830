"""Arithmetic that several per-layer metric readers share."""
from __future__ import annotations

from chipbench import work


def idle_share(run):
    """100 * (1 - busy / window) from the trace reduction, or None."""
    t = run.trace_summary
    if not t or t["window_s"] <= 0.0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def roofline_share(run, lane_iterations: int, lanes: int):
    """100 * least time of lane_iterations / lanes passes over busy time;
    None when nothing ran or no trace was read."""
    t = run.trace_summary
    if not t or t["busy_s"] <= 0.0 or lane_iterations <= 0:
        return None
    import jax

    peaks = work.peaks_for(jax.devices()[0].device_kind)
    per_pass, bound = work.least_time(run.dep.storage, lanes, peaks)
    run.say(f"roofline: one pass of {lanes} lanes is {bound}-bound, least "
            f"{per_pass!r} s; {lane_iterations / lanes!r} passes needed")
    return 100.0 * per_pass * (lane_iterations / lanes) / t["busy_s"]
