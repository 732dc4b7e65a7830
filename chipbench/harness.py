"""One benchmark run of one cell, found by name in BENCHMARK.json.

The cell names a configuration (``configs/<config>.json``, whose
``builder`` names ``builders/<builder>.py``) and a traffic mix
(``traffic/<mix>.json``, whose ``loop`` names ``loops/<loop>.py``); its
limits are ``limits/<cell>.json``, and each per-layer metric that lists
the cell is read by ``metrics/<metric>.py``. Nothing here names a cell,
a configuration, a mix or a metric.

A run: build the deployment on the device from the seed, let the loop
build the system under test and warm up every shape it will use
(set-up); prepare the reference and the client's inputs, timed apart and
left out of ``setup_s``; run the measured window (under the profiler
with ``trace``); read the device's peak memory; free the system; compare
what the window returned with the reference; print the numbers compared
and the result line.
"""
from __future__ import annotations

import _thread
import contextlib
import math
import os
import shutil
import sys
import threading
import time
import traceback

from chipbench import checks
from chipbench.common import BENCH_DIR, ROOT, load_json, load_module, seed32

CACHE_DIR = ROOT / ".jax_cache"        # fixed: the path is part of the key
TRACE_DIR = ROOT / ".bench_traces"
WINDOW_GRACE_S = 120.0                 # a window may overrun by this much
SETUP_GRACE_S = 1000.0                 # a cold set-up compiles everything


class Run:
    """What one run knows; loops and metric readers read and fill it."""

    def __init__(self, workload: str, seed: int, seconds: float,
                 trace: bool, t0: float, answerer: str, overrides: dict,
                 say, root=ROOT):
        bench = load_json(root / "BENCHMARK.json")
        data = root / BENCH_DIR.name
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
        self.bench, self.cell = bench, cells[workload]
        self.workload, self.seed = workload, int(seed)
        self.seconds, self.trace, self.t0 = float(seconds), bool(trace), t0
        self.answerer = answerer
        conf = {c["name"]: c for c in bench["configs"]}[self.cell["config"]]
        self.cfg = {**load_json(root / conf["file"]),
                    **overrides.get("config", {})}
        self.mix = {**load_json(data / "traffic"
                                / f"{self.cell['traffic']}.json"),
                    **overrides.get("traffic", {})}
        self.limits = load_json(data / "limits"
                                / f"{workload}.json")["limits"]
        self.builder = load_module("builders", self.cfg["builder"])
        self.loop = load_module("loops", self.mix["loop"])
        self.say = say
        self.excluded_s = 0.0
        self.dep = None
        self._reference = None
        self.records: list = []      # one dict per request or job
        self.stats: dict | None = None
        self.trace_summary: dict | None = None
        self.compiles_in_window = 0
        self.kind = self.loop.KIND    # "serve" or "job"

    @contextlib.contextmanager
    def excluded(self, label: str):
        """Time work that is the benchmark's, not the deployment's; it is
        printed on its own line and left out of setup_s."""
        t = time.perf_counter()
        yield
        dt = time.perf_counter() - t
        self.excluded_s += dt
        self.say(f"excluded from setup_s: {label} {dt!r} s")

    def reference(self):
        if self._reference is None:
            t = time.perf_counter()
            self._reference = self.builder.Reference(self.dep)
            self.say(f"reference: {self.cfg['builder']}.Reference built in "
                     f"{time.perf_counter() - t!r} s")
        return self._reference

    @staticmethod
    def span(name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)


class _Watchdog:
    """Interrupts the window if it overruns; with ``hard`` also ends the
    process a minute later, should the device never return."""

    def __init__(self, limit_s: float, hard: bool, on_hard):
        self.fired = False
        self._timers = [threading.Timer(limit_s, self._fire)]
        if hard:
            self._timers.append(threading.Timer(limit_s + 60.0, on_hard))
        for t in self._timers:
            t.daemon = True
            t.start()

    def _fire(self):
        self.fired = True
        _thread.interrupt_main()

    def cancel(self):
        for t in self._timers:
            t.cancel()


def _device_info(jax) -> dict:
    devs = jax.devices()
    # the CPU backend reports no memory stats
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def _count_compiles():
    from jax import monitoring

    box = [0, False]

    def listener(name, _dur, **_kw):
        if box[1] and name == "/jax/core/compile/backend_compile_duration":
            box[0] += 1

    monitoring.register_event_duration_secs_listener(listener)
    return box


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t0: float, answerer: str = "program", overrides: dict | None = None,
             window_grace_s: float = WINDOW_GRACE_S,
             setup_grace_s: float = SETUP_GRACE_S, hard_exit: bool = False,
             say=None, root=ROOT) -> dict:
    """One run; returns the result object that run.py prints last.
    ``root`` holds BENCHMARK.json and the data files (configs, mixes,
    limits); the code is always this package's."""
    say = say or (lambda msg: print(msg, file=sys.stderr, flush=True))
    import jax

    if jax.default_backend() != "cpu":   # CPU runs are the harness's tests
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = _count_compiles()

    run = Run(workload, seed, seconds, trace, t0, answerer, overrides or {},
              say, root)

    def hard():
        say("the run did not return after its grace and a minute more")
        os._exit(3)

    failure = None
    dog = _Watchdog(setup_grace_s, hard_exit, hard)
    try:
        run.dep = run.builder.build(run.cfg, seed32(seed, 0))
        say(f"deployment: {run.dep.describe}")
        run.loop.setup(run)
        run.loop.prepare(run)
    except KeyboardInterrupt:
        if not dog.fired:
            raise
        failure = ("setup_finished", f"set-up overran {setup_grace_s} s")
    finally:
        dog.cancel()
    setup_s = time.perf_counter() - t0 - run.excluded_s

    trace_dir = TRACE_DIR / workload
    if trace and failure is None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    if failure is None:
        dog = _Watchdog(seconds + window_grace_s, hard_exit, hard)
        compiles[1] = True
        try:
            run.loop.window(run)
        except KeyboardInterrupt:
            if not dog.fired:
                raise
            failure = ("window_closed",
                       f"window overran {seconds + window_grace_s} s")
        except Exception as e:  # noqa: BLE001 - reported as not correct
            say(traceback.format_exc())
            failure = ("window_closed", f"{type(e).__name__}: {e}")
        finally:
            dog.cancel()
            compiles[1] = False
        if trace:
            jax.profiler.stop_trace()
    run.compiles_in_window = compiles[0]
    device = _device_info(jax)
    say(f"window: compiles_in_window={run.compiles_in_window} "
        f"records={len(run.records)} failure={failure}")

    metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
    e2e = run.loop.end_to_end(run) if failure is None else {}
    run.loop.release(run)

    if failure is None:
        numbers = run.loop.check(run)
        correct, compared = checks.judge(numbers, run.limits)
    else:
        correct, compared = False, {failure[0]: {"value": 1.0, "limit": 0.0}}

    result = {"correct": correct, "attempted": len(run.records),
              "failed": sum(1 for r in run.records if r.get("failed", True))}
    wanted = [m for m in run.bench["end_to_end"] if m["name"] != "setup_s"
              and workload in m.get("workloads", [workload])]
    if not trace:
        for m in wanted:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        from chipbench import trace_reduce

        if failure is None:
            run.trace_summary = trace_reduce.reduce_dir(str(trace_dir))
        metrics = {}
        for m in run.bench["per_layer"]:
            if workload not in m.get("workloads", [workload]):
                continue
            value = load_module("metrics", m["name"]).read(run)
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if run.trace_summary is not None:
            device["busy_s"] = run.trace_summary["busy_s"]
            device["window_s"] = run.trace_summary["window_s"]
            result["breakdown"] = {
                "device_ops": run.trace_summary["device_ops"],
                "idle_gaps": run.trace_summary["idle_gaps"]}
    result["metrics"] = metrics
    result["device"] = device
    for name, c in compared.items():
        say(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    result["checks"] = compared
    return result
