"""program_trace and the readers of the program's spans, retrace markers
and named scopes: their arithmetic on synthetic device operations, spans
and framework-op rows, and xprof's table of a trace recorded on a v5e."""
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

import bench_helpers  # noqa: F401  (puts the repo on sys.path)
from chipbench import program_trace
from chipbench.common import load_module

DATA = Path(__file__).resolve().parent / "data"

# device busy [0, 10], [30, 40], [60, 100] of the window [0, 100]
OPS = {"/device:TPU:0": [(0, 10), (30, 40), (60, 100), (150, 160)]}
HARNESS = [("client.generate", 0, 5), ("engine.flush", 5, 100)]
ROUNDS = [
    ("bif.flush", 5, 100),
    ("bif.round", 5, 50), ("bif.admit", 5, 12), ("bif.init", 12, 20),
    ("bif.step", 20, 25), ("bif.readback", 25, 40), ("bif.retire", 40, 48),
    ("retrace.serve.engine.pool_step", 22, 22),
    ("bif.round", 50, 100), ("bif.admit", 50, 52), ("bif.step", 52, 55),
    ("bif.readback", 55, 90), ("bif.retire", 90, 98),
    ("retrace.late", 120, 120),
]


def _pt(program=ROUNDS, harness=HARNESS, ops=OPS):
    return program_trace.ProgramTrace(ops, harness, program)


def _run(kind, pt=None, rows=None, records=()):
    lines = []
    return SimpleNamespace(kind=kind, program_trace=pt, framework_ops=rows,
                           records=list(records), say=lines.append,
                           lines=lines)


def _read(metric, run):
    return load_module("metrics", metric).read(run)


def test_window_is_the_harness_spans():
    pt = _pt()
    assert (pt.lo, pt.hi) == (0, 100)
    assert pt.window_s == pytest.approx(100e-9)
    # idle [10, 30], [40, 60]; nothing after the window counts
    assert pt.idle == [[(10, 30), (40, 60)]]


def test_idle_split_by_program_span():
    pt = _pt()
    # admit [10, 12] + [50, 52]; init [12, 20]; step [20, 25] + [52, 55];
    # readback [25, 30] + [55, 60]; retire [40, 48]
    assert pt.idle_in(("bif.admit",)) == pytest.approx(4e-9)
    assert pt.idle_in(("bif.init",)) == pytest.approx(8e-9)
    assert pt.idle_in(("bif.step",)) == pytest.approx(8e-9)
    assert pt.idle_in(("bif.readback",)) == pytest.approx(10e-9)
    assert pt.idle_in(("bif.retire",)) == pytest.approx(8e-9)
    assert pt.idle_in(("bif.flush",)) == pytest.approx(40e-9)


def test_round_host_time_leaves_out_the_readback():
    # round 1: 45 - 15 = 30; round 2: 50 - 35 = 15
    assert _pt().round_host_s() == pytest.approx(22.5e-9)
    assert _pt(program=[]).round_host_s() is None


def test_markers_are_counted_in_the_window_only():
    assert _pt().markers() == {"serve.engine.pool_step": 1}


def test_split_names_idle_outside_every_leaf_span():
    lines = _pt().split()
    assert "idle in bif.init: 8e-09 s" in lines
    # engine.flush [5, 100] idles 40 ns; [48, 50] lies outside every
    # leaf span (the gap between round 1's retire and round 2's admit)
    assert ("idle in engine.flush: 4e-08 s, of it outside every leaf "
            "span: 2e-09 s") in lines
    assert lines[-1] == ("retrace markers in the window: "
                         "{'serve.engine.pool_step': 1}")


def test_serve_readers():
    run = _run("serve", _pt())
    assert _read("round_host_ms.serve", run) == pytest.approx(22.5e-6)
    # admit 4 + init 8 + step 8 + retire 8 idle ns, of 100
    assert _read("host_span_idle_share.serve", run) == pytest.approx(28.0)
    assert _read("host_span_idle_share.job", run) is None


def test_job_readers():
    job = [("trace.probes", 0, 10), ("trace.solve", 10, 35),
           ("retrace.core.solver.resume", 12, 12), ("trace.readback", 35, 45),
           ("trace.probes", 45, 50), ("trace.solve", 50, 70),
           ("retrace.core.solver.resume", 52, 52), ("trace.readback", 70, 95),
           ("trace.reduce", 95, 100)]
    run = _run("job", _pt(job, [("job.logdet", 0, 100)]),
               records=[{}, {}])
    # idle [10, 30] in solve, [40, 45] in readback, [45, 50] in probes,
    # [50, 60] in solve: 35 of 100 outside the readback
    assert _read("host_span_idle_share.job", run) == pytest.approx(35.0)
    assert _read("retraces_per_job.job", run) == pytest.approx(1.0)
    assert _read("round_host_ms.serve", run) is None


def test_a_program_without_spans_reads_none():
    pt = _pt(program=[])
    for metric in ("round_host_ms.serve", "host_span_idle_share.serve"):
        assert _read(metric, _run("serve", pt)) is None
    for metric in ("host_span_idle_share.job", "retraces_per_job.job"):
        assert _read(metric, _run("job", pt, records=[{}])) is None
    assert _read("operator_pass_ms.serve", _run("serve", rows=[])) is None
    assert _read("quad_eigh_share.job", _run("job", rows=[])) is None
    assert _read("operator_pass_ms.serve", _run("serve")) is None


def _row(name, occurrences, self_us):
    return {"host_or_device": "Device", "type": "fusion", "operation": name,
            "occurrences": occurrences, "total_self_time": self_us}


def test_operator_pass_is_scoped_self_time_over_passes():
    rows = [
        _row("jit(_pool_step_run)/while/body/op.matvec/op.matvec/dot_general",
             100, 150_000.0),
        _row("jit(_pool_step_run)/while/body/op.matvec/mul", 100, 10_000.0),
        _row("jit(_pool_admit_run)/op.matvec/dot_general", 10, 15_000.0),
        _row("jit(_pool_step_run)/while/body/copy", 100, 90_000.0),
        _row("jit(f)/not.op.matvec.x/add", 1000, 1.0),
    ]
    run = _run("serve", rows=rows)
    # (150 + 10 + 15) ms of self-time over the 100 passes of the step
    assert _read("operator_pass_ms.serve", run) == pytest.approx(1.75)
    assert run.lines[-1] == ("operator pass: 3 ops under op.matvec, "
                             "175000.0 us self, 100 passes")
    assert _read("operator_pass_ms.serve", _run("job", rows=rows)) is None


def test_eigh_share_is_over_all_device_self_time():
    rows = [_row("while/body/quad.eigh/eigh", 50, 700.0),
            _row("jit(estimates)/quad.eigh/mul", 3, 100.0),
            _row("while/body/op.matvec/dot_general", 50, 200.0)]
    assert _read("quad_eigh_share.job", _run("job", rows=rows)) \
        == pytest.approx(80.0)
    assert _read("quad_eigh_share.job", _run("serve", rows=rows)) is None


def test_device_rows_drop_idle_and_host_and_need_every_column():
    cols = [{"id": c} for c in ("rank",) + program_trace.COLUMNS]
    table = {"cols": cols, "rows": [
        {"c": [{"v": v} for v in (1, "Device", "IDLE", "IDLE", 0, 9.0)]},
        {"c": [{"v": v} for v in (2, "Device", "dot", "jit(f)/dot", 6, 3.0)]},
        {"c": [{"v": v} for v in (3, "Host", "", "dummy", 6, 0.0)]}]}
    assert program_trace.device_rows(table) == [{
        "host_or_device": "Device", "type": "dot", "operation": "jit(f)/dot",
        "occurrences": 6, "total_self_time": 3.0}]
    table["cols"] = cols[:-1]
    assert program_trace.device_rows(table) is None


def test_xprof_table_of_a_recorded_chip_trace(tmp_path):
    """The installed xprof converts a trace recorded on a v5e and its
    framework_op_stats table carries the columns the readers use."""
    pytest.importorskip("xprof")
    # xprof leaves a cache of its conversion beside the trace
    path = tmp_path / "tiny.xplane.pb"
    shutil.copyfile(DATA / "tiny.xplane.pb", path)
    rows = program_trace.framework_table(str(path))
    ops = {r["operation"]: r for r in rows}
    assert ops["jit(<lambda>)/dot_general"]["occurrences"] == 6
    assert ops["jit(<lambda>)/dot_general"]["total_self_time"] > 0.0
    assert all(r["type"] != "IDLE" for r in rows)


def test_recorded_chip_trace_has_harness_spans_and_no_program_spans():
    device_ops, harness, program = program_trace.read_xplane(
        str(DATA / "tiny.xplane.pb"))
    assert harness and device_ops and program == []
    pt = program_trace.ProgramTrace(device_ops, harness, program)
    assert 0.0 < pt.idle_s([(pt.lo, pt.hi)]) < pt.window_s
    assert _read("host_span_idle_share.serve", _run("serve", pt)) is None
