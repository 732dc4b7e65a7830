"""trace_reduce: busy and idle over the window, top device operations,
idle gaps split by the harness span the host was in."""
from pathlib import Path

import pytest

import bench_helpers  # noqa: F401  (puts the repo on sys.path)
from chipbench import trace_reduce

DATA = Path(__file__).resolve().parent / "data"


def _ops():
    return {"/device:TPU:0": [("a", 0, 10), ("b", 5, 20), ("c", 40, 50),
                              ("a", 55, 58), ("late", 70, 90)]}


def test_busy_idle_and_window_from_harness_spans():
    spans = [("engine.flush", 0, 30), ("client.generate", 30, 60)]
    out = trace_reduce.reduce(_ops(), spans)
    assert out["window_s"] == pytest.approx(60e-9)
    # union of [0, 20], [40, 50], [55, 58] inside [0, 60]
    assert out["busy_s"] == pytest.approx(33e-9)
    assert out["devices"] == 1


def test_idle_gaps_split_by_host_span():
    spans = [("engine.flush", 0, 30), ("client.generate", 30, 45)]
    out = trace_reduce.reduce(_ops(), spans)
    # window [0, 45]; idle [20, 40]: 10 ns in the flush, 10 ns in
    # generate; no gap after 45 counts (the window ends there)
    gaps = dict(out["idle_gaps"])
    assert gaps["engine.flush"] == pytest.approx(10e-9)
    assert gaps["client.generate"] == pytest.approx(10e-9)
    assert "host.other" not in gaps


def test_idle_outside_every_span_is_host_other():
    spans = [("engine.flush", 0, 10), ("engine.flush", 50, 60)]
    out = trace_reduce.reduce(_ops(), spans)
    gaps = dict(out["idle_gaps"])
    # idle [20, 40] and [50, 55], [58, 60]: 20 ns between spans
    assert gaps["host.other"] == pytest.approx(20e-9)
    assert gaps["engine.flush"] == pytest.approx(7e-9)


def test_top_ops_are_clipped_to_the_window_and_sorted():
    spans = [("engine.flush", 0, 60)]
    ops = dict(trace_reduce.reduce(_ops(), spans)["device_ops"])
    assert list(ops) == ["b", "a", "c"]
    assert ops["a"] == pytest.approx(13e-9)
    assert "late" not in ops


def test_busy_is_averaged_over_devices():
    ops = {"/device:TPU:0": [("x", 0, 10)], "/device:TPU:1": [("x", 0, 30)]}
    out = trace_reduce.reduce(ops, [("job.logdet", 0, 40)])
    assert out["busy_s"] == pytest.approx(20e-9)
    assert dict(out["idle_gaps"])["job.logdet"] == pytest.approx(20e-9)


def test_nothing_to_read_gives_none():
    assert trace_reduce.reduce({}, [("engine.flush", 0, 1)]) is None
    assert trace_reduce.reduce(_ops(), []) is None
    assert trace_reduce.reduce_dir(str(DATA / "no_such_dir")) is None


def test_recorded_chip_trace():
    """A trace recorded on a v5e chip: three jitted steps inside two
    engine.flush spans, with a generate and a submit span around them."""
    device_ops, spans = trace_reduce.read_xplane(str(DATA / "tiny.xplane.pb"))
    assert [p for p in device_ops if p.startswith("/device:TPU:")]
    assert {n for n, _, _ in spans} == {"client.generate", "engine.flush",
                                        "client.submit"}
    out = trace_reduce.reduce(device_ops, spans)
    assert 0.0 < out["busy_s"] < out["window_s"]
    gaps = dict(out["idle_gaps"])
    # the host slept 2 ms in each span; the device was idle meanwhile
    assert gaps["client.generate"] > 1e-3 and gaps["client.submit"] > 1e-3
    assert out["device_ops"] and all(s > 0 for _, s in out["device_ops"])
