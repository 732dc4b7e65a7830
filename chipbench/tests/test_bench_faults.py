"""The comparison that decides `correct` fails where it must: the
control (the reference in the precision below the configuration's, in
the program's place) and the faults a cell can have, planted under the
timed path with the harness's look for a chip skipped."""
import pytest

import bench_helpers as bh


@pytest.mark.parametrize("workload", ["elevators_gp.batch_score",
                                      "epinions_graph.batch_score",
                                      "elevators_gp.logdet_job"])
def test_control_is_not_correct(workload):
    res = bh.run(workload, answerer="control")
    assert res["correct"] is False
    bad = [k for k, v in res["checks"].items() if v["value"] > v["limit"]]
    assert bad, res["checks"]


def _patch_step(monkeypatch, fn):
    from repro.serve.engine import BIFEngine

    orig = BIFEngine._step
    monkeypatch.setattr(BIFEngine, "_step",
                        lambda self, *a: fn(self, orig, *a))


def test_served_answer_altered_where_produced(monkeypatch):
    def step(self, orig, state, ts, has_t, caps):
        state, lo, hi, res, dec, done, its = orig(self, state, ts, has_t, caps)
        return state, lo * 1.01, hi, res, dec, done, its

    _patch_step(monkeypatch, step)
    res = bh.run("elevators_gp.batch_score")
    assert res["correct"] is False
    assert res["checks"]["bracket_miss"]["value"] > \
        res["checks"]["bracket_miss"]["limit"]


def test_served_step_returning_its_state_unchanged(monkeypatch):
    from repro.serve import engine

    def step(self, orig, state, ts, has_t, caps):
        return engine._pool_step_run(self.solver, state, ts, has_t, caps, n=0)

    _patch_step(monkeypatch, step)
    # the warm-up flush never returns: the harness's guard ends the run
    res = bh.run("elevators_gp.batch_score", grace=5.0, setup_grace=30.0)
    assert res["correct"] is False
    assert {"setup_finished", "window_closed"} & set(res["checks"])


def test_served_half_of_the_batch_left_out(monkeypatch):
    from repro.serve.engine import BIFEngine

    orig = BIFEngine.flush

    def flush(self, **kw):
        queue = orig(self, **kw)
        for r in queue[::2]:
            r.lower = r.upper = r.decision = None
            r.resolved = False
        return queue

    monkeypatch.setattr(BIFEngine, "flush", flush)
    res = bh.run("epinions_graph.batch_score")
    assert res["correct"] is False
    assert res["checks"]["unanswered"]["value"] > 0


def _patch_trace_quad(monkeypatch, fn):
    from repro.core import trace

    orig = trace.trace_quad
    monkeypatch.setattr(trace, "trace_quad",
                        lambda *a, **kw: fn(orig, *a, **kw))


def test_job_answer_altered_where_produced(monkeypatch):
    def tq(orig, *a, **kw):
        r = orig(*a, **kw)
        st = r.state
        return r._replace(state=st._replace(probe_lower=st.probe_lower * 1.01,
                                            probe_upper=st.probe_upper * 1.01))

    _patch_trace_quad(monkeypatch, tq)
    res = bh.run("elevators_gp.logdet_job")
    assert res["correct"] is False, res["_lines"]
    assert res["checks"]["probe_misses"]["value"] > \
        res["checks"]["probe_misses"]["limit"]


def test_job_mean_taken_over_half_of_the_probes(monkeypatch):
    def tq(orig, *a, **kw):
        r = orig(*a, **kw)
        half = len(r.state.probe_lower) // 2
        return r._replace(lower=float(r.state.probe_lower[:half].mean()),
                          upper=float(r.state.probe_upper[:half].mean()))

    _patch_trace_quad(monkeypatch, tq)
    res = bh.run("elevators_gp.logdet_job")
    assert res["correct"] is False, res["_lines"]
    assert res["checks"]["job_miss"]["value"] > \
        res["checks"]["job_miss"]["limit"]


def test_job_half_of_the_probes_left_out(monkeypatch):
    def tq(orig, op, fn, num_probes, **kw):
        return orig(op, fn, num_probes // 2, **kw)

    _patch_trace_quad(monkeypatch, tq)
    res = bh.run("elevators_gp.logdet_job")
    assert res["correct"] is False
    assert res["checks"]["unanswered_probes"]["value"] > 0
