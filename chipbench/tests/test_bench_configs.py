"""Every configuration and mix at a tiny size: the operator's certified
interval, the reference against a dense numpy solve, the control near
the truth, the traffic generator's shares, and that every
name in BENCHMARK.json has its file."""
import json

import numpy as np
import pytest

import bench_helpers as bh
from chipbench import traffic_gen
from chipbench.common import BENCH_DIR, ROOT, load_json, load_module

BENCH = load_json(ROOT / "BENCHMARK.json")
CONFIGS = {c["name"]: load_json(ROOT / c["file"]) for c in BENCH["configs"]}


def _dep(name):
    cfg = {**CONFIGS[name], **bh.TINY[name]}
    builder = load_module("builders", cfg["builder"])
    return builder, builder.build(cfg, 12345)


def _dense(name, dep):
    if name == "elevators_gp":
        return np.asarray(dep.a, np.float64)
    a = np.zeros((dep.n, dep.n))
    np.add.at(a, (dep.rows, dep.cols), dep.vals.astype(np.float64))
    return a


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_agrees_with_numpy_solve(name):
    builder, dep = _dep(name)
    a = _dense(name, dep)
    us = dep.queries(7, 5)
    want = np.einsum("kn,nk->k", us.astype(np.float64),
                     np.linalg.solve(a, us.T.astype(np.float64)))
    np.testing.assert_allclose(builder.Reference(dep).quad(us), want,
                               rtol=1e-9)


def _log_forms(a, us):
    ev, v = np.linalg.eigh(a)
    return np.einsum("kj,j->k", (us.astype(np.float64) @ v) ** 2, np.log(ev))


def test_log_reference_agrees_with_numpy_eigh():
    builder, dep = _dep("elevators_gp")
    us = np.sign(np.random.default_rng(3).standard_normal((6, dep.n)))
    np.testing.assert_allclose(builder.Reference(dep).log_quad(us),
                               _log_forms(_dense("elevators_gp", dep), us),
                               rtol=1e-10)


def test_log_control_is_near_but_not_at_the_truth():
    builder, dep = _dep("elevators_gp")
    us = np.sign(np.random.default_rng(4).standard_normal((6, dep.n)))
    truth = _log_forms(_dense("elevators_gp", dep), us)
    ctl = builder.control_log_quad(dep, us.astype(np.float32),
                                   CONFIGS["elevators_gp"]
                                   ["control_lanczos_iters"])
    rel = np.abs(ctl - truth) / np.abs(truth)
    assert 0.0 < rel.max() < 1e-3


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_spectral_interval_is_certified_and_kappa_bounded(name):
    _, dep = _dep(name)
    a = _dense(name, dep)
    np.testing.assert_allclose(a, a.T, atol=0)
    ev = np.linalg.eigvalsh(a)
    assert dep.lam_min <= ev[0] and ev[-1] <= dep.lam_max
    assert dep.lam_max / dep.lam_min <= 1.01 * CONFIGS[name]["kappa"]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_operator_matvec_is_the_matrix(name):
    _, dep = _dep(name)
    x = np.random.default_rng(0).standard_normal((3, dep.n)).astype(np.float32)
    got = np.asarray(dep.op.matvec(x))
    np.testing.assert_allclose(got, x @ _dense(name, dep).T, rtol=2e-4,
                               atol=2e-4 * np.abs(got).max())


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_control_is_near_but_not_at_the_truth(name):
    builder, dep = _dep(name)
    us = dep.queries(9, 8)
    truth = builder.Reference(dep).quad(us)
    ctl = builder.control_quad(dep, us, CONFIGS[name]["control_cg_iters"])
    rel = np.abs(ctl - truth) / np.abs(truth)
    assert rel.max() < 1e-2 and rel.max() > 0.0


def test_queries_are_a_function_of_their_key():
    _, dep = _dep("elevators_gp")
    np.testing.assert_array_equal(dep.queries(3, 4), dep.queries(3, 4))
    assert not np.array_equal(dep.queries(3, 4), dep.queries(4, 4))


@pytest.mark.parametrize("count", [1, 4, 1024, 1001])
def test_judge_share_is_exact_and_seeded(count):
    mix = {"judge_share": 0.75}
    m1 = traffic_gen.judge_mask(mix, count, 2 ** 40, 1)
    assert m1.sum() == round(0.75 * count)
    np.testing.assert_array_equal(m1, traffic_gen.judge_mask(mix, count,
                                                             2 ** 40, 1))


def test_every_name_has_its_file():
    for w in BENCH["workloads"]:
        mix = load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
        assert (BENCH_DIR / "loops" / f"{mix['loop']}.py").is_file()
        assert (BENCH_DIR / "limits" / f"{w['name']}.json").is_file()
        assert w["config"] in CONFIGS
    for cfg in CONFIGS.values():
        assert (BENCH_DIR / "builders" / f"{cfg['builder']}.py").is_file()
        assert cfg["reduced"] == [c["reduced"] for c in BENCH["configs"]
                                  if c["name"] == cfg["name"]][0]
    for m in BENCH["per_layer"]:
        mod = load_module("metrics", m["name"])
        assert callable(mod.read)
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    json.dumps(BENCH)

