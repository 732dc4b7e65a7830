"""``chip_smoke.py`` rehearsed on the CPU at N = 256.

Each phase of the chip smoke runs here through the same functions the
chip run calls — Pallas kernels in interpret mode, f32 only — and must
pass its own checks against the float64 host reference. The four-chip
phase runs in a subprocess on 4 virtual CPU devices (the in-process
suite sees one device, see conftest.py). ``main()`` itself must refuse
to run off the TPU.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

N = 256
REQUESTS = 32
MAX_BATCH = 16


@pytest.fixture(scope="module")
def f32():
    with jax.enable_x64(False):
        yield


@pytest.fixture(scope="module")
def dense(f32):
    prob = chip_smoke.dense_problem(N, 0)
    return prob, chip_smoke.traffic(prob, REQUESTS, 0)


def test_main_refuses_off_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "repo"])
def test_compile_cache_directory(monkeypatch, tmp_path, from_env):
    """``JAX_COMPILATION_CACHE_DIR`` wins and no other directory is set;
    without it the cache is ``<repo>/.jax_cache``. Both keep every
    program, however quickly it compiled. Nothing compiles while the
    cache is on, and the config is restored after."""
    from repro.utils import compile_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable()
        set_dir = jax.config.jax_compilation_cache_dir
        min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
    assert min_s == 0
    if from_env:
        assert got == str(tmp_path)
        assert set_dir == saved["jax_compilation_cache_dir"]
    else:
        assert got == set_dir == str(ROOT / ".jax_cache")


def test_dense_problem_is_f32_with_certified_interval(dense):
    prob, (us, ts, truth) = dense
    assert prob.op.a.dtype == np.float32 and us.dtype == np.float32
    w = np.linalg.eigvalsh(np.asarray(prob.op.a, np.float64))
    assert prob.lam_min <= w[0] and w[-1] <= prob.lam_max
    assert prob.lam_max / prob.lam_min <= chip_smoke.KAPPA * 1.001
    judge = ~np.isnan(ts)
    assert judge.sum() == REQUESTS * 3 // 4
    np.testing.assert_allclose(np.abs(ts[judge] / truth[judge] - 1), 0.03,
                               rtol=1e-4)


@pytest.mark.parametrize("backend", ["reference", "fused", "pallas"])
def test_dense_serve_phase(dense, backend):
    prob, (us, ts, truth) = dense
    dec, its, lo, hi = chip_smoke.serve_phase(prob, backend, us, ts, truth,
                                              max_batch=MAX_BATCH)
    assert its.max() <= chip_smoke.MAX_ITERS
    assert np.all(lo <= hi)


def test_dense_reorth_phase(dense):
    prob, (us, _, truth) = dense
    chip_smoke.reorth_phase(prob, us[:16], truth[:16])


def test_logdet_phase(dense):
    prob, _ = dense
    chip_smoke.logdet_phase(prob, 8, 0)


def test_sparse_serve_phase(f32):
    prob = chip_smoke.sparse_problem(N, 0)
    eye = np.eye(N, dtype=np.float32)
    a = np.asarray(prob.op.matvec(eye), np.float64)   # columns of A
    np.testing.assert_array_equal(a, a.T)
    w = np.linalg.eigvalsh(a)
    assert prob.lam_min <= w[0] * (1 + 1e-6) and w[-1] <= prob.lam_max
    us, ts, truth = chip_smoke.traffic(prob, REQUESTS, 0)
    chip_smoke.serve_phase(prob, "pallas", us, ts, truth,
                           max_batch=MAX_BATCH)


def test_four_chip_phase_on_virtual_devices():
    """The --four-chips phase on a 4-device lane mesh of virtual CPU
    devices, against one of them: equal decisions and iterations."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(ROOT / "src")
    code = ("import chip_smoke; chip_smoke.four_chips("
            f"{N}, 0, requests={REQUESTS}, max_batch={MAX_BATCH})")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=900,
                         env=env)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-3000:])
    assert "equal_decisions=True equal_iterations=True" in out.stdout
