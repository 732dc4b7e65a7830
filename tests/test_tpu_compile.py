"""Ahead-of-time compiles of the main path for a described TPU v5e chip.

Nothing runs: the TPU compiler that ships with jax lowers each program
for a ``v5e:2x2`` topology that is described, not attached, at the sizes
``chip_smoke.py`` serves (N = 16,384, K = 64 lanes, f32). A kernel that
interpret mode accepts but Mosaic refuses (a block not tiled by
(8, 128), more VMEM than a kernel may use, an unparseable op) fails
here instead of on the chip. Every test that expects a kernel asserts a
``tpu_custom_call`` in the compiled HLO, so a silent fallback to the
reference composition fails too.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import BIFSolver, Dense, gql
from repro.kernels import gql_update, lanczos_step, spmv_bell

N = 16384
K = 64
BS = 128
KB = 5          # stored blocks per block row of the sparse operator
M = 64          # reorth basis rows


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def f32_only():
    """The chip runs without x64; the suite's conftest turns it on."""
    with jax.enable_x64(False):
        yield


@pytest.fixture
def spec(one_chip, f32_only):
    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args):
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_bell_matvec_compiles(spec):
    c = _compile(lambda d, c, x: spmv_bell.bell_matvec(d, c, x,
                                                       interpret=False),
                 spec((N // BS, KB, BS, BS)), spec((N // BS, KB), jnp.int32),
                 spec((N,)))
    _assert_kernel(c)


@pytest.mark.parametrize("m", [1, 8, K])
def test_bell_matvec_mrhs_compiles(spec, m):
    c = _compile(lambda d, c, x: spmv_bell.bell_matvec_mrhs(
        d, c, x, interpret=False),
        spec((N // BS, KB, BS, BS)), spec((N // BS, KB), jnp.int32),
        spec((N, m)))
    _assert_kernel(c)


@pytest.mark.parametrize("lanes", [8, K])
def test_gql_update_compiles(spec, lanes):
    c = _compile(lambda *xs: gql_update.gql_update(*xs, interpret=False),
                 *[spec((lanes,)) for _ in range(10)])
    _assert_kernel(c)


def test_fused_step_dense_kernel_compiles(spec):
    c = _compile(lambda a, *vs: lanczos_step.fused_step_dense(
        a, *vs, interpret=False), spec((N, N)), *[spec((K, N))] * 4)
    _assert_kernel(c)


def test_fused_step_bell_kernel_compiles(spec):
    c = _compile(lambda d, c, *vs: lanczos_step.fused_step_bell(
        d, c, *vs, interpret=False),
        spec((N // BS, KB, BS, BS)), spec((N // BS, KB), jnp.int32),
        *[spec((K, N))] * 4)
    _assert_kernel(c)


@pytest.mark.parametrize("with_basis", [False, True],
                         ids=["plain", "reorth"])
def test_fused_gql_step_dense_compiles(spec, with_basis):
    """One whole fused iteration (kernel + XLA tail), with and without a
    reorth basis of M rows per lane."""
    def step(a, u, basis):
        op = Dense(a)
        st = gql.gql_init(op, u, 1e-2, 10.0)
        return lanczos_step.gql_step_fused(op, st, 1e-2, 10.0, basis=basis,
                                           interpret=False)

    basis = spec((K, M, N)) if with_basis else None
    c = _compile(step, spec((N, N)), spec((K, N)), basis)
    _assert_kernel(c)


@pytest.mark.parametrize("backend", ["reference", "fused"])
def test_judge_batch_compiles(spec, backend):
    """The whole batched judge drive at the served size; the fused
    backend must keep its kernel."""
    solver = BIFSolver.create(max_iters=128, rtol=1e-3, backend=backend,
                              pallas_interpret=False)

    def judge(a, u, t):
        return solver.judge_batch(Dense(a), u, t, lam_min=1e-2,
                                  lam_max=10.0).decision

    c = _compile(judge, spec((N, N)), spec((K, N)), spec((K,)))
    if backend == "fused":
        _assert_kernel(c)
    else:
        assert "tpu_custom_call" not in c.as_text()
