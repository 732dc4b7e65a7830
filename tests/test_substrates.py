"""Data pipeline, DPP selection, compression, serving, KV select,
spectrum, preconditioning, configs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.configs import get_arch, list_archs
from repro.core import BIFSolver, Dense, lanczos_extremal
from repro.data import (DataConfig, DPPBatchStream, DPPSelector,
                        TokenStream, density, graph_laplacian, rbf_kernel)
from repro.models import model as M
from repro.optim import compression
from repro.serve import Engine, Request, rank_blocks, select_diverse_blocks
from conftest import make_spd


# ---------------------------------------------------------------- data
def test_stream_deterministic_and_host_disjoint():
    dc = DataConfig(vocab=100, seq_len=16, global_batch=4)
    s0 = TokenStream(dc, host_id=0, num_hosts=2)
    s1 = TokenStream(dc, host_id=1, num_hosts=2)
    a, b = s0.batch_at(3), s0.batch_at(3)
    np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                  np.asarray(b["tokens"]))
    assert not np.array_equal(np.asarray(s0.batch_at(3)["tokens"]),
                              np.asarray(s1.batch_at(3)["tokens"]))
    assert int(a["tokens"].max()) < 100
    # labels are next-token shifted
    full = s0.batch_at(0)
    assert full["tokens"].shape == (2, 16)


def test_kernel_builders_are_pd_and_sparse():
    k = rbf_kernel(80, sigma=0.3)
    assert density(k) < 0.9
    assert np.linalg.eigvalsh(k)[0] > 0
    lap = graph_laplacian(100, mean_degree=8)
    assert density(lap) < 0.2
    assert np.linalg.eigvalsh(lap)[0] > 0


def test_dpp_batch_selection():
    dc = DataConfig(vocab=500, seq_len=24, global_batch=4, selector="dpp")
    stream = DPPBatchStream(TokenStream(dc),
                            DPPSelector(pool_factor=3, steps_per_item=3))
    b = stream.batch_at(0)
    assert b["tokens"].shape == (4, 24)
    st = stream.selector.last_stats
    assert st["uncertified"] == 0
    assert st["quad_iterations"] > 0


# ---------------------------------------------------------- compression
def test_quantize_roundtrip_error():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(1000), jnp.float32)
    q, s = compression.quantize_int8(x)
    err = np.abs(np.asarray(compression.dequantize(q, s)) - np.asarray(x))
    assert err.max() <= float(s) * 0.5 + 1e-7


def test_error_feedback_psum_converges():
    """With EF, repeated compressed reductions track the true mean."""
    mesh = jax.make_mesh((1,), ("d",))
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.standard_normal(256), jnp.float32)

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def step(gg, res):
        return compression.compressed_psum(gg, "d", res)

    f = shard_map(step, mesh=mesh, in_specs=(P(), P()),
                  out_specs=(P(), P()))
    res = jnp.zeros_like(g)
    acc = jnp.zeros_like(g)
    for _ in range(20):
        out, res = f(g, res)
        acc = acc + out
    # average of EF-compressed reductions converges to the true value
    np.testing.assert_allclose(np.asarray(acc / 20), np.asarray(g),
                               atol=2e-3)


# -------------------------------------------------------------- serving
def test_engine_greedy_matches_manual():
    cfg = get_arch("stablelm-1.6b").reduced()
    params, _ = M.init_model(jax.random.key(0), cfg)
    eng = Engine(cfg, params, max_batch=2, max_seq=64)
    prompt = np.arange(5, 13, dtype=np.int32)
    [r] = eng.generate([Request(prompt=prompt, max_new_tokens=4)])
    # manual greedy decode
    caches = M.make_caches(cfg, 1, 64, jnp.float32)
    caches, logits = M.prefill(cfg, params, {"tokens": prompt[None]},
                               caches)
    toks = []
    tok = int(jnp.argmax(logits[0, -1]))
    toks.append(tok)
    for i in range(3):
        dec = {"tokens": jnp.asarray([[tok]], jnp.int32),
               "position": jnp.asarray([len(prompt) + i], jnp.int32)}
        caches, logits = M.decode_step(cfg, params, caches, dec)
        tok = int(jnp.argmax(logits[0, -1]))
        toks.append(tok)
    assert r.out_tokens.tolist() == toks


def test_kv_select_diversity():
    rng = np.random.default_rng(0)
    # two clusters of keys: diverse selection should cover both
    c1 = rng.standard_normal((512, 16)) * 0.05 + 1.0
    c2 = rng.standard_normal((512, 16)) * 0.05 - 1.0
    keys = np.concatenate([c1, c2]).astype(np.float32)
    mask, stats = select_diverse_blocks(keys, block=64)
    assert stats["uncertified"] == 0
    half = len(mask) // 2
    assert mask[:half].sum() >= 1 and mask[half:].sum() >= 1


def test_kv_rank_blocks_flags_near_duplicate_as_redundant():
    rng = np.random.default_rng(3)
    block, n, d = 4, 6, 8
    dirs = rng.standard_normal((n, d))
    dirs[-1] = dirs[0] + 0.01 * rng.standard_normal(d)  # near-duplicate pair
    keys = np.repeat(dirs, block, axis=0).astype(np.float32)
    keys += 0.001 * rng.standard_normal(keys.shape).astype(np.float32)
    order, stats = rank_blocks(keys, block=block, max_batch=4)
    # one of the duplicated pair must rank most redundant, and the pair's
    # leverage scores must clearly separate from the distinct blocks'
    assert order[0] in (0, n - 1)
    mids = np.array([0.5 * (lo + hi) for lo, hi in stats["brackets"]])
    rest = [i for i in range(1, n - 1)]
    assert min(mids[0], mids[-1]) > mids[rest].max() + 0.1


# ---------------------------------------------- spectrum / preconditioning
@given(seed=st.integers(0, 50), kappa=st.floats(5.0, 1e4))
def test_lanczos_extremal_brackets(seed, kappa):
    n = 40
    a = make_spd(n, kappa=kappa, seed=seed)
    w = np.linalg.eigvalsh(a)
    probe = np.random.default_rng(seed).standard_normal(n)
    est = lanczos_extremal(Dense(jnp.asarray(a)), jnp.asarray(probe),
                           num_iters=min(n, 24))
    assert float(est.lam_max) >= w[-1] * (1 - 1e-6)
    assert float(est.lam_min) <= w[0] + 1e-6
    assert float(est.lam_min) > 0


def test_preconditioning_reduces_iterations():
    """Sec. 5.4: Jacobi transform cuts iterations on badly scaled A."""
    n = 100
    rng = np.random.default_rng(0)
    d = np.geomspace(1e-3, 1e3, n)
    base = make_spd(n, kappa=10.0, seed=1)
    a = np.diag(np.sqrt(d)) @ base @ np.diag(np.sqrt(d))
    w = np.linalg.eigvalsh(a)
    u = rng.standard_normal(n)
    true = u @ np.linalg.solve(a, u)
    plain = BIFSolver.create(max_iters=n, rtol=1e-4).solve(
        Dense(jnp.asarray(a)), jnp.asarray(u),
        lam_min=float(w[0] * 0.99), lam_max=float(w[-1] * 1.01))
    pre = BIFSolver.create(max_iters=n, rtol=1e-4, precondition="jacobi",
                           spectrum="lanczos").solve(
        Dense(jnp.asarray(a)), jnp.asarray(u))
    assert int(pre.iterations) < int(plain.iterations)
    assert float(pre.lower) <= true * 1.001
    assert float(pre.upper) >= true * 0.999


# -------------------------------------------------------------- configs
def test_registry_complete():
    assert len(list_archs()) == 10


@pytest.mark.parametrize("name,target_b", [
    ("llama3-405b", 405), ("command-r-plus-104b", 104),
    ("arctic-480b", 480), ("llama4-maverick-400b-a17b", 400),
    ("falcon-mamba-7b", 7), ("olmo-1b", 1.2), ("stablelm-1.6b", 1.6),
    ("zamba2-1.2b", 1.2), ("qwen2-vl-2b", 2), ("whisper-medium", 0.77)])
def test_param_counts_match_names(name, target_b):
    c = get_arch(name)
    got = c.param_count() / 1e9
    assert 0.6 * target_b <= got <= 1.35 * target_b, (name, got)


def test_reduced_preserves_family():
    for n in list_archs():
        c = get_arch(n)
        r = c.reduced()
        assert r.family == c.family
        assert r.d_model <= 64
        if c.moe_experts:
            assert 0 < r.moe_experts <= 4
