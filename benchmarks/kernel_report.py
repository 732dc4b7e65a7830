"""Pallas kernel validation + arithmetic accounting. The kernels run
compiled on a TPU and in interpret mode elsewhere (``kernels.ops``
picks); interpret-mode walls on the CPU are NOT TPU performance, and the
derived column reports the analytic FLOP/byte profile that sizes the
kernels for v5e."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

from .common import row, time_fn


def run(quick: bool = True):
    rows = []
    rng = np.random.default_rng(0)

    b, n = 4, 512
    a = jnp.asarray(rng.standard_normal((b, n, n)), jnp.float32)
    a = (a + jnp.swapaxes(a, -1, -2)) / 2
    x = jnp.asarray(rng.standard_normal((b, n)), jnp.float32)
    y, al = ops.fused_matvec(a, x)
    yr, alr = ref.fused_matvec(a, x)
    # alpha accumulates 512^2 f32 terms tile-wise: looser tolerance
    ok = np.allclose(y, yr, rtol=1e-4, atol=1e-3) \
        and np.allclose(al, alr, rtol=1e-3)
    flops = 2 * b * n * n + 2 * b * n
    bytes_ = 4 * b * n * n
    rows.append(row("pallas_fused_matvec_B4_N512", 0.0,
                    f"valid={ok};flops={flops};bytes={bytes_};"
                    f"intensity={flops/bytes_:.2f};"
                    "fusion saves 1 full pass over A per GQL iter"))

    # block-structured sparsity (banded graph Laplacian): the regime the
    # blocked-ELL layout is built for
    from repro.data import graph_laplacian
    nn = 1024
    m = graph_laplacian(nn, mean_degree=8, rewire=0.0, seed=0)
    data, cols, _ = ops.dense_to_bell(m, bs=64)
    xx = jnp.asarray(rng.standard_normal(data.shape[0] * 64), jnp.float32)
    ok = np.allclose(ops.bell_matvec(data, cols, xx),
                     ref.bell_matvec(data, cols, xx), atol=1e-4)
    nb = int(data.shape[0] * data.shape[1])
    dense_nb = int(data.shape[0] ** 2)
    rows.append(row("pallas_bell_spmv_N1024_banded", 0.0,
                    f"valid={ok};stored_blocks={nb};dense_blocks={dense_nb};"
                    f"flop_saving={dense_nb/max(nb,1):.1f}x"))

    # blocked matvec: ONE gemm over a row-stacked probe block vs b
    # stacked gemvs (the block-Krylov workhorse, DESIGN.md Sec. 13).
    # Dense goes through operators.matvec_mrhs; BELL through the mrhs
    # pallas kernel (column-stacked X rides one pass over the blocks).
    from repro.core import operators as _op
    import jax as _jx
    bw = 8
    ad = jnp.asarray(rng.standard_normal((512, 512)), jnp.float32)
    dop = _op.Dense((ad + ad.T) / 2)
    xb = jnp.asarray(rng.standard_normal((bw, 512)), jnp.float32)
    gemm_fn = _jx.jit(lambda x_: _op.matvec_mrhs(dop, x_))
    gemv_fn = _jx.jit(lambda x_: jnp.stack(
        [dop.matvec(x_[i]) for i in range(bw)]))
    ok = np.allclose(gemm_fn(xb), gemv_fn(xb), rtol=1e-5, atol=1e-4)
    t_gemm, t_gemv = time_fn(gemm_fn, xb), time_fn(gemv_fn, xb)
    a_bytes = 4 * 512 * 512
    rows.append(row("dense_matvec_mrhs_b8_N512", t_gemm * 1e6,
                    f"valid={ok};stacked_gemv_us={t_gemv * 1e6:.2f};"
                    f"a_bytes_gemm={a_bytes};a_bytes_gemv={bw * a_bytes};"
                    "one (b,N)@(N,N) gemm reads A once per block-Lanczos "
                    "iter vs b passes (CPU walls are not accel perf)"))
    xc = jnp.asarray(rng.standard_normal((data.shape[0] * 64, bw)),
                     jnp.float32)
    ym = ops.bell_matvec_mrhs(data, cols, xc)
    ys = jnp.stack([ops.bell_matvec(data, cols, xc[:, i])
                    for i in range(bw)], axis=-1)
    ok = np.allclose(ym, ys, atol=1e-4)
    blk_fl = 2 * nb * 64 * 64 * bw
    rows.append(row("pallas_bell_mrhs_b8_N1024", 0.0,
                    f"valid={ok};flops={blk_fl};"
                    "each stored (bs,bs) block does one (bs,bs)@(bs,b) "
                    "MXU gemm -- b columns ride one block walk"))

    # realizable GQL states from a short real run (not random garbage)
    from repro.core import Dense, gql, lanczos
    from .conftest_shim import make_spd
    bb = 256
    aa = make_spd(96, kappa=200.0, seed=1).astype(np.float32)
    wop = Dense(jnp.broadcast_to(jnp.asarray(aa), (bb, 96, 96)))
    uu = jnp.asarray(rng.standard_normal((bb, 96)), jnp.float32)
    wv = np.linalg.eigvalsh(aa)
    lmn, lmx = float(wv[0] * 0.9), float(wv[-1] * 1.1)
    stt = gql.gql_init(wop, uu, lmn, lmx)
    lz1 = lanczos.lanczos_step(wop, stt.lz)
    out = ops.gql_update(lz1.alpha, lz1.beta, lz1.beta_prev, stt.g, stt.c,
                         stt.delta, stt.delta_lr, stt.delta_rr, lmn, lmx)
    outr = ref.gql_update(lz1.alpha, lz1.beta, lz1.beta_prev, stt.g, stt.c,
                          stt.delta, stt.delta_lr, stt.delta_rr,
                          jnp.float32(lmn), jnp.float32(lmx))
    ok = all(np.allclose(a_, b_, rtol=1e-5) for a_, b_ in zip(out, outr))
    rows.append(row("pallas_gql_update_B256", 0.0,
                    f"valid={ok};fuses 8 elementwise lane-ops -> 1 VPU pass"))

    # the fused step vs the reference composition: w = A v and alpha in
    # one pallas_call, the Lanczos/recurrence tail fused by XLA
    # (DESIGN.md Sec. 11)
    import jax as _jax
    st2 = gql.gql_step(wop, stt, lmn, lmx)  # one real step in
    fused_fn = _jax.jit(lambda s: ops.gql_step_fused(wop, s, lmn, lmx))
    ref_fn = _jax.jit(lambda s: gql.gql_step(wop, s, lmn, lmx))
    got, want = fused_fn(st2), ref_fn(st2)
    ok = all(np.allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                         atol=1e-6)
             for g, w in zip(_jax.tree.leaves(got), _jax.tree.leaves(want))
             if np.asarray(w).dtype.kind == "f")
    t_fused = time_fn(fused_fn, st2)
    t_ref = time_fn(ref_fn, st2)
    # per iteration the fused step produces alpha in the matvec's
    # epilogue; the composition pays one extra HBM round-trip over the
    # (B, N) vectors for it
    fl = 2 * bb * 96 * 96 + 10 * bb * 96
    extra_hbm = 2 * 4 * bb * 96
    rows.append(row("pallas_fused_step_B256_N96", t_fused * 1e6,
                    f"valid={ok};ref_us={t_ref * 1e6:.2f};"
                    f"flops={fl};vector_hbm_saved={extra_hbm};"
                    "w and alpha in one pallas_call "
                    "(interpret-mode walls off the TPU)"))

    q = jnp.asarray(rng.standard_normal((4, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((4, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((4, 256, 64)), jnp.float32)
    o = ops.flash_attention(q, k, v, causal=True, bt=64, bs=64)
    ok = np.allclose(o, ref.flash_attention(q, k, v, causal=True),
                     rtol=1e-4, atol=1e-4)
    fl = 4 * 4 * 256 * 256 * 64
    hbm = 4 * (3 * 4 * 256 * 64 + 4 * 256 * 64)
    rows.append(row("pallas_flash_attn_BH4_T256_D64", 0.0,
                    f"valid={ok};flops={fl};hbm_bytes={hbm};"
                    f"intensity={fl/hbm:.0f} (vs ~8 unfused)"))
    return rows, {}
