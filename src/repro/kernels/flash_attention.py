"""Flash-style streaming attention (forward) — beyond-paper kernel.

Used by the serving path and the prefill hillclimb (EXPERIMENTS.md
Sec. Perf): online-softmax attention that streams K/V tiles through VMEM,
never materializing the (T, S) score matrix in HBM.

Layout: q (BH, T, D), k/v (BH, S, D); GQA is handled by the wrapper
(kv heads repeated to q heads before flattening). Causal masking uses
global row/col indices; padded key tail (S_pad > s_len) is masked the
same way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc, m_i, l_i, *, scale, causal,
            s_len, bt, bs):
    t = pl.program_id(1)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_i[...] = jnp.full_like(m_i, _NEG_INF)
        l_i[...] = jnp.zeros_like(l_i)

    q = q_ref[0].astype(jnp.float32)            # (bt, d)
    k = k_ref[0].astype(jnp.float32)            # (bs, d)
    v = v_ref[0].astype(jnp.float32)            # (bs, d)
    qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale

    cols = s * bs + jax.lax.broadcasted_iota(jnp.int32, (bt, bs), 1)
    valid = cols < s_len
    if causal:
        rows = t * bt + jax.lax.broadcasted_iota(jnp.int32, (bt, bs), 0)
        valid = valid & (cols <= rows)
    qk = jnp.where(valid, qk, _NEG_INF)

    m_new = jnp.maximum(m_i[...], jnp.max(qk, axis=1, keepdims=True))
    p = jnp.exp(qk - m_new)
    alpha = jnp.exp(m_i[...] - m_new)
    l_i[...] = l_i[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc[...] = acc[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_i[...] = m_new

    @pl.when(s == pl.num_programs(2) - 1)
    def _():
        o_ref[0] = (acc[...] / jnp.maximum(l_i[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bt", "bs", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, bt: int = 128, bs: int = 128,
                    interpret: bool = True) -> jax.Array:
    """q: (BH, T, D); k, v: (BH, S, D) -> (BH, T, D)."""
    bh, t_len, d = q.shape
    _, s_len, _ = k.shape
    scale = 1.0 / (d ** 0.5)
    bt = min(bt, t_len)
    bs = min(bs, s_len)
    tp = -t_len % bt
    sp = -s_len % bs
    if tp:
        q = jnp.pad(q, ((0, 0), (0, tp), (0, 0)))
    if sp:
        k = jnp.pad(k, ((0, 0), (0, sp), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sp), (0, 0)))
    tt, ss = t_len + tp, s_len + sp
    grid = (bh, tt // bt, ss // bs)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, s_len=s_len,
                          bt=bt, bs=bs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, d), lambda b, t, s: (b, t, 0)),
            pl.BlockSpec((1, bs, d), lambda b, t, s: (b, s, 0)),
            pl.BlockSpec((1, bs, d), lambda b, t, s: (b, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, d), lambda b, t, s: (b, t, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tt, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bt, d), jnp.float32),
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :t_len, :]


def mha_flash(q, k, v, *, causal=True, interpret=True, bt=128, bs=128):
    """Convenience multi-head wrapper: q (B, T, H, D), k/v (B, S, Hkv, D);
    repeats kv heads for GQA and flattens (B, H)."""
    b, t, h, d = q.shape
    _, s, hkv, _ = k.shape
    if hkv != h:
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    of = flash_attention(qf, kf, vf, causal=causal, interpret=interpret,
                         bt=bt, bs=bs)
    return of.reshape(b, h, t, d).transpose(0, 2, 1, 3)
