"""Pallas TPU flash attention (prefill): causal + sliding-window + sink.

TPU-native design: the KQᵀ tiles are MXU-shaped (BQ×BK = 128×128 default),
online-softmax state (m, l, acc) lives in VMEM scratch and persists across
the innermost (k-block) grid dimension — the TPU grid is executed
sequentially minor-to-major, which replaces the CUDA-style intra-kernel
loop. The sink/window masks make this the single kernel for full causal
attention, streaming-head attention (window+sink), and gemma3 local layers
(window only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal, window, sink, q_offset, bq, bk, seq_q, seq_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # mask out-of-bounds block padding (its contents are unspecified)
    q_rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    k_rows = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
    q = jnp.where(q_rows < seq_q, q_ref[0].astype(jnp.float32), 0.0)  # (BQ, D)
    k = jnp.where(k_rows < seq_k, k_ref[0].astype(jnp.float32), 0.0)  # (BK, D)
    v = jnp.where(k_rows < seq_k, v_ref[0].astype(jnp.float32), 0.0)  # (BK, D)
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (BQ,BK)

    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = cols < seq_k
    if causal:
        mask &= cols <= rows
    if window > 0:
        w = cols > (rows - window)
        if sink > 0:
            w |= cols < sink
        mask &= w
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                          # (BQ, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                       # (BQ, BK)
    p = jnp.where(mask, p, 0.0)  # all-masked row: exp(-inf - -inf) = 1
    corr = jnp.exp(m_prev - m_new)               # (BQ, 1)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "sink", "q_offset", "bq", "bk",
                     "interpret"),
)
def flash_attention(q, k, v, *, causal=True, window=0, sink=0, q_offset=0,
                    bq=128, bk=128, interpret=False):
    """q: (B, Sq, Hq, D); k/v: (B, Sk, Hkv, D) -> (B, Sq, Hq, D)."""
    b, sq, hq, d = q.shape
    sk_len = k.shape[1]
    h_kv = k.shape[2]
    group = hq // h_kv

    # layout: fold heads into batch; kv heads repeated per group
    qt = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    kt = jnp.repeat(k.transpose(0, 2, 1, 3), group, axis=1).reshape(b * hq, sk_len, d)
    vt = jnp.repeat(v.transpose(0, 2, 1, 3), group, axis=1).reshape(b * hq, sk_len, d)

    bq_ = min(bq, sq)
    bk_ = min(bk, sk_len)
    nq = pl.cdiv(sq, bq_)
    nk = pl.cdiv(sk_len, bk_)
    grid = (b * hq, nq, nk)

    out = pl.pallas_call(
        functools.partial(_kernel, causal=causal, window=window, sink=sink,
                          q_offset=q_offset, bq=bq_, bk=bk_, seq_q=sq,
                          seq_k=sk_len),
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq_, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk_, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk_, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_, 1), jnp.float32),   # m
            pltpu.VMEM((bq_, 1), jnp.float32),   # l
            pltpu.VMEM((bq_, d), jnp.float32),   # acc
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.reshape(b, hq, sq, d).transpose(0, 2, 1, 3)
