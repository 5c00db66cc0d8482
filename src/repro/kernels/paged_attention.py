"""Pallas TPU decode attention over a gathered (compacted) KV buffer.

The top-k page gather happens outside (a sharded XLA gather — on TPU a
scalar-prefetch in-kernel gather buys nothing for this access pattern since
whole pages are contiguous). The kernels stream the compacted KV through
VMEM in (BT, D) tiles with online softmax; q is the (G, D) GQA group,
resident in VMEM for the whole program — this mirrors the paper's
"sink+local in logic-die SRAM" co-design: the hot operand stays on-die
while KV streams past it.

Three entry points (see docs/kernels.md for the full catalog):

  paged_attention          — normalized decode attention (single device).
  paged_attention_partial  — the same online-softmax stream, but emitting
                             the UNNORMALIZED flash partials (m, l, o) a
                             bank/shard contributes under memory-compute
                             co-placement (paper §IV-B). Contract matches
                             kernels.ref.paged_attention_partial_ref.
  combine_partials         — fused cross-bank epilogue: max/rescale/
                             sum/divide over the shard axis in one kernel
                             (the paper's cross-bank softmax merge).

Layout: q (BH, G, D); kv (BH, T, D); valid (BH, 1, T) -> out (BH, G, D),
where BH = B * Hkv. Every block keeps its last two dims TPU-tileable
(equal to the array's, or multiples of (8, 128)): masks are lane rows
behind a unit sublane dim, and m/l partials carry a trailing unit dim.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _stream_tile(q_ref, k_ref, v_ref, valid_ref, m_ref, l_ref, acc_ref, *,
                 bt, seq_t):
    """One (BT, D) KV tile of the online-softmax stream: init on the first
    tile, then masked rescale-and-accumulate into the (m, l, acc) VMEM
    state. Shared by the normalized and partial kernels — only their
    epilogues differ."""
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = ti * bt + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
    inb = rows < seq_t
    k = jnp.where(inb, k_ref[0].astype(jnp.float32), 0.0)   # (BT, D)
    v = jnp.where(inb, v_ref[0].astype(jnp.float32), 0.0)   # (BT, D)
    # key validity stays a lane row: (1, BT) broadcasts over the G rows
    cols = ti * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
    ok = (cols < seq_t) & (valid_ref[0] != 0)                # (1, BT)
    q = q_ref[0].astype(jnp.float32)                         # (G, D)

    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (G, BT)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(ok, p, 0.0)  # all-masked tile: exp(-inf - -inf)=1
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _stream_call(kernel, q, k, v, valid, *, bt, interpret, out_specs,
                 out_shape):
    """Shared pallas_call setup for the KV-streaming decode kernels:
    fold (B, Hkv) into the BH grid axis, tile T by ``bt``, and allocate
    the (m, l, acc) online-softmax scratch."""
    b, hq, d = q.shape
    h_kv, t = k.shape[1], k.shape[2]
    g = hq // h_kv
    qg = q.reshape(b * h_kv, g, d)
    kt = k.reshape(b * h_kv, t, d)
    vt = v.reshape(b * h_kv, t, d)
    # (BH, 1, T): the mask block's last two dims are (1, bt), which the
    # TPU tiling accepts (1 == the array's dim, bt == T or a 128 multiple)
    vd = valid.reshape(b * h_kv, 1, t).astype(jnp.int32)

    bt_ = min(bt, t)
    nt = pl.cdiv(t, bt_)
    return pl.pallas_call(
        functools.partial(kernel, bt=bt_, seq_t=t),
        grid=(b * h_kv, nt),
        in_specs=[
            pl.BlockSpec((1, g, d), lambda bh, ti: (bh, 0, 0)),
            pl.BlockSpec((1, bt_, d), lambda bh, ti: (bh, ti, 0)),
            pl.BlockSpec((1, bt_, d), lambda bh, ti: (bh, ti, 0)),
            pl.BlockSpec((1, 1, bt_), lambda bh, ti: (bh, 0, ti)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        interpret=interpret,
    )(qg, kt, vt, vd)


def _kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bt, seq_t):
    _stream_tile(q_ref, k_ref, v_ref, valid_ref, m_ref, l_ref, acc_ref,
                 bt=bt, seq_t=seq_t)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def paged_attention(q, k, v, valid, *, bt=512, interpret=False):
    """q: (B, Hq, D); k/v: (B, Hkv, T, D); valid: (B, Hkv, T) bool.

    Returns (B, Hq, D). Matches kernels.ref.paged_attention_ref.
    """
    b, hq, d = q.shape
    h_kv = k.shape[1]
    g = hq // h_kv
    out = _stream_call(
        _kernel, q, k, v, valid, bt=bt, interpret=interpret,
        out_specs=pl.BlockSpec((1, g, d), lambda bh, ti: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h_kv, g, d), q.dtype))
    return out.reshape(b, hq, d)


def _partial_kernel(q_ref, k_ref, v_ref, valid_ref, m_out, l_out, o_out,
                    m_ref, l_ref, acc_ref, *, bt, seq_t):
    """Same online-softmax stream as _kernel, but the epilogue emits the
    raw (m, l, acc) accumulator state instead of normalizing — the shard's
    contribution to the cross-bank combine."""
    _stream_tile(q_ref, k_ref, v_ref, valid_ref, m_ref, l_ref, acc_ref,
                 bt=bt, seq_t=seq_t)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _finish():
        m_out[0] = m_ref[...]
        l_out[0] = l_ref[...]
        o_out[0] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def paged_attention_partial(q, k, v, valid, *, bt=512, interpret=False):
    """Partial (unnormalized) decode attention for the cross-shard combine.

    q: (B, Hq, D); k/v: (B, Hkv, T, D); valid: (B, Hkv, T) bool.
    Returns (m, l, o): running max (B, Hq) f32, sumexp (B, Hq) f32,
    numerator (B, Hq, D) f32 — matching
    kernels.ref.paged_attention_partial_ref (all-invalid rows are the
    identity element m=NEG_INF, l=0, o=0).
    """
    b, hq, d = q.shape
    h_kv = k.shape[1]
    g = hq // h_kv
    m, l, o = _stream_call(
        _partial_kernel, q, k, v, valid, bt=bt, interpret=interpret,
        # m/l carry a trailing unit dim: (BH, G, 1) blocks of (1, G, 1)
        # store the (G, 1) scratch as is, with no rank-changing cast
        out_specs=[
            pl.BlockSpec((1, g, 1), lambda bh, ti: (bh, 0, 0)),
            pl.BlockSpec((1, g, 1), lambda bh, ti: (bh, 0, 0)),
            pl.BlockSpec((1, g, d), lambda bh, ti: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h_kv, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b * h_kv, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b * h_kv, g, d), jnp.float32),
        ])
    return m.reshape(b, hq), l.reshape(b, hq), o.reshape(b, hq, d)


def _combine_kernel(m_ref, l_ref, o_ref, out_ref, *, br, n_rows):
    """Fused cross-bank epilogue: global max, rescale, sum, divide.

    m/l arrive as (N, BR, 1) so every reduction runs over the leading
    shard axis and every broadcast is along lanes — no rank change."""
    ri = pl.program_id(0)
    rows = ri * br + jax.lax.broadcasted_iota(jnp.int32, (1, br, 1), 1)
    inb = rows < n_rows                                      # (1, BR, 1)
    m = jnp.where(inb, m_ref[...], NEG_INF)                  # (N, BR, 1)
    l = jnp.where(inb, l_ref[...], 0.0)
    o = jnp.where(inb, o_ref[...], 0.0)                      # (N, BR, D)
    m_g = jnp.max(m, axis=0)                                 # (BR, 1)
    corr = jnp.exp(m - m_g)                                  # (N, BR, 1)
    l_g = jnp.sum(l * corr, axis=0)                          # (BR, 1)
    o_g = jnp.sum(o * corr, axis=0)                          # (BR, D)
    out_ref[...] = o_g / jnp.maximum(l_g, 1e-30)


@functools.partial(jax.jit, static_argnames=("br", "interpret"))
def combine_partials(m, l, o, *, br=128, interpret=False):
    """Fused flash-partial combine over the leading shard axis.

    m/l: (N, B, Hq) f32; o: (N, B, Hq, D) f32 — the stacked per-bank
    partials (e.g. from an all_gather). Returns the combined output
    (B, Hq, D) f32, matching kernels.ref.combine_partials_ref(axis=0).
    """
    n, b_, hq = m.shape
    d = o.shape[-1]
    r = b_ * hq
    mr = m.reshape(n, r, 1)
    lr = l.reshape(n, r, 1)
    orr = o.reshape(n, r, d)

    br_ = min(br, r)
    nr = pl.cdiv(r, br_)
    out = pl.pallas_call(
        functools.partial(_combine_kernel, br=br_, n_rows=r),
        grid=(nr,),
        in_specs=[
            pl.BlockSpec((n, br_, 1), lambda ri: (0, ri, 0)),
            pl.BlockSpec((n, br_, 1), lambda ri: (0, ri, 0)),
            pl.BlockSpec((n, br_, d), lambda ri: (0, ri, 0)),
        ],
        out_specs=pl.BlockSpec((br_, d), lambda ri: (ri, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), jnp.float32),
        interpret=interpret,
    )(mr, lr, orr)
    return out.reshape(b_, hq, d)
