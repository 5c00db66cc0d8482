"""Pallas TPU chunked-prefill attention.

Two entry points (see docs/kernels.md for the full catalog):

  chunk_attention        — multi-query attention over a gathered KV buffer
                           with PER-QUERY validity: the (Cq, T) masks the
                           caller derives from absolute positions. The
                           whole chunk's queries stay resident in VMEM as
                           G (Cq, D) blocks, one per GQA group member,
                           each masked by the same (Cq, BT) tile, while
                           KV streams past in (BT, D) tiles.
  chunk_attention_paged  — the same online-softmax stream with the page
                           gather FUSED into the kernel: instead of a
                           materialized buffer + (B, H, Cq, T) mask, the
                           grid walks (KV tiles of the paged buffer...,
                           chunk). Pre-append cache keys need only per-KEY
                           validity (every buffered key precedes every
                           chunk query), a (1, T) row derived from
                           page_start, and the intra-chunk phase needs only
                           a STATIC causal mask built in-kernel — no
                           per-query mask ever hits HBM.

Both reuse the (m, l, acc) online-softmax contract of
paged_attention._stream_tile: init at the first tile, masked
rescale-and-accumulate per tile, normalize in the last tile's epilogue
(all-invalid rows yield 0 via the l = max(l, 1e-30) guard).

Layout: BH = B*Hkv. chunk_attention folds q to (BH, G, Cq, D);
chunk_attention_paged to (BH, Cq*G, D) with row r = c*G + g.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _accumulate(s, ok, v, m_ref, l_ref, acc_ref):
    """One masked rescale-and-accumulate step of the online softmax.

    s: (R, T) logits already NEG_INF-masked; ok: bool broadcastable to
    (R, T); v: (T, D) f32. Updates the (m, l, acc) VMEM state in place.
    """
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(ok, p, 0.0)  # all-masked tile: exp(-inf - -inf) = 1
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


# (rows x BT) f32 logits per KV tile: 2 MiB keeps a tile's logits,
# probabilities and masks inside the TPU's default 16 MiB scoped VMEM
_TILE_ELEMS = 1 << 19


def _kv_tile(bt: int, t: int, rows: int) -> int:
    """KV tile length: ``bt``, shrunk in 128-lane steps for tall query
    blocks (long chunks) so a (rows, BT) tile stays within
    ``_TILE_ELEMS``."""
    cap = max(128, _TILE_ELEMS // rows // 128 * 128)
    return min(bt, t, cap)


def _chunk_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_ref, l_ref,
                  acc_ref, *, bt, seq_t, group):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = ti * bt + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
    inb = rows < seq_t                                       # (BT, 1)
    k = jnp.where(inb, k_ref[0].astype(jnp.float32), 0.0)    # (BT, D)
    v = jnp.where(inb, v_ref[0].astype(jnp.float32), 0.0)
    cols = ti * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
    ok = (valid_ref[0] != 0) & (cols < seq_t)                # (Cq, BT)
    scale = 1.0 / jnp.sqrt(jnp.asarray(k.shape[-1], jnp.float32))
    # one (Cq, D) query block per GQA group member: the per-query mask
    # applies to each as is, never expanded over the group
    for g in range(group):
        q = q_ref[0, g].astype(jnp.float32)                  # (Cq, D)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = jnp.where(ok, s, NEG_INF)
        _accumulate(s, ok, v, m_ref.at[g], l_ref.at[g], acc_ref.at[g])

    @pl.when(ti == pl.num_programs(1) - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def chunk_attention(q, k, v, valid, *, bt=512, interpret=False):
    """q: (B, Cq, Hq, D); k/v: (B, Hkv, T, D); valid: (B, Hkv, Cq, T).

    Returns (B, Cq, Hq, D). Matches kernels.ref.chunk_attention_ref
    (all-invalid rows yield 0).
    """
    b, cq, hq, d = q.shape
    h_kv, t = k.shape[1], k.shape[2]
    g = hq // h_kv
    # (BH, G, Cq, D): the group is a leading (untiled) dim of the block
    qg = q.reshape(b, cq, h_kv, g, d).transpose(0, 2, 3, 1, 4)
    qg = qg.reshape(b * h_kv, g, cq, d)
    kt = k.reshape(b * h_kv, t, d)
    vt = v.reshape(b * h_kv, t, d)
    vd = valid.reshape(b * h_kv, cq, t).astype(jnp.int32)

    bt_ = _kv_tile(bt, t, cq)
    nt = pl.cdiv(t, bt_)
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, bt=bt_, seq_t=t, group=g),
        grid=(b * h_kv, nt),
        in_specs=[
            pl.BlockSpec((1, g, cq, d), lambda bh, ti: (bh, 0, 0, 0)),
            pl.BlockSpec((1, bt_, d), lambda bh, ti: (bh, ti, 0)),
            pl.BlockSpec((1, bt_, d), lambda bh, ti: (bh, ti, 0)),
            pl.BlockSpec((1, cq, bt_), lambda bh, ti: (bh, 0, ti)),
        ],
        out_specs=pl.BlockSpec((1, g, cq, d), lambda bh, ti: (bh, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h_kv, g, cq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, cq, 1), jnp.float32),
            pltpu.VMEM((g, cq, 1), jnp.float32),
            pltpu.VMEM((g, cq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qg, kt, vt, vd)
    out = out.reshape(b, h_kv, g, cq, d).transpose(0, 3, 1, 2, 4)
    return out.reshape(b, cq, hq, d)


def _paged_kernel(q_ref, kp_ref, vp_ref, ok_ref, kn_ref, vn_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, bt, seq_t, npt, cq, group):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                         # (Cq*G, D)
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))

    @pl.when(ti < npt)
    def _pages():
        # fused gather: the pages stream straight from the paged buffer.
        # Every buffered key precedes every chunk query (pos < start), so
        # the mask is per-KEY — one (1, BT) lane row, no Cq axis.
        rows = ti * bt + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
        inb = rows < seq_t
        cols = ti * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
        ok = (cols < seq_t) & (ok_ref[0] != 0)               # (1, BT)
        k = jnp.where(inb, kp_ref[0].astype(jnp.float32), 0.0)
        v = jnp.where(inb, vp_ref[0].astype(jnp.float32), 0.0)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = jnp.where(ok, s, NEG_INF)
        _accumulate(s, ok, v, m_ref, l_ref, acc_ref)

    @pl.when(ti == npt)
    def _chunk():
        # intra-chunk phase: STATIC causal mask — key j valid for query
        # row r = c*G + g iff j <= c.
        k = kn_ref[0].astype(jnp.float32)                    # (Cq, D)
        v = vn_ref[0].astype(jnp.float32)
        # (j <= r // G  <=>  j * G <= r, with no vector division)
        rows = jax.lax.broadcasted_iota(jnp.int32, (cq * group, cq), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (cq * group, cq), 1)
        ok = cols * group <= rows
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = jnp.where(ok, s, NEG_INF)
        _accumulate(s, ok, v, m_ref, l_ref, acc_ref)

    @pl.when(ti == npt)
    def _finish():
        # every query row attends at least itself, so l > 0; keep the
        # guard anyway to match the shared epilogue contract
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def chunk_attention_paged(q, k_pages, v_pages, page_start, start, k_new,
                          v_new, *, bt=512, interpret=False):
    """Chunked-prefill retrieval attention with the page gather fused.

    q: (B, Cq, Hq, D); k_pages/v_pages: (B, Hr, C, P, D) — the PRE-append
    paged buffer; page_start: (B, Hr, C) absolute position of each page's
    first token (-1 = unwritten); start: (B,) tokens already admitted;
    k_new/v_new: (B, Cq, Hr, D) the chunk's own keys/values (roped,
    kv-head order). Returns (B, Cq, Hq, D). Matches
    kernels.ref.chunk_attention_paged_ref.
    """
    b, cq, hq, d = q.shape
    hr, c, page = k_pages.shape[1:4]
    g = hq // hr
    bh = b * hr
    qg = q.reshape(b, cq, hr, g, d).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(bh, cq * g, d)
    t = c * page
    kp = k_pages.reshape(bh, t, d)
    vp = v_pages.reshape(bh, t, d)
    # per-key validity from page metadata: written pages, keys before
    # the chunk. A (BH, 1, T) lane row — 1/Cq of a per-query mask.
    ps = page_start.reshape(bh, c, 1).astype(jnp.int32)
    pos = (ps + jnp.arange(page, dtype=jnp.int32)).reshape(bh, 1, t)
    st = jnp.repeat(jnp.asarray(start, jnp.int32).reshape(b), hr)
    written = jnp.broadcast_to(ps >= 0, (bh, c, page)).reshape(bh, 1, t)
    ok = (written & (pos < st[:, None, None])).astype(jnp.int32)
    kn = k_new.transpose(0, 2, 1, 3).reshape(bh, cq, d)
    vn = v_new.transpose(0, 2, 1, 3).reshape(bh, cq, d)

    bt_ = _kv_tile(bt, t, cq * g)
    npt = pl.cdiv(t, bt_)
    last = npt - 1
    out = pl.pallas_call(
        functools.partial(_paged_kernel, bt=bt_, seq_t=t, npt=npt, cq=cq,
                          group=g),
        grid=(bh, npt + 1),
        in_specs=[
            pl.BlockSpec((1, cq * g, d), lambda bh_, ti: (bh_, 0, 0)),
            pl.BlockSpec((1, bt_, d),
                         lambda bh_, ti: (bh_, jnp.minimum(ti, last), 0)),
            pl.BlockSpec((1, bt_, d),
                         lambda bh_, ti: (bh_, jnp.minimum(ti, last), 0)),
            pl.BlockSpec((1, 1, bt_),
                         lambda bh_, ti: (bh_, 0, jnp.minimum(ti, last))),
            pl.BlockSpec((1, cq, d), lambda bh_, ti: (bh_, 0, 0)),
            pl.BlockSpec((1, cq, d), lambda bh_, ti: (bh_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, cq * g, d), lambda bh_, ti: (bh_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, cq * g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((cq * g, 1), jnp.float32),
            pltpu.VMEM((cq * g, 1), jnp.float32),
            pltpu.VMEM((cq * g, d), jnp.float32),
        ],
        interpret=interpret,
    )(qg, kp, vp, ok, kn, vn)
    out = out.reshape(b, hr, cq, g, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, cq, hq, d)
