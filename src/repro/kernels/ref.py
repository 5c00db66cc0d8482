"""Pure-jnp reference oracles for every Pallas kernel.

These are also the implementation used when lowering for non-TPU backends
(the multi-pod dry-run lowers these; XLA's cost model sees native HLO).
Shapes use the conventions:

  q  (prefill): (B, S, Hq, D)      q (decode): (B, Hq, D)
  k/v (prefill): (B, S, Hkv, D)    gathered kv (decode): (B, Hkv, T, D)

GQA is handled by broadcasting each kv head over its group of q heads.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

NEG_INF = -1e30


def _gqa_expand(x: Array, n_q_heads: int) -> Array:
    """(B, ..., Hkv, ...) -> repeat kv heads to match q heads on axis 2."""
    h_kv = x.shape[2]
    group = n_q_heads // h_kv
    if group == 1:
        return x
    return jnp.repeat(x, group, axis=2)


# ---------------------------------------------------------------------------
# Prefill attention (causal, optional sliding window + attention sinks)
# ---------------------------------------------------------------------------


CHUNK_THRESHOLD = 2048  # switch to the scan-over-q-chunks form above this
Q_CHUNK = 1024


def flash_attention_ref(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = True,
    window: int = 0,
    sink: int = 0,
    q_offset: int = 0,
) -> Array:
    """Reference attention.

    q: (B, Sq, Hq, D), k/v: (B, Sk, Hkv, D). window>0 keeps j in
    (i-window, i]; sink>0 additionally keeps j < sink (StreamingLLM).
    q_offset: absolute position of q[0] (for chunked prefill).
    Returns (B, Sq, Hq, D).

    For long sequences this dispatches to a chunked form (exact; scan over
    q blocks) so the S×S logits are never materialized — the pure-jnp path
    stays usable at 32k–500k for the dry-run and its HLO reflects the
    FLOPs/bytes a production kernel would do (window layers slice K to the
    window span instead of masking the full row).
    """
    sq, sk = q.shape[1], k.shape[1]
    if sq > CHUNK_THRESHOLD and sq % Q_CHUNK == 0:
        return _flash_attention_ref_chunked(
            q, k, v, causal=causal, window=window, sink=sink,
            q_offset=q_offset)
    return _flash_attention_ref_dense(
        q, k, v, causal=causal, window=window, sink=sink, q_offset=q_offset)


def _flash_attention_ref_dense(q, k, v, *, causal, window, sink, q_offset):
    b, sq, hq, d = q.shape
    sk = k.shape[1]
    k = _gqa_expand(k, hq)
    v = _gqa_expand(v, hq)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    # keep K/V in storage dtype; accumulate in f32 via the MXU
    # (an .astype(f32) here would be hoisted through gathers by XLA and
    # materialize whole caches in f32 — see EXPERIMENTS.md §Perf)
    logits = jnp.einsum("bihd,bjhd->bhij", q.astype(k.dtype), k,
                        preferred_element_type=jnp.float32) * scale
    i = jnp.arange(sq)[:, None] + q_offset
    j = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= j <= i
    if window > 0:
        win = j > (i - window)
        if sink > 0:
            win |= j < sink
        mask &= win
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhij,bjhd->bihd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _flash_attention_ref_chunked(q, k, v, *, causal, window, sink, q_offset):
    """Exact attention, scanning over q chunks of Q_CHUNK.

    Full-attention layers: each chunk sees K[:, :chunk_end] via masking of
    the full K (XLA DCE can't trim a traced slice per-iteration, so the
    cost model charges the causal-full quadratic — correct for roofline).
    Window layers: each chunk slices K to [start-window, end) + sink block,
    so local layers cost O(S·window), not O(S²).
    """
    from repro.runtime import hints

    b, sq, hq, d = q.shape
    sk = k.shape[1]
    kx = hints.attn_kv(_gqa_expand(k, hq))
    vx = hints.attn_kv(_gqa_expand(v, hq))
    nq = sq // Q_CHUNK
    qc = q.astype(k.dtype).reshape(b, nq, Q_CHUNK, hq, d)
    # sequence-parallel attention: balanced for any head count (see
    # runtime/hints.py; no-op outside a mesh context)
    qc = hints.attn_q_chunks(qc)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))

    if window > 0:
        span = Q_CHUNK + window  # static k-slice width per chunk
        # left-pad K/V by `window` so the slice never goes negative
        kpad = jnp.pad(kx, ((0, 0), (window, 0), (0, 0), (0, 0)))
        vpad = jnp.pad(vx, ((0, 0), (window, 0), (0, 0), (0, 0)))

        def chunk_fn(_, ci):
            qi = qc[:, ci]                                  # (B,CQ,H,D)
            start = ci * Q_CHUNK
            ipos = q_offset + start + jnp.arange(Q_CHUNK)   # q positions
            # keys [start+q_offset-window, start+q_offset+CQ) -> padded
            # slice starting at start+q_offset
            kw = jax.lax.dynamic_slice_in_dim(kpad, start + q_offset, span, 1)
            vw = jax.lax.dynamic_slice_in_dim(vpad, start + q_offset, span, 1)
            jpos = (start + q_offset - window) + jnp.arange(span)
            logits = jnp.einsum("bihd,bjhd->bhij", qi, kw,
                                preferred_element_type=jnp.float32) * scale
            m = (jpos[None, :] <= ipos[:, None])            # causal
            m &= jpos[None, :] > (ipos[:, None] - window)   # window
            m &= (jpos >= 0)[None, :]                       # pad
            logits = jnp.where(m[None, None], logits, NEG_INF)
            if sink > 0:
                ls = jnp.einsum("bihd,bjhd->bhij", qi, kx[:, :sink],
                                preferred_element_type=jnp.float32) * scale
                spos = jnp.arange(sink)
                # sink attended iff causal AND not already in the window
                ms = (spos[None, :] <= ipos[:, None]) & \
                     (spos[None, :] <= (ipos[:, None] - window))
                ls = jnp.where(ms[None, None], ls, NEG_INF)
                logits = jnp.concatenate([ls, logits], axis=-1)
                vw = jnp.concatenate([vx[:, :sink], vw], axis=1)
            p = jax.nn.softmax(logits, axis=-1)
            out = jnp.einsum("bhij,bjhd->bihd", p.astype(vw.dtype), vw,
                             preferred_element_type=jnp.float32)
            return None, out

        _, outs = jax.lax.scan(chunk_fn, None, jnp.arange(nq))
    else:
        jpos = jnp.arange(sk)

        def chunk_fn(_, ci):
            qi = qc[:, ci]
            ipos = q_offset + ci * Q_CHUNK + jnp.arange(Q_CHUNK)
            logits = jnp.einsum("bihd,bjhd->bhij", qi, kx,
                                preferred_element_type=jnp.float32) * scale
            if causal:
                m = jpos[None, :] <= ipos[:, None]
                logits = jnp.where(m[None, None], logits, NEG_INF)
            p = jax.nn.softmax(logits, axis=-1)
            out = jnp.einsum("bhij,bjhd->bihd", p.astype(vx.dtype), vx,
                             preferred_element_type=jnp.float32)
            return None, out

        _, outs = jax.lax.scan(chunk_fn, None, jnp.arange(nq))
    # outs: (nq, B, CQ, H, D) -> (B, S, H, D)
    outs = hints.attn_out(outs)
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, hq, d)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention over a gathered (compacted) KV buffer with validity mask
# ---------------------------------------------------------------------------


def paged_attention_ref(
    q: Array,
    k: Array,
    v: Array,
    valid: Array,
) -> Array:
    """q: (B, Hq, D); k/v: (B, Hkv, T, D); valid: (B, Hkv, T) bool.

    Computes softmax(q·kᵀ)·v over valid positions. Returns (B, Hq, D).
    """
    b, hq, d = q.shape
    h_kv = k.shape[1]
    group = hq // h_kv
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    qg = q.reshape(b, h_kv, group, d).astype(k.dtype)
    logits = jnp.einsum("bhgd,bhtd->bhgt", qg, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(valid[:, :, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    # guard the all-invalid case (empty context): softmax of all -inf
    any_valid = jnp.any(valid, axis=-1)[:, :, None, None]
    p = jnp.where(any_valid, p, 0.0)
    out = jnp.einsum("bhgt,bhtd->bhgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, hq, d).astype(q.dtype)


def chunk_attention_ref(q: Array, k: Array, v: Array, valid: Array) -> Array:
    """Multi-query attention over a gathered KV buffer (chunked prefill).

    q: (B, Cq, Hq, D) — one chunk of queries per slot; k/v:
    (B, Hkv, T, D); valid: (B, Hkv, Cq, T) bool — per-QUERY validity
    (causal / sink+local masks are computed by the caller from absolute
    positions). The single-query ``paged_attention_ref`` is the Cq == 1
    special case. Returns (B, Cq, Hq, D); all-invalid rows yield 0.
    """
    b, cq, hq, d = q.shape
    h_kv = k.shape[1]
    group = hq // h_kv
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    qg = q.reshape(b, cq, h_kv, group, d).astype(k.dtype)
    logits = jnp.einsum("bchgd,bhtd->bhgct", qg, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(valid[:, :, None, :, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    any_valid = jnp.any(valid, axis=-1)[:, :, None, :, None]
    p = jnp.where(any_valid, p, 0.0)
    out = jnp.einsum("bhgct,bhtd->bchgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, cq, hq, d).astype(q.dtype)


def chunk_attention_paged_ref(
    q: Array,
    k_pages: Array,
    v_pages: Array,
    page_start: Array,
    start: Array,
    k_new: Array,
    v_new: Array,
) -> Array:
    """Chunked-prefill retrieval attention with the page gather fused.

    q: (B, Cq, Hq, D) — one chunk of queries per slot; k_pages/v_pages:
    (B, Hr, C, P, D) — the PRE-append paged buffer; page_start:
    (B, Hr, C) absolute position of each page's first token (-1 =
    unwritten); start: (B,) tokens already admitted per slot; k_new/v_new:
    (B, Cq, Hr, D) — the chunk's own keys/values (roped, kv-head order).

    Because the buffer is pre-append, every buffered key precedes every
    chunk query (pos < start <= start + c), so cache validity is per-KEY
    — no (B, H, Cq, T) mask is ever materialized — and the intra-chunk
    part is a static causal triangle (key j attends query c iff j <= c).
    The union of the two key sets equals ``chunk_attention_ref`` over the
    post-append buffer with the positional mask (position math is inlined
    here; core.paging imports kernels.ops, so importing it back would be
    circular). Every query row attends at least itself, so no all-invalid
    guard is needed. Returns (B, Cq, Hq, D).
    """
    b, cq, hq, d = q.shape
    hr, c, p = k_pages.shape[1:4]
    group = hq // hr
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    kb = k_pages.reshape(b, hr, c * p, d)
    vb = v_pages.reshape(b, hr, c * p, d)
    start = jnp.asarray(start, jnp.int32).reshape(b)
    offs = jnp.arange(p, dtype=jnp.int32)
    pos = (page_start[..., None] + offs).reshape(b, hr, c * p)
    written = jnp.broadcast_to(
        (page_start >= 0)[..., None], (b, hr, c, p)).reshape(b, hr, c * p)
    cache_ok = written & (pos < start[:, None, None])        # (B, Hr, C*P)

    qg = q.reshape(b, cq, hr, group, d).astype(kb.dtype)
    lc = jnp.einsum("bchgd,bhtd->bhgct", qg, kb,
                    preferred_element_type=jnp.float32) * scale
    lc = jnp.where(cache_ok[:, :, None, None, :], lc, NEG_INF)
    kn = k_new.astype(kb.dtype)
    ln = jnp.einsum("bchgd,bjhd->bhgcj", qg, kn,
                    preferred_element_type=jnp.float32) * scale
    causal = jnp.arange(cq)[:, None] >= jnp.arange(cq)[None, :]
    ln = jnp.where(causal[None, None, None], ln, NEG_INF)
    probs = jax.nn.softmax(jnp.concatenate([lc, ln], axis=-1), axis=-1)
    # contract into (b, h, g, c, d) and transpose after: an einsum that
    # emits "bchgd" directly puts vb first with its contracting dim
    # minor-but-one, a bf16 x bf16 -> f32 dot XLA:CPU cannot execute
    out = jnp.einsum("bhgct,bhtd->bhgcd", probs[..., : c * p].astype(
        vb.dtype), vb, preferred_element_type=jnp.float32)
    out = out + jnp.einsum(
        "bhgcj,bhjd->bhgcd", probs[..., c * p:].astype(v_new.dtype),
        v_new.astype(vb.dtype).transpose(0, 2, 1, 3),
        preferred_element_type=jnp.float32)
    out = out.transpose(0, 3, 1, 2, 4)                       # (b,c,h,g,d)
    return out.reshape(b, cq, hq, d).astype(q.dtype)


def paged_attention_partial_ref(q, k, v, valid):
    """Partial (unnormalized) attention for cross-shard combine.

    q: (B, Hq, D); k/v: (B, Hkv, T, D); valid: (B, Hkv, T).

    Shape contract (any kernel impl — e.g. the Pallas
    paged_attention_partial — must match it):
      m: (B, Hq) f32 — running max of valid logits, NEG_INF (-1e30, a
         FINITE sentinel, never -inf) when a row has no valid token;
      l: (B, Hq) f32 — sum of exp(logit - m) over valid tokens, 0 for
         all-invalid rows;
      o: (B, Hq, D) f32 — unnormalized numerator sum(exp(logit - m) * v),
         0 for all-invalid rows.
    (NEG_INF, 0, 0) is the identity element of merge_partials_ref, so
    all-invalid shards drop out of the cross-shard combine exactly.
    Combine across shards with combine_partials_ref or a
    (pmax, psum, psum) collective merge.
    """
    b, hq, d = q.shape
    h_kv = k.shape[1]
    group = hq // h_kv
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    qg = q.reshape(b, h_kv, group, d).astype(k.dtype)
    logits = jnp.einsum("bhgd,bhtd->bhgt", qg, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(valid[:, :, None, :], logits, -jnp.inf)
    m = jnp.max(logits, axis=-1)                       # (B,Hkv,G)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(logits - m_safe[..., None])
    p = jnp.where(valid[:, :, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgt,bhtd->bhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    m = jnp.where(jnp.isfinite(m), m, NEG_INF)
    return (m.reshape(b, hq), l.reshape(b, hq), o.reshape(b, hq, d))


def paged_attention_weights_ref(q, k, valid):
    """Softmax weights only (B, Hkv, G, T) — used for importance accumulation."""
    b, hq, d = q.shape
    h_kv = k.shape[1]
    group = hq // h_kv
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    qg = q.reshape(b, h_kv, group, d).astype(k.dtype)
    logits = jnp.einsum("bhgd,bhtd->bhgt", qg, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(valid[:, :, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    any_valid = jnp.any(valid, axis=-1)[:, :, None, None]
    return jnp.where(any_valid, p, 0.0)


# ---------------------------------------------------------------------------
# Page relevance scoring (Quest-style min/max metadata)
# ---------------------------------------------------------------------------


def page_score_ref(q: Array, tau_min: Array, tau_max: Array) -> Array:
    """q: (B, Hq, D); tau_min/max: (B, Hkv, P, D) -> scores (B, Hkv, P).

    Per q head: Σ_d max(q_d·τmin_d, q_d·τmax_d) — the Quest upper bound on
    any key's logit in the page (q_d·k_d is linear in k_d, so it is
    maximized at an interval endpoint). Computed MXU-friendly as
    relu(q)·τmax + min(q,0)·τmin, which is exactly the per-coordinate max.
    GQA groups aggregate by summing over the group's q heads.
    """
    b, hq, d = q.shape
    h_kv = tau_min.shape[1]
    group = hq // h_kv
    qg = q.reshape(b, h_kv, group, d).astype(tau_min.dtype)
    qp = jnp.maximum(qg, 0)
    qn = jnp.minimum(qg, 0)
    hi = jnp.einsum("bhgd,bhpd->bhgp", qp, tau_max,
                    preferred_element_type=jnp.float32)
    lo = jnp.einsum("bhgd,bhpd->bhgp", qn, tau_min,
                    preferred_element_type=jnp.float32)
    return (hi + lo).sum(axis=2)


# ---------------------------------------------------------------------------
# Online-softmax partial combine (memory-compute co-placement cross-bank op)
# ---------------------------------------------------------------------------


def merge_partials_ref(m: Array, l: Array, o: Array, axis: int = 0):
    """Merge flash partials into ONE partial (still unnormalized).

    m/l: (N, ...); o: (N, ..., D) stacked on ``axis`` — each triple obeys
    the paged_attention_partial_ref shape contract. Returns (m', l', o')
    with the stack axis reduced. The merge is associative and commutative
    (up to float reassociation), with identity (NEG_INF, 0, 0) — the
    algebra that makes bank-count and shard-order irrelevant to the
    co-placed decode (tested in tests/test_kernels.py).
    """
    m_g = jnp.max(m, axis=axis)
    corr = jnp.exp(m - jnp.expand_dims(m_g, axis))
    l_g = jnp.sum(l * corr, axis=axis)
    o_g = jnp.sum(o * corr[..., None], axis=axis)
    return m_g, l_g, o_g


def combine_partials_ref(m: Array, l: Array, o: Array, axis: int = 0):
    """Combine flash-attention partials computed on different banks/shards.

    m: (N, ...) running max, l: (N, ...) sumexp, o: (N, ..., D) partial
    numerator (sum of exp(logit - m) * v). Returns combined output (..., D).
    Exact: softmax over the union equals the weighted combine
    (= merge_partials_ref followed by the l-normalization).
    """
    _, l_g, o_g = merge_partials_ref(m, l, o, axis=axis)
    return o_g / jnp.maximum(l_g, 1e-30)[..., None]
