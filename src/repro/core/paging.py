"""Page selection for retrieval heads (paper §IV-A.3).

Two-step pipeline: (1) relevance score of every page from its min/max
metadata, (2) top-k page selection. Selection is shared across
``share_window`` consecutive queries (LServe).

Consistent page partition (ctx = current context length; in the paper,
tokens enter pages only when they pop out of the local FIFO, so pages and
the local window never overlap — we express the same invariant with the
position->page layout by anchoring the local section at the page boundary
below ctx-local):

  first_local = max((ctx - local) // P, 0)
  sink section:     pages [0, n_sink): ALL in-context tokens (a superset of
                    the configured sink count, rounded up to page boundary)
  local section:    pages [first_local, first_local + n_local) with
                    n_local = ceil(local/P)+1; tokens valid iff
                    pos >= max(first_local, n_sink) * P
  selected section: top-k over pages in [n_sink, first_local)

Sink and local windows are therefore elastic by up to P-1 *extra* tokens
(never fewer than configured — retrieval heads attend a superset; streaming
heads use the exact sink/local counts). The three sections are mutually
exclusive and their union covers every resident token when top-k spans all
selectable pages — nothing is ever dropped at section boundaries or
double-counted in the softmax.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops

Array = jax.Array
NEG_INF = -1e30
SELECTABLE_MIN = NEG_INF / 2   # scores at or below are masked pages


def page_counts(*, sink: int, local: int, page: int) -> tuple[int, int]:
    """(n_sink_pages, n_local_pages) — static page counts always attended."""
    n_sink = -(-sink // page) if sink else 0
    n_local = -(-local // page) + 1 if local else 0  # +1 boundary page
    return n_sink, n_local


def _ctx_batched(ctx: Array, b: int) -> Array:
    """Normalize ctx to per-batch-row shape (B,).

    ``ctx`` is a scalar for the uniform (lockstep) decode path and a (B,)
    vector for the continuous-batching engine's ragged path; downstream
    math broadcasts over (B, H, ...) identically for both.
    """
    return jnp.broadcast_to(jnp.asarray(ctx, jnp.int32), (b,))


def _first_local_page(ctx: Array, *, local: int, page: int) -> Array:
    return jnp.maximum(ctx - local, 0) // page


def score_pages(
    q: Array,
    tau_min: Array,
    tau_max: Array,
    page_start: Array,
    ctx: Array,
    *,
    sink: int,
    local: int,
    page: int,
    impl: str = "ref",
) -> Array:
    """Relevance scores (B, Hkv, C); sink/local/empty pages forced to -inf.

    ``ctx`` may be a scalar (uniform batch) or (B,) (ragged batch).
    """
    scores = kops.page_score(q, tau_min, tau_max, impl=impl)
    n_sink, _ = page_counts(sink=sink, local=local, page=page)
    ctx = _ctx_batched(ctx, page_start.shape[0])
    first_local = _first_local_page(ctx, local=local, page=page)[:, None, None]
    pidx = jnp.where(page_start >= 0, page_start // page, -1)
    selectable = (page_start >= 0) & (pidx >= n_sink) & (pidx < first_local)
    return jnp.where(selectable, scores, NEG_INF)


def select_pages(scores: Array, top_k: int) -> Array:
    """Top-k page slots per (B, Hkv): (B, Hkv, K) int32.

    Picks whose score is masked (sink/local/empty pages, NEG_INF) and
    picks past the page count are -1 sentinels (masked downstream): a
    selection only ever holds pages that were selectable when it was
    made. Without this, a short context's top-k filled up with masked
    pages in index order, and a page that later slid out of the local
    window was attended on reuse steps only if its index happened to be
    among them — a layout-dependent accident (the distributed top-k of
    ``coplace_shmap`` never kept masked picks).
    """
    k_eff = min(top_k, scores.shape[-1])
    vals, idx = jax.lax.top_k(scores, k_eff)
    idx = jnp.where(vals > SELECTABLE_MIN, idx, -1).astype(jnp.int32)
    if k_eff < top_k:
        pad = jnp.full(idx.shape[:-1] + (top_k - k_eff,), -1, jnp.int32)
        idx = jnp.concatenate([idx, pad], axis=-1)
    return idx


def attended_page_slots(
    sel_idx: Array,
    ctx: Array,
    *,
    sink: int,
    local: int,
    page: int,
) -> Array:
    """Concatenate [sink pages | selected pages | local pages] slot indices.

    Returns (B, Hkv, n_sink + K + n_local) int32. Assumes the no-eviction
    layout where slot == page index == position // page. Out-of-range local
    slots are clamped for gather safety; token_validity() masks them.
    ``ctx`` may be a scalar (uniform batch) or (B,) (ragged batch).
    """
    b, h, _ = sel_idx.shape
    n_sink, n_local = page_counts(sink=sink, local=local, page=page)
    sink_pages = jnp.broadcast_to(
        jnp.arange(n_sink, dtype=jnp.int32), (b, h, n_sink))
    ctx = _ctx_batched(ctx, b)
    first_local = _first_local_page(ctx, local=local, page=page)[:, None, None]
    local_pages = first_local + jnp.arange(n_local, dtype=jnp.int32)
    local_pages = jnp.maximum(local_pages, 0)
    local_pages = jnp.broadcast_to(local_pages, (b, h, n_local)).astype(jnp.int32)
    return jnp.concatenate([sink_pages, sel_idx, local_pages], axis=2)


def coplace_attended_slots(
    sel_phys: Array,
    ctx: Array,
    *,
    sink: int,
    local: int,
    page: int,
    capacity: int,
    n_shards: int,
) -> Array:
    """`attended_page_slots` for the co-placed (shard_map) layout.

    ``sel_phys`` (B, H, K) holds PHYSICAL slot indices (the distributed
    top-k already returns physical ids; -1 = sentinel). The fixed sink and
    local sections are logical page indices mapped through
    ``interleave_slot``. ``capacity`` is the GLOBAL page count; each shard
    later subtracts its base offset and masks slots it does not own.
    ``ctx`` may be a scalar (lockstep) or (B,) (ragged batch).

    Logical local pages past the end of the cache are clamped to the last
    page — the same page the unsharded path's clamped gather reads — and
    `token_validity` masks them, so sharded and unsharded attend the same
    token set.
    """
    b, h, _ = sel_phys.shape
    n_sink, n_local = page_counts(sink=sink, local=local, page=page)
    ctx = _ctx_batched(ctx, b)
    first_local = _first_local_page(ctx, local=local, page=page)  # (B,)
    sink_log = jnp.broadcast_to(jnp.arange(n_sink, dtype=jnp.int32),
                                (b, n_sink))
    local_log = first_local[:, None] + jnp.arange(n_local, dtype=jnp.int32)
    fixed_log = jnp.concatenate([sink_log, local_log], axis=1)
    fixed_log = jnp.clip(fixed_log, 0, capacity - 1)
    fixed_phys = interleave_slot(fixed_log, capacity, n_shards)
    fixed_phys = jnp.broadcast_to(
        fixed_phys[:, None, :], (b, h, n_sink + n_local)).astype(jnp.int32)
    return jnp.concatenate(
        [fixed_phys[:, :, :n_sink], sel_phys.astype(jnp.int32),
         fixed_phys[:, :, n_sink:]], axis=2)


def gather_pages(k_pages: Array, v_pages: Array, slots: Array):
    """k/v_pages: (B, H, C, P, D); slots: (B, H, N) -> (B, H, N*P, D) each."""
    b, h, c, p, d = k_pages.shape
    n = slots.shape[2]
    sc = jnp.maximum(slots, 0)[:, :, :, None, None]
    k = jnp.take_along_axis(k_pages, sc, axis=2)
    v = jnp.take_along_axis(v_pages, sc, axis=2)
    return k.reshape(b, h, n * p, d), v.reshape(b, h, n * p, d)


def token_validity(
    slots: Array,
    page_start: Array,
    ctx: Array,
    *,
    sink: int,
    local: int,
    page: int,
    top_k: int,
) -> Array:
    """Validity mask (B, H, N*P) for the gathered token buffer.

    Enforces the section partition documented in the module docstring, so
    the three sections never overlap even for degenerate selections (short
    contexts where nothing is selectable yet).
    ``ctx`` may be a scalar (uniform batch) or (B,) (ragged batch).

    Sharding-safe: under the co-placed layout ``slots`` are shard-LOCAL
    slot indices (non-owned slots masked to -1) while ``page_start`` stores
    ABSOLUTE token positions, so the section math (pidx, first_local) stays
    in global coordinates and is identical on every shard.
    """
    b, h, n = slots.shape
    n_sink, n_local = page_counts(sink=sink, local=local, page=page)
    sentinel = (slots < 0)[:, :, :, None]
    start = jnp.take_along_axis(page_start, jnp.maximum(slots, 0), axis=2)
    offs = jnp.arange(page, dtype=jnp.int32)
    pos = start[:, :, :, None] + offs[None, None, None, :]  # (B,H,N,P)
    nonempty = (start >= 0)[:, :, :, None]
    ctx = _ctx_batched(ctx, b)
    in_ctx = pos < ctx[:, None, None, None]
    section = jnp.concatenate([
        jnp.zeros((n_sink,), jnp.int32),
        jnp.ones((top_k,), jnp.int32),
        jnp.full((n_local,), 2, jnp.int32),
    ])
    sec = section[None, None, :, None]
    first_local = _first_local_page(ctx, local=local, page=page)[:, None, None]
    pidx = start // page
    ok_sink = jnp.broadcast_to(True, pos.shape)  # whole sink page(s)
    ok_local = (
        (pos >= (jnp.maximum(first_local, n_sink) * page)[:, :, :, None])
        & (pidx >= first_local)[:, :, :, None]
    )
    ok_sel = ((pidx >= n_sink) & (pidx < first_local))[:, :, :, None]
    ok = jnp.where(sec == 0, ok_sink, jnp.where(sec == 2, ok_local, ok_sel))
    return (nonempty & in_ctx & ok & ~sentinel).reshape(b, h, n * page)


# ---------------------------------------------------------------------------
# Chunked prefill (multi-token) validity
#
# During chunked prefill there is no page selection: retrieval heads
# attend FULL causal (exactly like single-shot prefill), streaming heads
# sink+local. Keys live in cache buffers whose layout is physical (pages
# may be slot-permuted, the stream ring wraps), so validity is computed
# from absolute POSITIONS, never from slot indices — identical math on
# every layout.
# ---------------------------------------------------------------------------


def chunk_positions(start: Array, chunk: int) -> Array:
    """Absolute positions (B, C) of a left-aligned chunk starting at
    ``start`` (B,). Rows are valid only below the caller's chunk_len."""
    start = jnp.asarray(start, jnp.int32).reshape(-1)
    return start[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None, :]


def paged_key_positions(page_start: Array, page: int):
    """(key_pos, key_ok) for the flattened page buffer.

    page_start: (B, H, C) absolute first-token positions (-1 = empty).
    Returns key_pos (B, H, C*P) int32 and key_ok (B, H, C*P) bool. Works
    for any physical page order (the metadata carries absolute
    positions).
    """
    b, h, c = page_start.shape
    offs = jnp.arange(page, dtype=jnp.int32)
    pos = page_start[:, :, :, None] + offs[None, None, None, :]
    ok = jnp.broadcast_to((page_start >= 0)[:, :, :, None], pos.shape)
    return pos.reshape(b, h, c * page), ok.reshape(b, h, c * page)


def chunk_causal_validity(key_pos: Array, key_ok: Array,
                          pos_q: Array) -> Array:
    """Causal chunk-prefill mask: (B, H, Cq, T) — key attended iff it
    exists and its position is <= the query's. key_pos/key_ok: (B, H, T);
    pos_q: (B, Cq). Appended-but-unwritten page offsets are excluded by
    the causal bound alone (their positions are >= every chunk query)."""
    return (key_ok[:, :, None, :]
            & (key_pos[:, :, None, :] <= pos_q[:, None, :, None]))


def chunk_stream_validity(key_pos: Array, pos_q: Array, *, sink: int,
                          local: int) -> Array:
    """Sink+local chunk-prefill mask, matching the streaming decode mask
    ((pos < sink) | (pos > q - local)) and the flash window semantics.
    key_pos: (B, H, T) with -1 = empty slot; pos_q: (B, Cq).
    Returns (B, H, Cq, T)."""
    kp = key_pos[:, :, None, :]
    pq = pos_q[:, None, :, None]
    return (key_pos >= 0)[:, :, None, :] & (kp <= pq) & (
        (kp < sink) | (kp > pq - local))


# ---------------------------------------------------------------------------
# Speculative verify (multi-query decode over the PRE-APPEND cache)
#
# The verify chunk holds k tokens at positions start .. start+k-1 (start =
# current context length); query j plays the role of decode step j and must
# attend EXACTLY what the sequential engine's token j would attend. Keys at
# positions >= start are not in the cache yet (attend-before-append) — they
# arrive as the causally-masked chunk tail, so the paged buffer only ever
# supplies positions < start and the per-page in-context bound is the CACHE
# context, shared by all queries. What IS per-query is the section
# partition: first_local_j = first_local(start+j+1) grows with j, so a page
# can be local for query 0 and selectable-but-unselected (hence dropped,
# exactly as the sequential reuse step drops it) for query k-1.
#
# The gathered buffer is anchored at first_local(start+1): every query's
# local low edge is >= it, and the highest live page (start-1)//page is
# within n_local pages of it, so no extension is needed — per-query
# validity does all the sectioning.
# ---------------------------------------------------------------------------


def verify_attended_slots(
    sel_idx: Array,
    ctx: Array,
    *,
    sink: int,
    local: int,
    page: int,
    capacity: int,
    n_shards: int = 1,
) -> Array:
    """[sink | selected | local] slot indices for the verify gather.

    ``ctx`` is start+1 (B,) — the context of the FIRST verify query, which
    anchors the shared local section. ``sel_idx`` (B, Hkv, K) holds slot
    indices in the cache's physical page order (identical to logical order
    unless ``n_shards > 1``); the fixed sink/local sections are logical
    page indices mapped through ``interleave_slot`` (identity for 1 shard)
    and clipped for gather safety — verify_token_validity masks the
    clipped duplicates. Returns (B, Hkv, n_sink + K + n_local) int32.
    """
    b, h, _ = sel_idx.shape
    n_sink, n_local = page_counts(sink=sink, local=local, page=page)
    ctx = _ctx_batched(ctx, b)
    first_local = _first_local_page(ctx, local=local, page=page)  # (B,)
    sink_log = jnp.broadcast_to(jnp.arange(n_sink, dtype=jnp.int32),
                                (b, n_sink))
    local_log = first_local[:, None] + jnp.arange(n_local, dtype=jnp.int32)
    fixed_log = jnp.concatenate([sink_log, local_log], axis=1)
    fixed_log = jnp.clip(fixed_log, 0, capacity - 1)
    fixed_phys = interleave_slot(fixed_log, capacity, n_shards)
    fixed_phys = jnp.broadcast_to(
        fixed_phys[:, None, :], (b, h, n_sink + n_local)).astype(jnp.int32)
    return jnp.concatenate(
        [fixed_phys[:, :, :n_sink], sel_idx.astype(jnp.int32),
         fixed_phys[:, :, n_sink:]], axis=2)


def verify_token_validity(
    slots: Array,
    page_start: Array,
    cache_ctx: Array,
    pos_q: Array,
    *,
    sink: int,
    local: int,
    page: int,
    top_k: int,
) -> Array:
    """Per-query validity (B, H, Cq, N*P) for the gathered verify buffer.

    Same section rules as ``token_validity`` with two deltas: the
    in-context bound is the PRE-APPEND cache length ``cache_ctx`` (B,) —
    identical for every query because chunk-tail keys are supplied
    separately — and the sink/selected/local partition is evaluated at
    each query's own context ``pos_q + 1`` (pos_q: (B, Cq) absolute query
    positions), so section membership shifts across the chunk exactly as
    it does across k sequential decode steps.
    """
    b, h, n = slots.shape
    cq = pos_q.shape[1]
    n_sink, n_local = page_counts(sink=sink, local=local, page=page)
    sentinel = (slots < 0)[:, :, None, :, None]
    start = jnp.take_along_axis(page_start, jnp.maximum(slots, 0), axis=2)
    offs = jnp.arange(page, dtype=jnp.int32)
    pos = (start[:, :, :, None] + offs[None, None, None, :])[:, :, None]
    nonempty = (start >= 0)[:, :, None, :, None]
    cache_ctx = _ctx_batched(cache_ctx, b)
    in_ctx = pos < cache_ctx[:, None, None, None, None]
    section = jnp.concatenate([
        jnp.zeros((n_sink,), jnp.int32),
        jnp.ones((top_k,), jnp.int32),
        jnp.full((n_local,), 2, jnp.int32),
    ])
    sec = section[None, None, None, :, None]
    first_local = _first_local_page(
        pos_q + 1, local=local, page=page)[:, None, :, None, None]
    pidx = (start // page)[:, :, None, :, None]
    ok_sink = jnp.broadcast_to(True, pos.shape)
    ok_local = ((pos >= jnp.maximum(first_local, n_sink) * page)
                & (pidx >= first_local))
    ok_sel = (pidx >= n_sink) & (pidx < first_local)
    ok = jnp.where(sec == 0, ok_sink, jnp.where(sec == 2, ok_local, ok_sel))
    return (nonempty & in_ctx & ok & ~sentinel).reshape(b, h, cq, n * page)


def accumulate_importance(importance: Array, scores: Array) -> Array:
    """Paper: accumulate the computed relevance score at each step.

    Scores of masked pages are NEG_INF; those contribute 0.
    """
    return importance + jnp.where(scores > NEG_INF / 2, scores, 0.0)


def interleave_slot(page: Array, capacity: int, n_shards: int) -> Array:
    """Physical cache slot for logical page index under interleaved
    (round-robin) bank allocation (paper Fig 7b): owner shard = page mod
    n_shards, so any top-k selection lands uniformly on all shards.

    Identity when n_shards == 1. capacity must divide by n_shards.
    """
    if n_shards == 1:
        return page
    local_c = capacity // n_shards
    return (page % n_shards) * local_c + page // n_shards


def slots_of_positions(page_start: Array, positions: Array) -> Array:
    """Pool-mode slot lookup: for each target page-start position, the
    slot holding it (or -1). page_start: (B, H, C); positions: (N,) or
    (B, H, N) -> (B, H, N) int32."""
    if positions.ndim == 1:
        positions = jnp.broadcast_to(
            positions[None, None], page_start.shape[:2] + positions.shape)
    eq = page_start[:, :, :, None] == positions[:, :, None, :]
    slot = jnp.argmax(eq, axis=2).astype(jnp.int32)
    found = jnp.any(eq, axis=2)
    return jnp.where(found, slot, -1)


def evict_lowest(cache_importance: Array, page_start: Array):
    """Return per-(B,H) slot index of the lowest-importance *live* page.

    Used by the fixed-pool (kv_budget) mode: the returned slot is overwritten
    by the next page.
    """
    live = page_start >= 0
    masked = jnp.where(live, cache_importance, jnp.inf)
    return jnp.argmin(masked, axis=-1).astype(jnp.int32)
