"""KV-cache structures for H²EAL serving.

Three cache kinds:

  FullCache    — dense (B, H, S, D) baseline (paper's "full attention" HB
                 baseline; also used when ``h2eal.enabled = False``).
  PagedCache   — retrieval heads: paged KV + Quest min/max metadata +
                 accumulated importance (+ page_start table so a fixed-size
                 pool with eviction is expressible with static shapes).
  StreamCache  — streaming heads: sink + local ring buffer only (this is
                 where the paper's memory reduction comes from).

All are registered pytree dataclasses so they can live inside jitted
functions and be sharded leaf-wise.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def _dc(cls):
    fields = [f.name for f in dataclasses.fields(cls)]
    return jax.tree_util.register_dataclass(cls, data_fields=fields, meta_fields=[])


@_dc
@dataclasses.dataclass
class FullCache:
    k: Array  # (B, Hkv, S, D)
    v: Array  # (B, Hkv, S, D)


@_dc
@dataclasses.dataclass
class PagedCache:
    k_pages: Array     # (B, Hr, C, P, D)
    v_pages: Array     # (B, Hr, C, P, D)
    tau_min: Array     # (B, Hr, C, D)   elementwise min of keys in page
    tau_max: Array     # (B, Hr, C, D)
    importance: Array  # (B, Hr, C)      accumulated relevance (f32)
    page_start: Array  # (B, Hr, C)      absolute pos of first token; -1 empty
    sel_idx: Array     # (B, Hr, K)      cached top-k selection (shared window)


@_dc
@dataclasses.dataclass
class StreamCache:
    k: Array  # (B, Hs, W, D)  W = sink + local_cap, local part is a ring
    v: Array  # (B, Hs, W, D)
    pos: Array  # (B, Hs, W)   absolute position stored in each slot; -1 empty


def empty_fill_value(path: str):
    """Empty-cache sentinel for a serve-state leaf identified by its
    pytree key path — the single source of truth the constructors above
    encode shape-wise (``make_paged_cache`` / ``make_stream_cache``):
    tau_min +inf, tau_max -inf, page_start and the stream ring's ``pos``
    -1, the xLSTM max-stabilizer ``m`` -inf (init_mlstm_state /
    init_slstm_state), everything else 0. Consumed by the serving
    engine's dynamic-slot reset (chunked admission) so a cleared slot
    row is exactly what a fresh constructor would produce."""
    if "tau_min" in path:
        return jnp.inf
    if "tau_max" in path:
        return -jnp.inf
    if "page_start" in path or path.endswith(".pos"):
        return -1
    if path.endswith("['m']"):
        return -jnp.inf
    return 0


def make_full_cache(b, h_kv, capacity, d, dtype=jnp.bfloat16):
    z = jnp.zeros((b, h_kv, capacity, d), dtype)
    return FullCache(k=z, v=z)


def make_paged_cache(b, h_r, num_pages, page, d, top_k, dtype=jnp.bfloat16):
    zp = jnp.zeros((b, h_r, num_pages, page, d), dtype)
    return PagedCache(
        k_pages=zp,
        v_pages=zp,
        tau_min=jnp.full((b, h_r, num_pages, d), jnp.inf, jnp.float32),
        tau_max=jnp.full((b, h_r, num_pages, d), -jnp.inf, jnp.float32),
        importance=jnp.zeros((b, h_r, num_pages), jnp.float32),
        page_start=jnp.full((b, h_r, num_pages), -1, jnp.int32),
        sel_idx=jnp.zeros((b, h_r, top_k), jnp.int32),
    )


def make_stream_cache(b, h_s, sink, local_cap, d, dtype=jnp.bfloat16):
    w = sink + local_cap
    z = jnp.zeros((b, h_s, w, d), dtype)
    return StreamCache(k=z, v=z, pos=jnp.full((b, h_s, w), -1, jnp.int32))


# ---------------------------------------------------------------------------
# Append ops (decode: one token for all heads of one layer)
#
# ``length`` is a scalar on the uniform (lockstep) path and a (B,) vector
# on the continuous-batching ragged path, where each slot writes at its own
# position. ``active`` ((B,) bool, ragged path only) masks retired / empty
# slots: their rows are written back unchanged, so a slot's cache is
# bit-stable while it waits for the next admission.
# ---------------------------------------------------------------------------


def _is_ragged(length, active) -> bool:
    return active is not None or jnp.asarray(length).ndim == 1


def _row_mask(active, b: int) -> Array:
    if active is None:
        return jnp.ones((b,), bool)
    return jnp.asarray(active).reshape(b)


def full_cache_append(cache: FullCache, k_new: Array, v_new: Array, length,
                      active=None):
    """k_new/v_new: (B, Hkv, D); length: scalar or (B,) context len."""
    if not _is_ragged(length, active):
        k = jax.lax.dynamic_update_slice(
            cache.k, k_new[:, :, None, :].astype(cache.k.dtype),
            (0, 0, length, 0))
        v = jax.lax.dynamic_update_slice(
            cache.v, v_new[:, :, None, :].astype(cache.v.dtype),
            (0, 0, length, 0))
        return FullCache(k=k, v=v)
    b, h, s, _ = cache.k.shape
    lb = jnp.clip(jnp.broadcast_to(length, (b,)), 0, s - 1)
    bi = jnp.arange(b)[:, None]
    hi = jnp.arange(h)[None, :]
    sl = jnp.broadcast_to(lb[:, None], (b, h))
    act = _row_mask(active, b)[:, None, None]
    k_wr = jnp.where(act, k_new.astype(cache.k.dtype), cache.k[bi, hi, sl])
    v_wr = jnp.where(act, v_new.astype(cache.v.dtype), cache.v[bi, hi, sl])
    return FullCache(k=cache.k.at[bi, hi, sl].set(k_wr),
                     v=cache.v.at[bi, hi, sl].set(v_wr))


def stream_cache_append(cache: StreamCache, k_new, v_new, length, *,
                        sink: int, active=None):
    """Ring-buffer append: pos<sink go to slot=pos, else ring over local part."""
    w = cache.k.shape[2]
    local_cap = w - sink
    if not _is_ragged(length, active):
        slot = jnp.where(length < sink, length,
                         sink + (length - sink) % local_cap)
        k = jax.lax.dynamic_update_slice(
            cache.k, k_new[:, :, None, :].astype(cache.k.dtype), (0, 0, slot, 0))
        v = jax.lax.dynamic_update_slice(
            cache.v, v_new[:, :, None, :].astype(cache.v.dtype), (0, 0, slot, 0))
        pos = jax.lax.dynamic_update_slice(
            cache.pos, jnp.broadcast_to(length, cache.pos.shape[:2])[:, :, None].astype(jnp.int32),
            (0, 0, slot))
        return StreamCache(k=k, v=v, pos=pos)
    b, h, _, _ = cache.k.shape
    lb = jnp.broadcast_to(length, (b,)).astype(jnp.int32)
    slot = jnp.where(lb < sink, lb, sink + (lb - sink) % local_cap)
    bi = jnp.arange(b)[:, None]
    hi = jnp.arange(h)[None, :]
    sl = jnp.broadcast_to(slot[:, None], (b, h))
    act = _row_mask(active, b)
    k_wr = jnp.where(act[:, None, None], k_new.astype(cache.k.dtype),
                     cache.k[bi, hi, sl])
    v_wr = jnp.where(act[:, None, None], v_new.astype(cache.v.dtype),
                     cache.v[bi, hi, sl])
    pos_wr = jnp.where(act[:, None], lb[:, None], cache.pos[bi, hi, sl])
    return StreamCache(k=cache.k.at[bi, hi, sl].set(k_wr),
                       v=cache.v.at[bi, hi, sl].set(v_wr),
                       pos=cache.pos.at[bi, hi, sl].set(
                           pos_wr.astype(jnp.int32)))


def paged_cache_append(cache: PagedCache, k_new, v_new, length, active=None):
    """Append one token at absolute position ``length`` (page = length//P).

    Metadata for the page is updated incrementally (running min/max).
    No-eviction layout: page index is position//P (capacity covers max ctx).

    The leaves may carry leading layer axes — the decode layer scan's
    stacked cache, k/v_pages (L, B, Hr, C, P, D) with k_new/v_new
    (L, B, Hr, D), every layer's token at the one position ``length``.
    Those axes fold into the batch axis (a reshape, free), so one write
    per leaf puts every layer's row in place.
    """
    lead = cache.k_pages.shape[:-5]
    if lead:
        b = cache.k_pages.shape[len(lead)]
        n = math.prod(lead)
        fold = lambda a: a.reshape((-1,) + a.shape[len(lead) + 1:])
        if _is_ragged(length, active):
            length = jnp.tile(
                jnp.broadcast_to(length, (b,)).astype(jnp.int32), n)
            active = None if active is None else jnp.tile(
                jnp.asarray(active).reshape(b), n)
        out = paged_cache_append(jax.tree.map(fold, cache), fold(k_new),
                                 fold(v_new), length, active=active)
        return jax.tree.map(lambda a, like: a.reshape(like.shape), out, cache)
    p = cache.k_pages.shape[3]
    if not _is_ragged(length, active):
        page = length // p
        off = length % p
        k_pages = jax.lax.dynamic_update_slice(
            cache.k_pages, k_new[:, :, None, None, :].astype(cache.k_pages.dtype),
            (0, 0, page, off, 0))
        v_pages = jax.lax.dynamic_update_slice(
            cache.v_pages, v_new[:, :, None, None, :].astype(cache.v_pages.dtype),
            (0, 0, page, off, 0))
        kf = k_new.astype(jnp.float32)[:, :, None, :]
        old_min = jax.lax.dynamic_slice(
            cache.tau_min, (0, 0, page, 0),
            (cache.tau_min.shape[0], cache.tau_min.shape[1], 1, cache.tau_min.shape[3]))
        old_max = jax.lax.dynamic_slice(
            cache.tau_max, (0, 0, page, 0),
            (cache.tau_max.shape[0], cache.tau_max.shape[1], 1, cache.tau_max.shape[3]))
        tau_min = jax.lax.dynamic_update_slice(
            cache.tau_min, jnp.minimum(old_min, kf), (0, 0, page, 0))
        tau_max = jax.lax.dynamic_update_slice(
            cache.tau_max, jnp.maximum(old_max, kf), (0, 0, page, 0))
        return dataclasses.replace(
            cache, k_pages=k_pages, v_pages=v_pages,
            tau_min=tau_min, tau_max=tau_max,
            page_start=paged_start_append(cache.page_start, length, page=p))

    b, h, c, _, _ = cache.k_pages.shape
    lb = jnp.broadcast_to(length, (b,)).astype(jnp.int32)
    page = jnp.clip(lb // p, 0, c - 1)
    off = lb % p
    bi = jnp.arange(b)[:, None]
    hi = jnp.arange(h)[None, :]
    pg = jnp.broadcast_to(page[:, None], (b, h))
    act = _row_mask(active, b)
    a3 = act[:, None, None]
    # whole pages are read and written back, not single rows: a scatter of
    # (D,)-rows makes the TPU compiler transpose the whole bf16 page buffer
    # through two copies when D is narrower than a lane tile (head_dim 64)
    put = ((jnp.arange(p)[None, :] == off[:, None]) & act[:, None])[
        :, None, :, None]
    k_wr = jnp.where(put, k_new[:, :, None, :].astype(cache.k_pages.dtype),
                     cache.k_pages[bi, hi, pg])
    v_wr = jnp.where(put, v_new[:, :, None, :].astype(cache.v_pages.dtype),
                     cache.v_pages[bi, hi, pg])
    kf = k_new.astype(jnp.float32)
    old_min = cache.tau_min[bi, hi, pg]
    old_max = cache.tau_max[bi, hi, pg]
    min_wr = jnp.where(a3, jnp.minimum(old_min, kf), old_min)
    max_wr = jnp.where(a3, jnp.maximum(old_max, kf), old_max)
    return dataclasses.replace(
        cache,
        k_pages=cache.k_pages.at[bi, hi, pg].set(k_wr),
        v_pages=cache.v_pages.at[bi, hi, pg].set(v_wr),
        tau_min=cache.tau_min.at[bi, hi, pg].set(min_wr),
        tau_max=cache.tau_max.at[bi, hi, pg].set(max_wr),
        page_start=paged_start_append(cache.page_start, length, page=p,
                                      active=active))


def paged_start_append(page_start, length, *, page: int, active=None):
    """``page_start`` (B, Hr, C) after appending a token at ``length``:
    the page taking it starts at page * P (rows of inactive slots are
    unchanged). The page-start part of ``paged_cache_append``, and
    idempotent: applying it twice leaves what applying it once does."""
    if not _is_ragged(length, active):
        pg = length // page
        return jax.lax.dynamic_update_slice(
            page_start,
            jnp.broadcast_to(pg * page, page_start.shape[:2])[
                :, :, None].astype(jnp.int32),
            (0, 0, pg))
    b, h, c = page_start.shape
    lb = jnp.broadcast_to(length, (b,)).astype(jnp.int32)
    pg = jnp.broadcast_to(jnp.clip(lb // page, 0, c - 1)[:, None], (b, h))
    bi = jnp.arange(b)[:, None]
    hi = jnp.arange(h)[None, :]
    start_wr = jnp.where(_row_mask(active, b)[:, None], pg * page,
                         page_start[bi, hi, pg])
    return page_start.at[bi, hi, pg].set(start_wr.astype(jnp.int32))


#: the PagedCache leaves the decode layer scan reads in place and writes
#: once after the scan (``paged_cache_append`` on the stacked cache); the
#: rest are small and ride the scan
PAGE_LEAVES = ("k_pages", "v_pages", "tau_min", "tau_max")


def sharded_paged_append(k_pages, v_pages, tau_min, tau_max, page_start,
                         k_new, v_new, length, *, page: int, shard_idx,
                         n_shards: int, active=None):
    """Owner-shard append for the co-placed (shard_map) paged layout.

    The leaves hold this shard's ``C_loc = C / n_shards`` pages of the
    interleaved physical layout (paper Fig 7b: logical page ``p`` lives on
    shard ``p % n_shards``). Only the shard that owns the token's page
    writes; every other shard returns its leaves bit-unchanged, so the
    global cache state is exactly the unsharded one, page-permuted.

    ``length`` is a scalar (lockstep) or (B,) per-slot vector (continuous
    batching); ``active`` masks retired slots on the ragged path the same
    way as ``paged_cache_append``. Returns the five updated leaves.
    """
    from repro.core import paging

    c_loc = k_pages.shape[2]
    cap = c_loc * n_shards
    if not _is_ragged(length, active):
        pg = length // page
        off = length % page
        phys = paging.interleave_slot(pg, cap, n_shards)
        local = phys - shard_idx * c_loc
        mine = (local >= 0) & (local < c_loc)
        lc = jnp.clip(local, 0, c_loc - 1)
        kp2 = jax.lax.dynamic_update_slice(
            k_pages, k_new[:, :, None, None, :].astype(k_pages.dtype),
            (0, 0, lc, off, 0))
        vp2 = jax.lax.dynamic_update_slice(
            v_pages, v_new[:, :, None, None, :].astype(v_pages.dtype),
            (0, 0, lc, off, 0))
        kf = k_new.astype(jnp.float32)[:, :, None, :]
        sl = lambda a: jax.lax.dynamic_slice(
            a, (0, 0, lc, 0), (a.shape[0], a.shape[1], 1, a.shape[3]))
        tmin2 = jax.lax.dynamic_update_slice(
            tau_min, jnp.minimum(sl(tau_min), kf), (0, 0, lc, 0))
        tmax2 = jax.lax.dynamic_update_slice(
            tau_max, jnp.maximum(sl(tau_max), kf), (0, 0, lc, 0))
        ps2 = jax.lax.dynamic_update_slice(
            page_start,
            jnp.broadcast_to(pg * page, page_start.shape[:2])[
                :, :, None].astype(jnp.int32),
            (0, 0, lc))
        return (jnp.where(mine, kp2, k_pages), jnp.where(mine, vp2, v_pages),
                jnp.where(mine, tmin2, tau_min),
                jnp.where(mine, tmax2, tau_max),
                jnp.where(mine, ps2, page_start))

    b, h = k_new.shape[0], k_pages.shape[1]
    lb = jnp.broadcast_to(length, (b,)).astype(jnp.int32)
    pg = jnp.clip(lb // page, 0, cap - 1)
    off = lb % page
    phys = paging.interleave_slot(pg, cap, n_shards)
    local = phys - shard_idx * c_loc
    mine = (local >= 0) & (local < c_loc)
    lc = jnp.clip(local, 0, c_loc - 1)
    act = _row_mask(active, b) & mine
    bi = jnp.arange(b)[:, None]
    hi = jnp.arange(h)[None, :]
    pgl = jnp.broadcast_to(lc[:, None], (b, h))
    of = jnp.broadcast_to(off[:, None], (b, h))
    a3 = act[:, None, None]
    k_wr = jnp.where(a3, k_new.astype(k_pages.dtype),
                     k_pages[bi, hi, pgl, of])
    v_wr = jnp.where(a3, v_new.astype(v_pages.dtype),
                     v_pages[bi, hi, pgl, of])
    kf = k_new.astype(jnp.float32)
    old_min = tau_min[bi, hi, pgl]
    old_max = tau_max[bi, hi, pgl]
    min_wr = jnp.where(a3, jnp.minimum(old_min, kf), old_min)
    max_wr = jnp.where(a3, jnp.maximum(old_max, kf), old_max)
    start_wr = jnp.where(act[:, None],
                         jnp.broadcast_to((pg * page)[:, None], (b, h)),
                         page_start[bi, hi, pgl])
    return (k_pages.at[bi, hi, pgl, of].set(k_wr),
            v_pages.at[bi, hi, pgl, of].set(v_wr),
            tau_min.at[bi, hi, pgl].set(min_wr),
            tau_max.at[bi, hi, pgl].set(max_wr),
            page_start.at[bi, hi, pgl].set(start_wr.astype(jnp.int32)))


def _ext_overflow(a: Array) -> Array:
    """Append one transient overflow slot on axis 2 (the page / sequence
    dim). Chunk appends route masked-out tokens there so no valid write
    ever aliases a masked one (scatter with duplicate indices and
    different values is undefined); callers slice the slot away with
    ``[:, :, :n]`` after the scatter — the stream_cache_from_prefill
    trick."""
    pad = [(0, 0)] * a.ndim
    pad[2] = (0, 1)
    return jnp.pad(a, pad)


def paged_cache_append_chunk(cache: PagedCache, k_new, v_new, start,
                             chunk_len, *, active=None, phys_shards: int = 1):
    """Multi-token ragged chunk append (chunked prefill).

    k_new/v_new: (B, C, Hr, D) — per-slot prompt chunks, left-aligned.
    Slot ``b`` appends its first ``chunk_len[b]`` tokens at absolute
    positions ``start[b] .. start[b]+chunk_len[b]-1``; the rest of the
    chunk (and every row with ``active`` False) appends nothing. Page
    min/max metadata merges via scatter-min/max — exact for chunks that
    open, fill, or straddle pages, PROVIDED the touched pages start from
    the empty sentinels (the engine resets a slot's rows at admission).

    ``phys_shards`` > 1 routes each logical page through
    ``paging.interleave_slot`` (the coplace_shmap physical round-robin
    striping); the metadata keeps absolute positions, so validity math
    is layout-independent. Invalid tokens scatter into a transient
    overflow page that is sliced away (the stream_cache_from_prefill
    trick), so no valid write ever aliases a masked one.
    """
    from repro.core import paging

    b, cch, h, d = k_new.shape
    cap = cache.k_pages.shape[2]
    p_sz = cache.k_pages.shape[3]
    start = jnp.broadcast_to(start, (b,)).astype(jnp.int32)
    clen = jnp.broadcast_to(chunk_len, (b,)).astype(jnp.int32)
    act = _row_mask(active, b)
    j = jnp.arange(cch, dtype=jnp.int32)
    pos = start[:, None] + j[None, :]                       # (B, C)
    valid = (j[None, :] < clen[:, None]) & act[:, None]
    page_log = jnp.clip(pos // p_sz, 0, cap - 1)
    phys = paging.interleave_slot(page_log, cap, phys_shards)
    off = pos % p_sz
    pg_eff = jnp.where(valid, phys, cap)                    # cap = overflow
    ext = _ext_overflow
    bi = jnp.arange(b)[:, None, None]
    hi = jnp.arange(h)[None, :, None]
    pg = pg_eff[:, None, :]
    of = off[:, None, :]
    kt = k_new.transpose(0, 2, 1, 3)                        # (B, H, C, D)
    vt = v_new.transpose(0, 2, 1, 3)
    k_pages = ext(cache.k_pages).at[bi, hi, pg, of].set(
        kt.astype(cache.k_pages.dtype))[:, :, :cap]
    v_pages = ext(cache.v_pages).at[bi, hi, pg, of].set(
        vt.astype(cache.v_pages.dtype))[:, :, :cap]
    kf = kt.astype(jnp.float32)
    tau_min = ext(cache.tau_min).at[bi, hi, pg].min(kf)[:, :, :cap]
    tau_max = ext(cache.tau_max).at[bi, hi, pg].max(kf)[:, :, :cap]
    ps_val = jnp.broadcast_to((page_log * p_sz)[:, None, :], (b, h, cch))
    page_start = ext(cache.page_start).at[bi, hi, pg].set(
        ps_val.astype(jnp.int32))[:, :, :cap]
    return dataclasses.replace(
        cache, k_pages=k_pages, v_pages=v_pages, tau_min=tau_min,
        tau_max=tau_max, page_start=page_start)


def stream_cache_append_chunk(cache: StreamCache, k_new, v_new, start,
                              chunk_len, *, sink: int, active=None):
    """Chunk append into the sink+local ring (chunked prefill).

    k_new/v_new: (B, C, Hs, D). Equivalent to appending the chunk's
    tokens one at a time with ``stream_cache_append`` — expressed in
    closed form: each ring slot keeps the LAST appended position mapping
    to it (later positions win, matching ring semantics), so a chunk
    larger than the ring is handled exactly.
    """
    b, cch, h, d = k_new.shape
    w = cache.k.shape[2]
    local_cap = w - sink
    start = jnp.broadcast_to(start, (b,)).astype(jnp.int32)
    clen = jnp.broadcast_to(chunk_len, (b,)).astype(jnp.int32)
    act = _row_mask(active, b)
    e = start + clen - 1                                    # last position
    wi = jnp.arange(w, dtype=jnp.int32)
    # the position written LAST into each slot: sink slots hold their own
    # index; ring slot w holds the largest appended p >= sink with
    # (p - sink) % local_cap == w - sink
    r = wi[None, :] - sink
    m = (e[:, None] - sink - r) % local_cap                 # (B, W) >= 0
    p_ring = e[:, None] - m
    p_tgt = jnp.where(wi[None, :] < sink, wi[None, :], p_ring)
    written = (act[:, None] & (p_tgt >= start[:, None])
               & (p_tgt <= e[:, None])
               & ((wi[None, :] < sink) | (p_tgt >= sink)))
    jidx = jnp.clip(p_tgt - start[:, None], 0, cch - 1)     # chunk offset
    kt = k_new.transpose(0, 2, 1, 3)                        # (B, H, C, D)
    vt = v_new.transpose(0, 2, 1, 3)
    take = lambda a: jnp.take_along_axis(
        a, jnp.broadcast_to(jidx[:, None, :, None], (b, h, w, 1)), axis=2)
    wr = written[:, None, :, None]
    k2 = jnp.where(wr, take(kt).astype(cache.k.dtype), cache.k)
    v2 = jnp.where(wr, take(vt).astype(cache.v.dtype), cache.v)
    pos2 = jnp.where(written[:, None, :],
                     jnp.broadcast_to(p_tgt[:, None, :], (b, h, w)),
                     cache.pos)
    return StreamCache(k=k2, v=v2, pos=pos2.astype(jnp.int32))


def full_cache_append_chunk(cache: FullCache, k_new, v_new, start,
                            chunk_len, active=None):
    """Chunk append for the dense baseline cache (chunked prefill of
    full-attention / plain-window layers). k_new/v_new: (B, C, Hkv, D)
    appended at positions ``start .. start+chunk_len-1`` per slot."""
    b, cch, h, d = k_new.shape
    s = cache.k.shape[2]
    start = jnp.broadcast_to(start, (b,)).astype(jnp.int32)
    clen = jnp.broadcast_to(chunk_len, (b,)).astype(jnp.int32)
    act = _row_mask(active, b)
    j = jnp.arange(cch, dtype=jnp.int32)
    pos = start[:, None] + j[None, :]
    valid = (j[None, :] < clen[:, None]) & act[:, None]
    sl_eff = jnp.where(valid, jnp.clip(pos, 0, s - 1), s)   # s = overflow
    ext = _ext_overflow
    bi = jnp.arange(b)[:, None, None]
    hi = jnp.arange(h)[None, :, None]
    sl = sl_eff[:, None, :]
    kt = k_new.transpose(0, 2, 1, 3)
    vt = v_new.transpose(0, 2, 1, 3)
    return FullCache(
        k=ext(cache.k).at[bi, hi, sl].set(kt.astype(cache.k.dtype))[:, :, :s],
        v=ext(cache.v).at[bi, hi, sl].set(vt.astype(cache.v.dtype))[:, :, :s])


def pool_append(cache: PagedCache, k_new: Array, v_new: Array, length: Array,
                *, page: int, sink: int, local: int):
    """Fixed-pool append with eviction (paper §IV-A.3 'memory
    consideration'): the pool holds ``C_pool`` pages; when a NEW page opens
    and the pool is full, the live page with the LOWEST accumulated
    importance is overwritten. Sink and local-window pages are protected.

    k_new/v_new: (B, Hr, D); length: scalar. Slots are per-(B, H) (each
    head evicts independently, as in the paper).
    """
    b, h, c_pool, p_sz, d = cache.k_pages.shape
    pg = length // page
    off = length % page
    pos0 = (pg * page).astype(jnp.int32)

    # slot of the page currently open at pos0 (if any)
    is_open = cache.page_start == pos0                      # (B,H,C)
    open_slot = jnp.argmax(is_open, axis=-1).astype(jnp.int32)
    has_open = jnp.any(is_open, axis=-1)

    # eviction candidate: dead slots first, else lowest importance among
    # unprotected live pages (sink pages and the local window never evict)
    dead = cache.page_start < 0
    local_lo = jnp.maximum(length + 1 - local, 0)
    protected = (cache.page_start < sink) | (cache.page_start >= (local_lo // page) * page)
    protected &= ~dead
    evict_score = jnp.where(dead, -jnp.inf,
                            jnp.where(protected, jnp.inf, cache.importance))
    evict_slot = jnp.argmin(evict_score, axis=-1).astype(jnp.int32)

    slot = jnp.where(has_open, open_slot, evict_slot)       # (B,H)
    fresh = ~has_open                                       # opening a page

    bi = jnp.arange(b)[:, None]
    hi = jnp.arange(h)[None, :]
    kf = k_new.astype(jnp.float32)

    k_pages = cache.k_pages.at[bi, hi, slot, off].set(
        k_new.astype(cache.k_pages.dtype))
    v_pages = cache.v_pages.at[bi, hi, slot, off].set(
        v_new.astype(cache.v_pages.dtype))
    old_min = jnp.where(fresh[..., None], jnp.inf,
                        cache.tau_min[bi, hi, slot])
    old_max = jnp.where(fresh[..., None], -jnp.inf,
                        cache.tau_max[bi, hi, slot])
    tau_min = cache.tau_min.at[bi, hi, slot].set(jnp.minimum(old_min, kf))
    tau_max = cache.tau_max.at[bi, hi, slot].set(jnp.maximum(old_max, kf))
    imp = jnp.where(fresh, 0.0, cache.importance[bi, hi, slot])
    importance = cache.importance.at[bi, hi, slot].set(imp)
    page_start = cache.page_start.at[bi, hi, slot].set(
        jnp.broadcast_to(pos0, (b, h)))
    return dataclasses.replace(
        cache, k_pages=k_pages, v_pages=v_pages, tau_min=tau_min,
        tau_max=tau_max, importance=importance, page_start=page_start)


# ---------------------------------------------------------------------------
# Tiered hot/cold page residency (two-tier KV cache)
#
# The paged caches' k/v page rows are the only leaves that move between
# tiers: selection scores, page validity, and the append bookkeeping all
# read the metadata leaves (tau_min/tau_max/importance/page_start), which
# stay device-resident, so a spilled page is *selectable* (and its
# selection is bit-identical to the all-resident cache) even while its
# contents live in the far store. The serving engine detects
# selected-but-cold pages after the (metadata-only) selection, fills
# them, and replays the step — served late, never skipped.
#
# The three tree ops below are generic over a batched serve-state pytree:
# they path-match leaves whose key ends in ``.k_pages`` / ``.v_pages``
# and use the engine's leaf convention (batch axis 1 for scan-stacked
# "blocks" leaves, else 0; the page axis is two to the right of batch).
# Page-index vectors are fixed-length (the cache's page count) and
# -1-padded; padded entries are routed to a transient overflow row that
# is sliced away (the ``_ext_overflow`` trick), so each op is one compile
# regardless of how many pages move.
# ---------------------------------------------------------------------------


def _is_kv_page_leaf(ps: str) -> bool:
    return ps.endswith(".k_pages") or ps.endswith(".v_pages")


def _leaf_batch_axis(ps: str) -> int:
    return 1 if "['blocks']" in ps else 0


def gather_kv_page_rows(state, slot):
    """Read slot ``slot``'s k/v page rows out of the batched serve state.

    Returns ``{path: (C, ...)}`` — one stacked array per paged k/v leaf,
    page axis moved to the front. The engine device_gets this to archive
    pages into the far store before zeroing them on device.
    """
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        ps = jax.tree_util.keystr(path)
        if not _is_kv_page_leaf(ps):
            continue
        ax = _leaf_batch_axis(ps)
        row = jax.lax.dynamic_index_in_dim(leaf, slot, axis=ax,
                                           keepdims=False)
        out[ps] = jnp.moveaxis(row, ax + 1, 0)
    return out


def _update_kv_page_rows(state, slot, pages, value_fn):
    """Scatter into slot ``slot``'s page rows at physical page indices
    ``pages`` ((C,) int32, -1 padded). ``value_fn(path, ext, idx)``
    writes into the page-fronted, overflow-extended view ``ext``
    ((C+1, ...)); padded indices land on the overflow row, which is
    sliced away. Non-k/v leaves pass through untouched."""

    def upd(path, leaf):
        ps = jax.tree_util.keystr(path)
        if not _is_kv_page_leaf(ps):
            return leaf
        ax = _leaf_batch_axis(ps)
        row = jax.lax.dynamic_index_in_dim(leaf, slot, axis=ax,
                                           keepdims=False)
        moved = jnp.moveaxis(row, ax + 1, 0)                # (C, ...)
        c = moved.shape[0]
        ext = jnp.concatenate(
            [moved, jnp.zeros((1,) + moved.shape[1:], moved.dtype)], axis=0)
        idx = jnp.where(pages >= 0, pages, c).astype(jnp.int32)
        ext = value_fn(ps, ext, idx)
        row2 = jnp.moveaxis(ext[:c], 0, ax + 1)
        row2 = jnp.expand_dims(row2, ax)
        start = (0,) * ax + (slot,) + (0,) * (leaf.ndim - ax - 1)
        return jax.lax.dynamic_update_slice(leaf, row2.astype(leaf.dtype),
                                            start)

    return jax.tree_util.tree_map_with_path(upd, state)


def spill_kv_page_rows(state, slot, pages):
    """Zero the k/v contents of ``pages`` for slot ``slot`` (the cold
    tier's device-side residue — zero is the empty-page sentinel, so a
    spilled page is indistinguishable from an empty one to the kernels;
    only the untouched metadata says otherwise)."""
    return _update_kv_page_rows(
        state, slot, pages, lambda ps, ext, idx: ext.at[idx].set(0))


def fill_kv_page_rows(state, slot, pages, rows):
    """Restore far-store rows into ``pages`` of slot ``slot``. ``rows``
    is ``{path: (C, ...)}`` aligned with ``pages`` entry-wise (padding
    entries carry zeros and land on the discarded overflow row). Exact
    inverse of spill: the page contents return bit-identical."""
    return _update_kv_page_rows(
        state, slot, pages,
        lambda ps, ext, idx: ext.at[idx].set(rows[ps].astype(ext.dtype)))


# -- batched (slot, page)-pair variants (PR 10) -----------------------------
#
# One refresh plan touches many slots; the per-slot ops above would cost
# one dispatch per slot per direction. These variants take fixed-length
# -1-padded (M,) slot/page index vectors — M is the engine's static pair
# capacity (n_slots x n_pages), so ONE compiled program applies any
# refresh plan as one batched gather plus one batched scatter per
# direction. Same overflow-row trick, lifted to the flattened
# (batch x page) row space; (slot, page) pairs are unique by
# construction, so the scatters never collide.


def _pair_flat(leaf, ps: str):
    """Leaf -> ((B*C, ...) pair-row view, the (B, C, ...) shape, ax)."""
    ax = _leaf_batch_axis(ps)
    m = jnp.moveaxis(leaf, ax, 0)          # batch to front
    m = jnp.moveaxis(m, ax + 2, 1)         # page axis rides at ax+2
    return m.reshape((-1,) + m.shape[2:]), m.shape, ax


def _pair_idx(slots, pages, c: int, n: int):
    """Flattened pair-row indices; padded (-1) pairs -> overflow row n."""
    fi = slots.astype(jnp.int32) * c + pages.astype(jnp.int32)
    return jnp.where((slots >= 0) & (pages >= 0), fi, n)


def gather_kv_rows_pairs(state, slots, pages):
    """Batched ``gather_kv_page_rows``: read M (slot, page) page rows out
    of the batched serve state in one program. Returns
    ``{path: (M, ...)}``; padded pairs return zeros."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        ps = jax.tree_util.keystr(path)
        if not _is_kv_page_leaf(ps):
            continue
        flat, mshape, _ = _pair_flat(leaf, ps)
        n, c = flat.shape[0], mshape[1]
        ext = jnp.concatenate(
            [flat, jnp.zeros((1,) + flat.shape[1:], flat.dtype)], axis=0)
        out[ps] = ext[_pair_idx(slots, pages, c, n)]
    return out


def _update_kv_rows_pairs(state, slots, pages, value_fn):
    def upd(path, leaf):
        ps = jax.tree_util.keystr(path)
        if not _is_kv_page_leaf(ps):
            return leaf
        flat, mshape, ax = _pair_flat(leaf, ps)
        n, c = flat.shape[0], mshape[1]
        ext = jnp.concatenate(
            [flat, jnp.zeros((1,) + flat.shape[1:], flat.dtype)], axis=0)
        ext = value_fn(ps, ext, _pair_idx(slots, pages, c, n))
        m2 = ext[:n].reshape(mshape)
        m2 = jnp.moveaxis(m2, 1, ax + 2)
        return jnp.moveaxis(m2, 0, ax).astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(upd, state)


def spill_kv_rows_pairs(state, slots, pages):
    """Batched ``spill_kv_page_rows``: zero M (slot, page) page rows in
    one program (zero is the empty-page sentinel)."""
    return _update_kv_rows_pairs(
        state, slots, pages, lambda ps, ext, idx: ext.at[idx].set(0))


def fill_kv_rows_pairs(state, slots, pages, rows):
    """Batched ``fill_kv_page_rows``: restore ``{path: (M, ...)}``
    far-store rows into M (slot, page) page rows in one program. Exact
    inverse of the batched spill."""
    return _update_kv_rows_pairs(
        state, slots, pages,
        lambda ps, ext, idx: ext.at[idx].set(rows[ps].astype(ext.dtype)))


class TieredPagedCache:
    """Host-side residency controller for the two-tier paged KV cache.

    Tracks, per engine slot, which **physical** pages are device-resident
    (``resident`` bitmap) and archives spilled page rows in a host far
    store (``far``) keyed ``(slot, phys_page) -> {path: np row}`` — the
    simulated HB far bank (hbsim/sim.py costs the traffic). The policy
    methods are pure bookkeeping over numpy; the device-side spill/fill
    tree ops live next to it in this module and are dispatched by the
    serving engine.

    Residency policy (exactness-safe by construction):

    * **Pinned (never spilled):** sink pages, every page at or above the
      local-window start ``first_local(ctx)`` (local span + the current
      append page + not-yet-written pages), and the currently selected
      pages. Since ``first_local`` only grows with context, a page below
      it is complete and will never be appended to or re-enter the local
      window — the *only* way a spilled page is read again is via
      selection, which is metadata-only and therefore miss-detectable.
    * **Hot set:** pinned pages plus the ``hot_pages`` - |pinned| most
      important spill candidates (the accumulated Quest hotness the
      selector maintains). ``hot_pages`` is a soft per-slot budget: pins
      may exceed it.
    * **Refresh:** at each selection refresh the engine asks
      ``plan_refresh`` for pages to prefetch (``to_fill`` — hot again
      but cold on device; fetched one share window ahead of the next
      selection) and pages to spill (``to_spill``).

    Physical vs logical: ``stripe_shards`` > 1 applies the coplace_shmap
    round-robin page striping (core/paging.interleave_slot); selection
    indices and importance are already physical there, so the bitmap and
    far store are kept in physical page space and only the sink/local
    pins are mapped through the stripe.
    """

    def __init__(self, *, n_slots: int, n_pages: int, hot_pages: int,
                 page_size: int, sink: int, local: int,
                 stripe_shards: int = 1):
        from repro.core import paging

        self.n_slots = int(n_slots)
        self.n_pages = int(n_pages)
        self.hot_pages = int(hot_pages)
        self.page_size = int(page_size)
        self.sink = int(sink)
        self.local = int(local)
        self.stripe = max(int(stripe_shards), 1)
        self.n_sink_pages, _ = paging.page_counts(
            sink=sink, local=local, page=page_size)
        self.resident = np.ones((self.n_slots, self.n_pages), bool)
        self.far: dict = {}   # (slot, phys_page) -> {path: np row}

    # -- page-space mapping -------------------------------------------
    def phys(self, logical: int) -> int:
        from repro.core import paging

        if self.stripe == 1:
            return int(logical)
        return int(paging.interleave_slot(logical, self.n_pages,
                                          self.stripe))

    def first_local(self, ctx: int) -> int:
        return max(int(ctx) - self.local, 0) // self.page_size

    def data_pages(self, ctx: int) -> int:
        return -(-int(ctx) // self.page_size)

    # -- residency bookkeeping ----------------------------------------
    def reset_slot(self, slot: int):
        """Slot retired or (re)admitted: the next occupant's pack/reset
        overwrites every device row, so the whole slot is resident."""
        self.resident[slot] = True
        for key in [k for k in self.far if k[0] == slot]:
            del self.far[key]

    def missing(self, slot: int, pages) -> list:
        """Subset of physical ``pages`` not device-resident (the cold
        misses of a fresh selection)."""
        return [p for p in pages if not self.resident[slot, p]]

    def store_rows(self, slot: int, pages, rows: dict):
        """Archive gathered page rows (``{path: (C, ...)}``) into the far
        store. Idempotent per page: a page already archived keeps its
        copy (complete pages never change on device, so the copy stays
        exact across spill/fill/spill cycles)."""
        for p in pages:
            if (slot, p) in self.far:
                continue
            self.far[(slot, p)] = {ps: np.asarray(buf[p]).copy()
                                   for ps, buf in rows.items()}

    def store_pair_rows(self, slots, pages, rows: dict, count: int):
        """Archive a batched pair gather (``{path: (M, ...)}`` aligned
        with the (slot, page) index vectors; first ``count`` entries
        real). Same idempotence rule as ``store_rows``."""
        for i in range(count):
            key = (int(slots[i]), int(pages[i]))
            if key in self.far:
                continue
            self.far[key] = {ps: np.asarray(buf[i]).copy()
                             for ps, buf in rows.items()}

    # -- policy --------------------------------------------------------
    def spill_candidates(self, slot: int, ctx: int, selected) -> list:
        """Physical pages legal to spill: complete pages strictly between
        the sink and local sections, minus ``selected``."""
        fl = self.first_local(ctx)
        return [self.phys(p) for p in range(self.n_sink_pages, fl)
                if self.phys(p) not in selected]

    def plan_refresh(self, slot: int, ctx: int, selected, hotness):
        """(to_fill, to_spill) physical page lists for one refresh.

        ``selected`` — the slot's fresh physical selection (already
        resident: misses were repaired before this runs); ``hotness`` —
        (n_pages,) accumulated importance in physical page space. The
        want-set is pins ∪ top-m candidates by hotness, m sized so the
        resident data pages meet the ``hot_pages`` budget."""
        fl = self.first_local(ctx)
        nd = self.data_pages(ctx)
        cand = self.spill_candidates(slot, ctx, selected)
        pinned_data = (min(self.n_sink_pages, nd) + max(nd - fl, 0)
                       + len(selected))
        m = max(self.hot_pages - pinned_data, 0)
        order = sorted(cand, key=lambda p: (-float(hotness[p]), p))
        want = set(order[:m])
        to_fill = [p for p in order[:m] if not self.resident[slot, p]]
        to_spill = [p for p in cand
                    if p not in want and self.resident[slot, p]]
        return to_fill, to_spill


# ---------------------------------------------------------------------------
# Prefill constructors (build caches from full-sequence K/V)
# ---------------------------------------------------------------------------


def paged_cache_from_prefill(k, v, num_pages, page, top_k):
    """k/v: (B, S, Hr, D) -> PagedCache with S//P pages filled (S % P == 0)."""
    b, s, h, d = k.shape
    n_filled = s // page
    kp = k.transpose(0, 2, 1, 3).reshape(b, h, n_filled, page, d)
    vp = v.transpose(0, 2, 1, 3).reshape(b, h, n_filled, page, d)
    pad = num_pages - n_filled
    kf = kp.astype(jnp.float32)
    tau_min = jnp.pad(kf.min(axis=3), ((0, 0), (0, 0), (0, pad), (0, 0)),
                      constant_values=jnp.inf)
    tau_max = jnp.pad(kf.max(axis=3), ((0, 0), (0, 0), (0, pad), (0, 0)),
                      constant_values=-jnp.inf)
    z = ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))
    start = jnp.arange(num_pages, dtype=jnp.int32) * page
    start = jnp.where(jnp.arange(num_pages) < n_filled, start, -1)
    return PagedCache(
        k_pages=jnp.pad(kp, z), v_pages=jnp.pad(vp, z),
        tau_min=tau_min, tau_max=tau_max,
        importance=jnp.zeros((b, h, num_pages), jnp.float32),
        page_start=jnp.broadcast_to(start, (b, h, num_pages)).astype(jnp.int32),
        sel_idx=jnp.zeros((b, h, top_k), jnp.int32),
    )


def stream_cache_from_prefill(k, v, *, sink, local_cap, length):
    """k/v: (B, S, Hs, D); keep sink + last min(local_cap, S-sink) tokens.

    ``length`` is the static int prefill length (== S).
    """
    b, s, h, d = k.shape
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    w = sink + local_cap
    cache = make_stream_cache(b, h, sink, local_cap, d, dtype=k.dtype)
    # positions that belong in the ring and their slots
    pos = jnp.arange(s)
    slot = jnp.where(pos < sink, pos, sink + (pos - sink) % local_cap)
    keep = (pos < sink) | (pos >= max(sink, length - local_cap))
    # scatter (later positions win, matching ring semantics) — iterate via
    # segment trick: sort by (keep, pos) then scatter
    slot_eff = jnp.where(keep, slot, w)  # dump discarded into overflow slot
    kk = jnp.zeros((b, h, w + 1, d), k.dtype).at[:, :, slot_eff].set(k)
    vv = jnp.zeros((b, h, w + 1, d), v.dtype).at[:, :, slot_eff].set(v)
    pp = jnp.full((b, h, w + 1), -1, jnp.int32).at[:, :, slot_eff].set(
        jnp.broadcast_to(pos, (b, h, s)).astype(jnp.int32))
    return StreamCache(k=kk[:, :, :w], v=vv[:, :, :w], pos=pp[:, :, :w])
