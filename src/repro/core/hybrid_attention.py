"""H²EAL hybrid static-dynamic sparse attention (paper §IV-A).

Per attention layer, KV heads are ordered by a per-layer permutation
(produced by the scheduler, sched/tiling.py) so that the first
``n_retrieval`` kv heads are retrieval heads and the rest are streaming
heads. Counts are static (static_sparsity is a global proportion, paper
§V-B), the permutation is data — so every layer lowers to the same program
and the whole stack scans.

Prefill:  retrieval heads -> full causal flash attention;
          streaming heads -> sink+local flash attention.
Decode:   retrieval heads -> page-score -> top-k -> paged attention over
          [sink pages | selected pages | local pages];
          streaming heads -> attention over the sink+local ring buffer.
Selection is recomputed every ``share_window`` steps (``do_select``).

This module holds the attention BODIES. Layout dispatch lives one level
up in core/layouts.py (the AttentionLayout registry + the DecodeInputs
pytree): ``decode_attention`` backs the ``default`` layout (and, via
GSPMD repartitioning of the same program, ``head``/``coplace``/
``interleave``); ``decode_attention_coplace`` backs ``coplace_shmap``.
Calling these functions directly with their long positional signatures
still works but is a deprecated path kept for one release — new code
should go through ``layouts.dispatch_decode``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.configs.base import H2ealConfig
from repro.core import cache as cachelib
from repro.core import paging
from repro.kernels import ops as kops

Array = jax.Array


@dataclass(frozen=True)
class AttnSpec:
    """Static attention-layer spec (hashable; safe as jit static arg)."""

    n_q: int
    n_kv: int
    head_dim: int
    h2: H2ealConfig
    window: int = 0        # >0: plain sliding-window layer (gemma3 local)
    impl: str = "ref"

    @property
    def group(self) -> int:
        return self.n_q // self.n_kv

    @property
    def n_retrieval(self) -> int:
        if not self.h2.enabled or self.window > 0:
            return self.n_kv
        n_s = round(self.n_kv * self.h2.static_sparsity)
        return max(self.n_kv - n_s, 0)

    @property
    def n_streaming(self) -> int:
        return self.n_kv - self.n_retrieval


def identity_perm(spec: AttnSpec) -> Array:
    return jnp.arange(spec.n_kv, dtype=jnp.int32)


def _permute_kv(x: Array, perm: Array) -> Array:
    """x: (..., Hkv, ...) permuted on the kv-head axis (axis 2 of B,S,H,D
    or axis 1 of B,H,D)."""
    axis = 2 if x.ndim == 4 else 1
    return jnp.take(x, perm, axis=axis)


def _permute_q(q: Array, perm: Array, group: int) -> Array:
    """q: (B, S, Hq, D) or (B, Hq, D): permute q heads following kv groups."""
    if q.ndim == 4:
        b, s, hq, d = q.shape
        qg = q.reshape(b, s, hq // group, group, d)
        qg = jnp.take(qg, perm, axis=2)
        return qg.reshape(b, s, hq, d)
    b, hq, d = q.shape
    qg = q.reshape(b, hq // group, group, d)
    qg = jnp.take(qg, perm, axis=1)
    return qg.reshape(b, hq, d)


def _inverse_perm(perm: Array) -> Array:
    n = perm.shape[0]
    return jnp.zeros((n,), jnp.int32).at[perm].set(
        jnp.arange(n, dtype=jnp.int32))


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


def prefill_attention(spec: AttnSpec, q: Array, k: Array, v: Array,
                      perm: Array | None = None) -> Array:
    """q: (B,S,Hq,D); k/v: (B,S,Hkv,D) -> (B,S,Hq,D)."""
    h2 = spec.h2
    if spec.window > 0:  # plain sliding-window layer
        return kops.flash_attention(q, k, v, causal=True, window=spec.window,
                                    impl=spec.impl)
    if not h2.enabled or spec.n_streaming == 0:
        return kops.flash_attention(q, k, v, causal=True, impl=spec.impl)
    if perm is None:
        perm = identity_perm(spec)
    g = spec.group
    nr = spec.n_retrieval
    qp = _permute_q(q, perm, g)
    kp = _permute_kv(k, perm)
    vp = _permute_kv(v, perm)
    outs = []
    if nr > 0:
        outs.append(kops.flash_attention(
            qp[:, :, : nr * g], kp[:, :, :nr], vp[:, :, :nr],
            causal=True, impl=spec.impl))
    if spec.n_streaming > 0:
        outs.append(kops.flash_attention(
            qp[:, :, nr * g:], kp[:, :, nr:], vp[:, :, nr:],
            causal=True, window=h2.local, sink=h2.sink, impl=spec.impl))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=2)
    return _permute_q(out, _inverse_perm(perm), g)


def init_decode_state(spec: AttnSpec, k: Array, v: Array, length: int,
                      capacity: int, perm: Array | None = None,
                      interleave_shards: int = 1):
    """Build (PagedCache, StreamCache) from prefill K/V.

    k/v: (B, S, Hkv, D) post-RoPE; length == S (static). capacity: max
    context (tokens) the paged cache must hold. interleave_shards > 1 lays
    pages out round-robin across that many page-dim shards (co-placement).
    """
    h2 = spec.h2
    if perm is None:
        perm = identity_perm(spec)
    kp = jnp.take(k, perm, axis=2)
    vp = jnp.take(v, perm, axis=2)
    nr = spec.n_retrieval
    p = h2.page_size
    num_pages = -(-capacity // p)
    # pad sequence to page multiple for the paged constructor (stream cache
    # is built from the UNPADDED sequence below)
    s = k.shape[1]
    pad = (-s) % p
    kpad, vpad = kp, vp
    if pad:
        kpad = jnp.pad(kp, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vpad = jnp.pad(vp, ((0, 0), (0, pad), (0, 0), (0, 0)))
    paged = cachelib.paged_cache_from_prefill(
        kpad[:, :, :nr], vpad[:, :, :nr], num_pages, p, h2.top_k_pages)
    if pad:  # recompute metadata masking the pad tokens of the last page
        offs = (jnp.arange(num_pages * p) < s).reshape(num_pages, p)
        kpp = paged.k_pages.astype(jnp.float32)
        tau_min = jnp.where(offs[None, None, :, :, None], kpp, jnp.inf).min(3)
        tau_max = jnp.where(offs[None, None, :, :, None], kpp, -jnp.inf).max(3)
        paged = dataclasses.replace(paged, tau_min=tau_min, tau_max=tau_max)
    if interleave_shards > 1:
        # permute the page dim to the interleaved physical layout:
        # physical slot p holds logical page (p % c_loc) * nsh + p // c_loc
        nsh = interleave_shards
        assert num_pages % nsh == 0, (
            f"page capacity {num_pages} must divide by {nsh} shards")
        c_loc = num_pages // nsh
        phys = jnp.arange(num_pages)
        logical_of_phys = (phys % c_loc) * nsh + phys // c_loc
        take = lambda a: jnp.take(a, logical_of_phys, axis=2)
        paged = cachelib.PagedCache(
            k_pages=take(paged.k_pages), v_pages=take(paged.v_pages),
            tau_min=take(paged.tau_min), tau_max=take(paged.tau_max),
            importance=take(paged.importance),
            page_start=take(paged.page_start),
            sel_idx=paged.sel_idx)
    stream = cachelib.stream_cache_from_prefill(
        kp[:, :, nr:], vp[:, :, nr:], sink=h2.sink,
        local_cap=_local_cap(h2), length=length)
    return paged, stream


def chunk_prefill_attention(
    spec: AttnSpec,
    q: Array,                  # (B, C, Hq, D) roped at the chunk positions
    k_new: Array,              # (B, C, Hkv, D) roped
    v_new: Array,              # (B, C, Hkv, D)
    paged: cachelib.PagedCache,
    stream: cachelib.StreamCache,
    start: Array,              # (B,) context length BEFORE the chunk
    chunk_len: Array,          # (B,) valid tokens in the chunk
    active: Array | None = None,   # (B,) bool — slots prefilling this step
    *,
    perm: Array | None = None,
    phys_shards: int = 1,
):
    """One chunked-prefill step: append a prompt chunk into the serve
    caches and attend each chunk token causally over everything before it
    (retrieval heads: full causal, exactly single-shot prefill; streaming
    heads: sink+local). Returns (out (B, C, Hq, D), paged', stream').

    There is no page selection during prefill — selection state
    (sel_idx / importance) is untouched, matching the single-shot
    prefill-then-pack constructor. Rows past ``chunk_len`` (and inactive
    slots) append nothing; their outputs are garbage the caller ignores.
    Touched pages must start from the empty sentinels (the engine resets
    a slot's cache rows at admission), so the incremental min/max
    metadata merge is exact.

    ``phys_shards`` > 1 applies the coplace_shmap round-robin physical
    page order on append; validity is derived from absolute positions
    (in-op for the retrieval body, core/paging.py chunk_* helpers for
    the streaming ring) so the math is identical on every layout. The
    retrieval body is ``kops.chunk_attention_paged`` selected by static
    ``spec.impl`` — ref or the Pallas fused-gather kernel — and attends
    the PRE-append buffer plus the chunk's own KV, so the page scatter
    never serializes before the attention. Numerics: the chunk body
    reassociates float sums differently from the single-shot flash
    prefill, so chunked and packed admission agree to float tolerance —
    greedy traces match off argmax ties (EXPERIMENTS.md §Serving
    experiments).
    """
    h2 = spec.h2
    g = spec.group
    nr = spec.n_retrieval
    if perm is None:
        perm = identity_perm(spec)
    qp = _permute_q(q, perm, g)
    kp = _permute_kv(k_new, perm)
    vp = _permute_kv(v_new, perm)
    b, cch = q.shape[0], q.shape[1]
    act = jnp.ones((b,), bool) if active is None else \
        jnp.asarray(active).reshape(b)
    start = jnp.broadcast_to(start, (b,)).astype(jnp.int32)
    pos_q = paging.chunk_positions(start, cch)              # (B, C)

    outs = []
    if nr > 0:
        # fused pre-append body: the chunk attends [paged buffer ∥ chunk
        # keys] with validity computed from page metadata inside the op
        # (per-key for the buffer, static causal for the chunk) — no
        # (B, H, Cq, T) mask, and the append no longer serializes before
        # the attention. Under coplace_shmap the physical page striping
        # only reorders pages; page_start rides along, so the in-op
        # position math is layout-invariant.
        k_r = kp[:, :, :nr]
        v_r = vp[:, :, :nr]
        outs.append(kops.chunk_attention_paged(
            qp[:, :, : nr * g], paged.k_pages, paged.v_pages,
            paged.page_start, start, k_r, v_r, impl=spec.impl))
        paged = cachelib.paged_cache_append_chunk(
            paged, k_r, v_r, start, chunk_len,
            active=act, phys_shards=phys_shards)
    if spec.n_streaming > 0:
        ns = spec.n_streaming
        k_s = kp[:, :, nr:]                                 # (B, C, Hs, D)
        v_s = vp[:, :, nr:]
        # attend against [pre-append ring ∥ chunk keys]: ring slots can be
        # overwritten WITHIN a chunk (positions local_cap apart share a
        # slot), so the post-append ring would lose keys still inside an
        # early chunk query's window
        kr = jnp.concatenate([stream.k, k_s.transpose(0, 2, 1, 3)], axis=2)
        vr = jnp.concatenate([stream.v, v_s.transpose(0, 2, 1, 3)], axis=2)
        chunk_pos = jnp.broadcast_to(pos_q[:, None, :], (b, ns, cch))
        kpos = jnp.concatenate([stream.pos, chunk_pos], axis=2)
        valid_s = paging.chunk_stream_validity(kpos, pos_q, sink=h2.sink,
                                               local=h2.local)
        outs.append(kops.chunk_attention(qp[:, :, nr * g:], kr, vr, valid_s,
                                         impl=spec.impl))
        stream = cachelib.stream_cache_append_chunk(
            stream, k_s, v_s, start, chunk_len, sink=h2.sink, active=act)
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=2)
    return _permute_q(out, _inverse_perm(perm), g), paged, stream


def _local_cap(h2: H2ealConfig) -> int:
    # ring capacity: local window + one page of slack so the boundary page
    # semantics match the paged side
    return h2.local + h2.page_size


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def decode_attention(
    spec: AttnSpec,
    q: Array,                 # (B, Hq, D) roped at position `length`
    k_new: Array,             # (B, Hkv, D) roped
    v_new: Array,             # (B, Hkv, D)
    paged: cachelib.PagedCache,
    stream: cachelib.StreamCache,
    length: Array,            # context BEFORE this token: scalar or (B,)
    *,
    do_select: bool,
    perm: Array | None = None,
    active: Array | None = None,       # (B,) bool — ragged batch only
    need_select: Array | None = None,  # (B,) bool — per-slot share window
):
    """One decode step. Returns (out (B,Hq,D), paged', stream').

    Uniform (lockstep) batches pass a scalar ``length``; the
    continuous-batching engine passes per-slot (B,) lengths plus ``active``
    (inactive slots neither append nor advance — their caches are
    bit-stable) and ``need_select`` (per-slot share-window phase: under the
    ``do_select`` variant only slots whose window expired take the fresh
    page selection / importance update; the rest keep their cached
    selection, exactly as if the select step had not run for them).
    """
    h2 = spec.h2
    g = spec.group
    nr = spec.n_retrieval
    if perm is None:
        perm = identity_perm(spec)
    qp = _permute_q(q, perm, g)
    kp = _permute_kv(k_new, perm)
    vp = _permute_kv(v_new, perm)
    q_r, q_s = qp[:, : nr * g], qp[:, nr * g:]
    k_r, k_s = kp[:, :nr], kp[:, nr:]
    v_r, v_s = vp[:, :nr], vp[:, nr:]
    ctx = length + 1

    outs = []
    if nr > 0:
        paged = cachelib.paged_cache_append(paged, k_r, v_r, length,
                                            active=active)
        if do_select:
            scores = paging.score_pages(
                q_r, paged.tau_min, paged.tau_max, paged.page_start, ctx,
                sink=h2.sink, local=h2.local, page=h2.page_size,
                impl=spec.impl)
            sel = paging.select_pages(scores, h2.top_k_pages)
            imp = paging.accumulate_importance(paged.importance, scores)
            if need_select is not None:
                ns = need_select[:, None, None]
                sel = jnp.where(ns, sel, paged.sel_idx)
                imp = jnp.where(ns, imp, paged.importance)
            paged = dataclasses.replace(paged, sel_idx=sel, importance=imp)
        slots = paging.attended_page_slots(
            paged.sel_idx, ctx, sink=h2.sink, local=h2.local,
            page=h2.page_size)
        gk, gv = paging.gather_pages(paged.k_pages, paged.v_pages, slots)
        valid = paging.token_validity(
            slots, paged.page_start, ctx, sink=h2.sink, local=h2.local,
            page=h2.page_size, top_k=h2.top_k_pages)
        outs.append(kops.paged_attention(q_r, gk, gv, valid, impl=spec.impl))
    if spec.n_streaming > 0:
        stream = cachelib.stream_cache_append(
            stream, k_s, v_s, length, sink=h2.sink, active=active)
        # exact sink+local mask (ring carries one page of slack)
        ctx_b = jnp.broadcast_to(jnp.asarray(ctx, jnp.int32),
                                 (q.shape[0],))[:, None, None]
        valid_s = (stream.pos >= 0) & (
            (stream.pos < h2.sink) | (stream.pos >= ctx_b - h2.local))
        outs.append(kops.paged_attention(
            q_s, stream.k, stream.v, valid_s, impl=spec.impl))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    out = _permute_q(out, _inverse_perm(perm), g)
    return out, paged, stream


# ---------------------------------------------------------------------------
# Speculative verify (PR 8): k decode steps in one chunked forward
# ---------------------------------------------------------------------------


def chunk_verify_attention(
    spec: AttnSpec,
    q: Array,                  # (B, k, Hq, D) roped at start .. start+k-1
    k_new: Array,              # (B, k, Hkv, D) roped
    v_new: Array,              # (B, k, Hkv, D)
    paged: cachelib.PagedCache,
    stream: cachelib.StreamCache,
    start: Array,              # (B,) context length BEFORE the chunk
    active: Array | None = None,
    need_select: Array | None = None,
    *,
    perm: Array | None = None,
    phys_shards: int = 1,
):
    """Verify k drafted tokens: each chunk query attends exactly what its
    sequential decode step would, WITHOUT mutating the KV pages or the
    stream ring (attend-before-append — acceptance decides how much of the
    chunk ``chunk_verify_append`` later commits, so no rollback of the
    non-invertible tau scatter-min/max is ever needed). Returns
    (out (B, k, Hq, D), paged', stream) where paged' carries only the
    refreshed selection / importance (gated by ``need_select & active``;
    gated-off slots keep them bit-stable, exactly a reuse step).

    Selection is scored once per chunk with query 0 at context start+1 —
    the same query, context, and (because the page receiving position
    start is never selectable) the same tau metadata the sequential select
    step uses, so the refreshed selection is bitwise that of the
    sequential engine; max_emit clamping in the engine guarantees no
    share-window boundary falls inside a chunk. Keys come from the
    gathered [sink | selected | local] buffer (per-query sectioning via
    paging.verify_token_validity) concatenated with the chunk's own keys
    under a causal triangle. ``phys_shards`` > 1 maps the fixed sections
    through the coplace_shmap physical page order; scoring and top-k read
    physical-order metadata directly (page_start carries absolute
    positions), so this single program is layout-transparent, like
    chunk_prefill_attention.
    """
    h2 = spec.h2
    g = spec.group
    nr = spec.n_retrieval
    if perm is None:
        perm = identity_perm(spec)
    qp = _permute_q(q, perm, g)
    kp = _permute_kv(k_new, perm)
    vp = _permute_kv(v_new, perm)
    b, kch = q.shape[0], q.shape[1]
    act = jnp.ones((b,), bool) if active is None else \
        jnp.asarray(active).reshape(b)
    need = jnp.ones((b,), bool) if need_select is None else \
        jnp.asarray(need_select).reshape(b)
    start = jnp.broadcast_to(start, (b,)).astype(jnp.int32)
    pos_q = paging.chunk_positions(start, kch)              # (B, k)
    ctx1 = start + 1

    outs = []
    if nr > 0:
        q_r = qp[:, :, : nr * g]                            # (B, k, HqR, D)
        k_r = kp[:, :, :nr]
        v_r = vp[:, :, :nr]
        scores = paging.score_pages(
            q_r[:, 0], paged.tau_min, paged.tau_max, paged.page_start,
            ctx1, sink=h2.sink, local=h2.local, page=h2.page_size,
            impl=spec.impl)
        sel = paging.select_pages(scores, h2.top_k_pages)
        imp = paging.accumulate_importance(paged.importance, scores)
        ns = (need & act)[:, None, None]
        sel = jnp.where(ns, sel, paged.sel_idx)
        imp = jnp.where(ns, imp, paged.importance)
        paged = dataclasses.replace(paged, sel_idx=sel, importance=imp)
        slots = paging.verify_attended_slots(
            paged.sel_idx, ctx1, sink=h2.sink, local=h2.local,
            page=h2.page_size, capacity=paged.k_pages.shape[2],
            n_shards=phys_shards)
        gk, gv = paging.gather_pages(paged.k_pages, paged.v_pages, slots)
        valid_p = paging.verify_token_validity(
            slots, paged.page_start, start, pos_q, sink=h2.sink,
            local=h2.local, page=h2.page_size, top_k=h2.top_k_pages)
        kr = jnp.concatenate(
            [gk, k_r.transpose(0, 2, 1, 3).astype(gk.dtype)], axis=2)
        vr = jnp.concatenate(
            [gv, v_r.transpose(0, 2, 1, 3).astype(gv.dtype)], axis=2)
        tail = jnp.tril(jnp.ones((kch, kch), bool))         # key i <= query j
        valid = jnp.concatenate([
            valid_p,
            jnp.broadcast_to(tail[None, None], (b, nr, kch, kch)),
        ], axis=3)
        outs.append(kops.chunk_attention(q_r, kr, vr, valid, impl=spec.impl))
    if spec.n_streaming > 0:
        n_s = spec.n_streaming
        k_s = kp[:, :, nr:]
        v_s = vp[:, :, nr:]
        kr = jnp.concatenate([stream.k, k_s.transpose(0, 2, 1, 3)], axis=2)
        vr = jnp.concatenate([stream.v, v_s.transpose(0, 2, 1, 3)], axis=2)
        chunk_pos = jnp.broadcast_to(pos_q[:, None, :], (b, n_s, kch))
        kpos = jnp.concatenate([stream.pos, chunk_pos], axis=2)
        valid_s = paging.chunk_stream_validity(kpos, pos_q, sink=h2.sink,
                                               local=h2.local)
        outs.append(kops.chunk_attention(qp[:, :, nr * g:], kr, vr, valid_s,
                                         impl=spec.impl))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=2)
    return _permute_q(out, _inverse_perm(perm), g), paged, stream


def chunk_verify_append(
    spec: AttnSpec,
    k_new: Array,              # (B, k, Hkv, D) roped — the VERIFIED chunk
    v_new: Array,
    paged: cachelib.PagedCache,
    stream: cachelib.StreamCache,
    start: Array,              # (B,) context length before the chunk
    accepted: Array,           # (B,) tokens of the chunk to commit (>= 1)
    active: Array | None = None,
    *,
    perm: Array | None = None,
    phys_shards: int = 1,
):
    """Commit the accepted prefix of a verified chunk into the serve
    caches via the ragged chunk appends (PR 5) — the same scatter +
    incremental tau min/max merge a sequence of single-token appends
    performs, so committed state is bitwise what sequential decode leaves
    behind. Returns (paged', stream')."""
    h2 = spec.h2
    nr = spec.n_retrieval
    if perm is None:
        perm = identity_perm(spec)
    kp = _permute_kv(k_new, perm)
    vp = _permute_kv(v_new, perm)
    b = k_new.shape[0]
    act = jnp.ones((b,), bool) if active is None else \
        jnp.asarray(active).reshape(b)
    if nr > 0:
        paged = cachelib.paged_cache_append_chunk(
            paged, kp[:, :, :nr], vp[:, :, :nr], start, accepted,
            active=act, phys_shards=phys_shards)
    if spec.n_streaming > 0:
        stream = cachelib.stream_cache_append_chunk(
            stream, kp[:, :, nr:], vp[:, :, nr:], start, accepted,
            sink=h2.sink, active=act)
    return paged, stream


# ---------------------------------------------------------------------------
# Fixed-pool decode with eviction (paper §IV-A.3 "memory consideration")
# ---------------------------------------------------------------------------


def decode_attention_pool(
    spec: AttnSpec,
    q, k_new, v_new,
    paged: cachelib.PagedCache,
    stream: cachelib.StreamCache,
    length,
    *,
    do_select: bool,
    perm=None,
):
    """Decode against a FIXED-SIZE page pool (capacity = kv_budget tokens):
    when the pool is full, the lowest-accumulated-importance page is
    overwritten (sink/local pages protected). Slots are arbitrary — sink
    and local pages are found by their stored start positions.
    """
    h2 = spec.h2
    g = spec.group
    nr = spec.n_retrieval
    if perm is None:
        perm = identity_perm(spec)
    qp = _permute_q(q, perm, g)
    kp = _permute_kv(k_new, perm)
    vp = _permute_kv(v_new, perm)
    q_r, q_s = qp[:, : nr * g], qp[:, nr * g:]
    ctx = length + 1
    p_sz = h2.page_size

    outs = []
    if nr > 0:
        paged = cachelib.pool_append(
            paged, kp[:, :nr], vp[:, :nr], length,
            page=p_sz, sink=h2.sink, local=h2.local)
        if do_select:
            scores = paging.score_pages(
                q_r, paged.tau_min, paged.tau_max, paged.page_start, ctx,
                sink=h2.sink, local=h2.local, page=p_sz, impl=spec.impl)
            sel = paging.select_pages(scores, h2.top_k_pages)
            paged = dataclasses.replace(
                paged, sel_idx=sel,
                importance=paging.accumulate_importance(
                    paged.importance, scores))
        # sink/local slots by position lookup (pool slots are arbitrary)
        n_sink, n_local = paging.page_counts(sink=h2.sink, local=h2.local,
                                             page=p_sz)
        first_local = jnp.maximum(ctx - h2.local, 0) // p_sz
        sink_pos = jnp.arange(n_sink, dtype=jnp.int32) * p_sz
        local_pos = (first_local + jnp.arange(n_local, dtype=jnp.int32)) * p_sz
        sink_slots = paging.slots_of_positions(paged.page_start, sink_pos)
        local_slots = paging.slots_of_positions(paged.page_start, local_pos)
        slots = jnp.concatenate([sink_slots, paged.sel_idx, local_slots],
                                axis=2)
        gk, gv = paging.gather_pages(paged.k_pages, paged.v_pages, slots)
        valid = paging.token_validity(
            slots, paged.page_start, ctx, sink=h2.sink, local=h2.local,
            page=p_sz, top_k=h2.top_k_pages)
        outs.append(kops.paged_attention(q_r, gk, gv, valid, impl=spec.impl))
    if spec.n_streaming > 0:
        stream = cachelib.stream_cache_append(
            stream, kp[:, nr:], vp[:, nr:], length, sink=h2.sink)
        valid_s = (stream.pos >= 0) & (
            (stream.pos < h2.sink) | (stream.pos >= ctx - h2.local))
        outs.append(kops.paged_attention(
            q_s, stream.k, stream.v, valid_s, impl=spec.impl))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    out = _permute_q(out, _inverse_perm(perm), g)
    return out, paged, stream


# ---------------------------------------------------------------------------
# Distributed memory-compute co-placement (paper §IV-B via shard_map)
# ---------------------------------------------------------------------------
#
# The paged KV cache is sharded across the 'model' axis on the PAGE dim
# with interleaved (round-robin) page->shard assignment (Fig 7b). Each
# device appends/score/attends ONLY the pages it stores (compute moves to
# the data), producing flash partials (m, l, o); the cross-bank softmax is
# an exact (pmax, psum, psum) combine — the paper's FlashAttention-style
# cross-bank communication, at (2+D) floats per head instead of whole
# pages.


def _paged_decode_coplace(spec: AttnSpec, q_r, k_r, v_r,
                          paged: cachelib.PagedCache, length, *,
                          do_select: bool, mesh, axis: str = "model",
                          active=None, need_select=None):
    """Retrieval-head decode under interleaved co-placement.

    q_r: (B, HqR, D); k_r/v_r: (B, Hr, D) — replicated over `axis`.
    paged leaves sharded on the page dim over `axis` (page dim divisible).
    Returns (out (B,HqR,D), new PagedCache).

    Ragged (continuous-batching) path: ``length`` is (B,) per-slot,
    ``active`` masks live slots (retired slots neither append nor refresh —
    their local cache rows are bit-stable on every shard), ``need_select``
    is the per-slot share-window phase mask for the select variant. The
    per-slot vectors shard with the batch axis, so each device sees exactly
    the slots whose pages it co-owns.

    ``spec.impl`` selects the per-shard partial-attention body
    (kernels/ops.py): "ref" lowers the pure-jnp oracle and merges with a
    (pmax, psum, psum) collective; "pallas" runs the Pallas
    paged_attention_partial kernel per shard and merges with the fused
    combine_partials epilogue after an all_gather of the (2+D)-floats-
    per-head partials (the paper's cross-bank communication volume).
    Both are exact up to float reassociation; per-slot validity masking
    is identical (see docs/kernels.md).
    """
    import numpy as np
    from jax.sharding import PartitionSpec as P

    h2 = spec.h2
    p_sz = h2.page_size
    cap_pages = paged.k_pages.shape[2]
    nsh = int(mesh.shape[axis])
    assert cap_pages % nsh == 0, (
        f"page capacity {cap_pages} must divide by {axis}={nsh}; "
        "round ServeConfig.capacity up to page_size*mesh_model pages")
    c_loc = cap_pages // nsh
    ba = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    b = q_r.shape[0]
    dp = int(np.prod([mesh.shape[a] for a in ba]))
    bspec = ba if b % dp == 0 else None
    ragged = active is not None or jnp.asarray(length).ndim == 1
    # static (trace-time) impl switch: selects the shard_map body's partial
    # kernel AND its combine strategy, never a per-step branch
    use_pallas = kops.resolve_impl(spec.impl) == "pallas"

    rep = P(bspec, None, None)
    cache5 = P(bspec, None, axis, None, None)
    cache4 = P(bspec, None, axis, None)
    cache3 = P(bspec, None, axis)
    vec = P(bspec)

    extra_args = ()
    extra_specs = ()
    if ragged:
        length = jnp.broadcast_to(
            jnp.asarray(length, jnp.int32), (b,))
        act = (jnp.ones((b,), bool) if active is None
               else jnp.asarray(active).reshape(b))
        extra_args = (act,)
        extra_specs = (vec,)
        if do_select:
            need = (jnp.ones((b,), bool) if need_select is None
                    else jnp.asarray(need_select).reshape(b))
            extra_args += (need,)
            extra_specs += (vec,)

    def body(q, kn, vn, kp, vp, tmin, tmax, imp, pstart, sel_prev, length,
             *extra):
        i = jax.lax.axis_index(axis)
        ctx = length + 1                       # scalar or (B_loc,)
        act = extra[0] if ragged else None
        need = extra[1] if (ragged and do_select) else None
        # ---- append (only the owner shard writes; retired slots masked) --
        kp, vp, tmin, tmax, pstart = cachelib.sharded_paged_append(
            kp, vp, tmin, tmax, pstart, kn, vn, length, page=p_sz,
            shard_idx=i, n_shards=nsh, active=act)

        # ---- selection (local score + distributed top-k) ----
        if do_select:
            scores_loc = paging.score_pages(
                q, tmin, tmax, pstart, ctx, sink=h2.sink, local=h2.local,
                page=p_sz, impl=spec.impl)          # (B, Hr, C_loc)
            imp_new = paging.accumulate_importance(imp, scores_loc)
            k_eff = min(h2.top_k_pages, c_loc)
            v_loc, i_loc = jax.lax.top_k(scores_loc, k_eff)
            phys_loc = i_loc + i * c_loc
            v_all = jax.lax.all_gather(v_loc, axis)   # (nsh, B, Hr, k)
            i_all = jax.lax.all_gather(phys_loc, axis)
            bsz, hr = v_loc.shape[0], v_loc.shape[1]
            v_cat = v_all.transpose(1, 2, 0, 3).reshape(bsz, hr, nsh * k_eff)
            i_cat = i_all.transpose(1, 2, 0, 3).reshape(bsz, hr, nsh * k_eff)
            sel_v, sel_pos = jax.lax.top_k(v_cat, min(h2.top_k_pages,
                                                      nsh * k_eff))
            sel = jnp.take_along_axis(i_cat, sel_pos, axis=2)
            sel = jnp.where(sel_v > paging.SELECTABLE_MIN, sel, -1)
            if sel.shape[2] < h2.top_k_pages:
                pad = jnp.full(sel.shape[:2] + (h2.top_k_pages
                                                - sel.shape[2],), -1,
                               jnp.int32)
                sel = jnp.concatenate([sel.astype(jnp.int32), pad], axis=2)
            sel = sel.astype(jnp.int32)
            if need is not None:
                # per-slot share window: slots whose window has not expired
                # keep their cached selection / importance bit-unchanged
                ns = need[:, None, None]
                sel = jnp.where(ns, sel, sel_prev)
                imp = jnp.where(ns, imp_new, imp)
            else:
                imp = imp_new
        else:
            sel = sel_prev

        # ---- attended slots (physical) + local partial attention ----
        slots_phys = paging.coplace_attended_slots(
            sel, ctx, sink=h2.sink, local=h2.local, page=p_sz,
            capacity=cap_pages, n_shards=nsh)
        loc = slots_phys - i * c_loc
        mine_s = (slots_phys >= 0) & (loc >= 0) & (loc < c_loc)
        loc_masked = jnp.where(mine_s, loc, -1)
        gk, gv = paging.gather_pages(kp, vp, loc_masked)
        valid = paging.token_validity(
            loc_masked, pstart, ctx, sink=h2.sink, local=h2.local,
            page=p_sz, top_k=h2.top_k_pages)
        m, l, o = kops.paged_attention_partial(q, gk, gv, valid,
                                               impl=spec.impl)

        # ---- cross-shard flash combine (the paper's cross-bank softmax) --
        if use_pallas:
            # fused epilogue: ship each shard's (2+D) floats per head and
            # run the max/rescale/sum/divide merge as one kernel
            m_all = jax.lax.all_gather(m, axis)      # (nsh, B, HqR)
            l_all = jax.lax.all_gather(l, axis)
            o_all = jax.lax.all_gather(o, axis)      # (nsh, B, HqR, D)
            out = kops.combine_partials(m_all, l_all, o_all,
                                        impl=spec.impl).astype(q.dtype)
        else:
            m_max = jax.lax.pmax(m, axis)
            corr = jnp.where(jnp.isfinite(m),
                             jnp.exp(m - jnp.where(jnp.isfinite(m_max),
                                                   m_max, 0.0)), 0.0)
            l_g = jax.lax.psum(l * corr, axis)
            o_g = jax.lax.psum(o * corr[..., None].astype(o.dtype), axis)
            out = (o_g / jnp.maximum(l_g, 1e-30)[..., None]).astype(q.dtype)
        return out, kp, vp, tmin, tmax, imp, pstart, sel

    len_spec = vec if ragged else P()
    shard = jax.shard_map(
        body, mesh=mesh,
        in_specs=(rep, rep, rep, cache5, cache5, cache4, cache4, cache3,
                  cache3, P(bspec, None, None), len_spec) + extra_specs,
        out_specs=(rep, cache5, cache5, cache4, cache4, cache3, cache3,
                   P(bspec, None, None)),
        check_vma=False,
    )
    out, kpn, vpn, tminn, tmaxn, impn, pstartn, seln = shard(
        q_r, k_r, v_r, paged.k_pages, paged.v_pages, paged.tau_min,
        paged.tau_max, paged.importance, paged.page_start, paged.sel_idx,
        length, *extra_args)
    new_paged = cachelib.PagedCache(
        k_pages=kpn, v_pages=vpn, tau_min=tminn, tau_max=tmaxn,
        importance=impn, page_start=pstartn, sel_idx=seln)
    return out, new_paged


def decode_attention_coplace(spec: AttnSpec, q, k_new, v_new, paged, stream,
                             length, *, do_select: bool, perm=None,
                             axis: str = "model", active=None,
                             need_select=None):
    """decode_attention with the retrieval heads under shard_map
    co-placement. Streaming heads use the normal (tiny) path.

    Accepts the same ragged-batch arguments as ``decode_attention``
    (per-slot (B,) ``length``, ``active``, ``need_select``) — this is the
    path the continuous-batching engine takes under
    ``layout="coplace_shmap"``.
    """
    from repro.runtime import hints

    mesh = hints.current_mesh()
    if mesh is None or axis not in mesh.axis_names:
        raise ValueError(
            f"decode_attention_coplace needs an ambient mesh with a "
            f"{axis!r} axis (jax.set_mesh); got {mesh}")
    h2 = spec.h2
    g = spec.group
    nr = spec.n_retrieval
    if perm is None:
        perm = identity_perm(spec)
    qp = _permute_q(q, perm, g)
    kp = _permute_kv(k_new, perm)
    vp = _permute_kv(v_new, perm)
    ctx = length + 1
    outs = []
    if nr > 0:
        out_r, paged = _paged_decode_coplace(
            spec, qp[:, : nr * g], kp[:, :nr], vp[:, :nr], paged, length,
            do_select=do_select, mesh=mesh, axis=axis, active=active,
            need_select=need_select)
        outs.append(out_r)
    if spec.n_streaming > 0:
        stream = cachelib.stream_cache_append(
            stream, kp[:, nr:], vp[:, nr:], length, sink=h2.sink,
            active=active)
        ctx_b = jnp.broadcast_to(jnp.asarray(ctx, jnp.int32),
                                 (q.shape[0],))[:, None, None]
        valid_s = (stream.pos >= 0) & (
            (stream.pos < h2.sink) | (stream.pos >= ctx_b - h2.local))
        outs.append(kops.paged_attention(
            qp[:, nr * g:], stream.k, stream.v, valid_s, impl=spec.impl))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    out = _permute_q(out, _inverse_perm(perm), g)
    return out, paged, stream


# ---------------------------------------------------------------------------
# Full-attention baseline (paper's "full attention" HB baseline)
# ---------------------------------------------------------------------------


def full_decode_attention(spec: AttnSpec, q, k_new, v_new,
                          cache: cachelib.FullCache, length,
                          active: Array | None = None):
    cache = cachelib.full_cache_append(cache, k_new, v_new, length,
                                       active=active)
    b = q.shape[0]
    lb = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))[:, None, None]
    pos = jnp.arange(cache.k.shape[2])[None, None, :]
    valid = pos < (lb + 1)
    if spec.window > 0:
        valid &= pos > (lb - spec.window)
    valid = jnp.broadcast_to(valid, cache.k.shape[:3])
    out = kops.paged_attention(q, cache.k, cache.v, valid, impl=spec.impl)
    return out, cache
