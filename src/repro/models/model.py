"""Top-level model API: init / forward (train) / prefill / decode_step.

All functions are pure and jit-friendly; ``cfg`` is static. The layer
stack runs as ``lax.scan`` over periods (see transformer.py).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.cache import PAGE_LEAVES, paged_cache_append
from repro.core.layouts import get_layout
from repro.models import transformer as T
from repro.models.layers import rms_norm, rope_cos_sin

Array = jax.Array


def init_params(cfg: ArchConfig, key, dtype=jnp.float32):
    return T.init_params(cfg, key, dtype)


def default_plan(cfg: ArchConfig):
    return T.default_plan(cfg)


def embed_input(cfg: ArchConfig, params, batch) -> Array:
    """batch: (B,S) int32 tokens, or (B,S,frontend_dim) embeddings for
    frontend-stub archs (vlm/audio)."""
    if cfg.embed_frontend_stub:
        return batch  # precomputed frame/patch embeddings
    with jax.named_scope("embed"):
        return jnp.take(params["embed"], batch, axis=0)


def unembed(cfg: ArchConfig, params, x: Array) -> Array:
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if cfg.tie_embeddings:
            return jnp.einsum("...d,vd->...v", x, params["embed"])
        return jnp.einsum("...d,dv->...v", x, params["lm_head"])


def _rope(cfg: ArchConfig, positions: Array):
    return rope_cos_sin(positions, cfg.resolved_head_dim, cfg.rope_theta)


# ---------------------------------------------------------------------------
# Training forward
# ---------------------------------------------------------------------------


def forward(cfg: ArchConfig, params, batch, *, plan=None, impl: str = "ref",
            alpha: Array | None = None, remat: bool = False) -> Array:
    """Full-sequence forward -> logits (B, S, V).

    alpha: (num_layers, Hkv) gating parameters for head-identification
    training (None = plain attention).
    """
    plan = plan if plan is not None else T.default_plan(cfg)
    x = embed_input(cfg, params, batch)
    s = x.shape[1]
    rope = _rope(cfg, jnp.arange(s))
    n_per, n_rem = T.layer_layout(cfg)
    p_len = T.period_len(cfg)

    if alpha is not None:
        alpha_blocks = alpha[: n_per * p_len].reshape(n_per, p_len, -1)
    else:
        alpha_blocks = None

    def period_fn(x, xs):
        params_p, plan_p, alpha_p = xs
        for pos in range(p_len):
            a = alpha_p[pos] if alpha_p is not None else None
            x = T.block_train(cfg, pos, params_p[f"pos{pos}"],
                              plan_p[f"pos{pos}"], x, rope, impl=impl,
                              alpha=a)
        return x, ()

    body = jax.checkpoint(period_fn) if remat else period_fn
    if n_per > 0:
        xs = (params["blocks"], plan["blocks"], alpha_blocks)
        x, _ = jax.lax.scan(lambda c, s_: body(c, s_), x, xs)
    for r in range(n_rem):
        a = alpha[n_per * p_len + r] if alpha is not None else None
        x = T.block_train(cfg, r, params["rem"][f"rem{r}"],
                          plan["rem"][f"rem{r}"], x, rope, impl=impl, alpha=a)
    return unembed(cfg, params, x)


def lm_loss(cfg: ArchConfig, params, batch, labels, *, plan=None,
            impl: str = "ref", alpha=None, remat: bool = True) -> Array:
    """Mean next-token cross-entropy. labels: (B, S) int32 (-100 = pad)."""
    logits = forward(cfg, params, batch, plan=plan, impl=impl, alpha=alpha,
                     remat=remat)
    logits = logits.astype(jnp.float32)
    mask = labels >= 0
    lab = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1)


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------


def prefill(cfg: ArchConfig, params, batch, *, capacity: int, plan=None,
            impl: str = "ref", layout=None):
    """Process the prompt; returns (last-token logits, ServeState)."""
    plan = plan if plan is not None else T.default_plan(cfg)
    x = embed_input(cfg, params, batch)
    s = x.shape[1]
    rope = _rope(cfg, jnp.arange(s))
    n_per, n_rem = T.layer_layout(cfg)
    p_len = T.period_len(cfg)

    def period_fn(x, xs):
        params_p, plan_p = xs
        caches = {}
        for pos in range(p_len):
            x, c = T.block_prefill(cfg, pos, params_p[f"pos{pos}"],
                                   plan_p[f"pos{pos}"], x, rope,
                                   capacity=capacity, impl=impl,
                                   layout=layout)
            caches[f"pos{pos}"] = c
        return x, caches

    state: dict[str, Any] = {"length": jnp.int32(s), "blocks": {}, "rem": {}}
    if n_per > 0:
        x, caches = jax.lax.scan(
            period_fn, x, (params["blocks"], plan["blocks"]))
        state["blocks"] = caches
    for r in range(n_rem):
        x, c = T.block_prefill(cfg, r, params["rem"][f"rem{r}"],
                               plan["rem"][f"rem{r}"], x, rope,
                               capacity=capacity, impl=impl, layout=layout)
        state["rem"][f"rem{r}"] = c
    logits = unembed(cfg, params, x[:, -1])
    return logits, state


def prefill_chunk(cfg: ArchConfig, params, state, tokens, *, chunk_len,
                  active, plan=None, impl: str = "ref", layout=None):
    """Feed one prompt chunk per slot into the batched serve state
    (chunked, slot-resident prefill — the admission half of the engine's
    mixed prefill+decode step).

    tokens: (B, C) int32 — left-aligned per-slot chunks, padded past
    ``chunk_len`` ((B,), valid tokens per slot). ``active`` (B,) marks
    the slots taking a chunk this step; the rest of the batch (decoding
    or free slots) appends nothing and keeps its length. Each slot's
    chunk starts at its current ``state["length"]``. Returns
    (last-chunk-token logits (B, V), new state) — the logits row of a
    slot whose prompt just completed is its first-token distribution
    (garbage for every other row). C is static, so one compiled program
    serves every chunk schedule (the zero-recompile invariant).
    """
    assert not cfg.embed_frontend_stub, (
        "chunked prefill feeds token chunks through the embedding; "
        "frontend-stub archs use prefill-then-pack admission")
    plan = plan if plan is not None else T.default_plan(cfg)
    start = jnp.asarray(state["length"], jnp.int32).reshape(-1)   # (B,)
    x = embed_input(cfg, params, tokens)                          # (B,C,d)
    cch = tokens.shape[1]
    with jax.named_scope("qkv"):
        pos_q = start[:, None] + jnp.arange(cch, dtype=jnp.int32)
        rope = _rope(cfg, pos_q)                                  # (B,C,half)
    chunk_len = jnp.asarray(chunk_len, jnp.int32).reshape(-1)
    active = jnp.asarray(active).reshape(-1)
    n_per, n_rem = T.layer_layout(cfg)
    p_len = T.period_len(cfg)

    def period_fn(x, xs):
        params_p, plan_p, cache_p = xs
        new_caches = {}
        for pos in range(p_len):
            x, c = T.block_prefill_chunk(
                cfg, pos, params_p[f"pos{pos}"], plan_p[f"pos{pos}"], x,
                rope, cache_p[f"pos{pos}"], start=start,
                chunk_len=chunk_len, active=active, impl=impl,
                layout=layout)
            new_caches[f"pos{pos}"] = c
        return x, new_caches

    new_len = jnp.where(active, start + chunk_len, start)
    new_state: dict[str, Any] = {
        "length": new_len.astype(jnp.asarray(state["length"]).dtype),
        "blocks": {}, "rem": {}}
    if n_per > 0:
        x, caches = jax.lax.scan(
            period_fn, x,
            (params["blocks"], plan["blocks"], state["blocks"]))
        new_state["blocks"] = caches
    for r in range(n_rem):
        x, c = T.block_prefill_chunk(
            cfg, r, params["rem"][f"rem{r}"], plan["rem"][f"rem{r}"], x,
            rope, state["rem"][f"rem{r}"], start=start,
            chunk_len=chunk_len, active=active, impl=impl, layout=layout)
        new_state["rem"][f"rem{r}"] = c
    # logits at each slot's LAST valid chunk position (first-token
    # emission for slots whose prompt completed this step)
    with jax.named_scope("head"):
        idx = jnp.clip(chunk_len - 1, 0, cch - 1)
        x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    return unembed(cfg, params, x_last), new_state


def verify_forward(cfg: ArchConfig, params, state, tokens, *, active,
                   need_select, plan=None, impl: str = "ref", layout=None):
    """Speculative verify forward: run k drafted tokens per slot as k
    decode steps in ONE chunked pass over the PRE-append caches
    (attend-before-append; see core/hybrid_attention.py).

    tokens: (B, k) int32 — row 0 is each slot's pending feed token, rows
    1..k-1 the draft; positions are state["length"] .. +k-1. Returns
    (logits (B, k, V), state', stash): logits row j is the target
    distribution at position length+j; state' carries ONLY the refreshed
    page selection/importance (gated by ``need_select``/``active``) with
    KV pages, stream rings, and lengths untouched; ``stash`` holds each
    layer's roped chunk (k, v) for ``verify_commit``. Acceptance decides
    how much of the chunk commits — the cache is never rolled back.
    """
    plan = plan if plan is not None else T.default_plan(cfg)
    start = jnp.asarray(state["length"], jnp.int32).reshape(-1)   # (B,)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)             # (B,k,d)
    kch = tokens.shape[1]
    with jax.named_scope("qkv"):
        pos_q = start[:, None] + jnp.arange(kch, dtype=jnp.int32)
        rope = _rope(cfg, pos_q)                                  # (B,k,half)
    active = jnp.asarray(active).reshape(-1)
    need_select = jnp.asarray(need_select).reshape(-1)
    n_per, n_rem = T.layer_layout(cfg)
    p_len = T.period_len(cfg)

    def period_fn(x, xs):
        params_p, plan_p, cache_p = xs
        new_caches, stash_p = {}, {}
        for pos in range(p_len):
            x, c, kv = T.block_verify_chunk(
                cfg, pos, params_p[f"pos{pos}"], plan_p[f"pos{pos}"], x,
                rope, cache_p[f"pos{pos}"], start=start, active=active,
                need_select=need_select, impl=impl, layout=layout)
            new_caches[f"pos{pos}"] = c
            stash_p[f"pos{pos}"] = kv
        return x, (new_caches, stash_p)

    new_state: dict[str, Any] = {"length": state["length"],
                                 "blocks": {}, "rem": {}}
    stash: dict[str, Any] = {"blocks": {}, "rem": {}}
    if n_per > 0:
        x, (caches, stash_b) = jax.lax.scan(
            period_fn, x,
            (params["blocks"], plan["blocks"], state["blocks"]))
        new_state["blocks"] = caches
        stash["blocks"] = stash_b
    for r in range(n_rem):
        x, c, kv = T.block_verify_chunk(
            cfg, r, params["rem"][f"rem{r}"], plan["rem"][f"rem{r}"], x,
            rope, state["rem"][f"rem{r}"], start=start, active=active,
            need_select=need_select, impl=impl, layout=layout)
        new_state["rem"][f"rem{r}"] = c
        stash["rem"][f"rem{r}"] = kv
    return unembed(cfg, params, x), new_state, stash


def verify_commit(cfg: ArchConfig, state, stash, *, accepted, active,
                  plan=None, impl: str = "ref", layout=None):
    """Commit each slot's accepted prefix (``accepted`` (B,), >= 1
    tokens of the verified chunk) into the serve caches from the
    ``verify_forward`` stash, through the same ragged chunk appends a
    sequence of single-token decode appends reduces to. Inactive slots
    commit nothing. Returns the advanced state (length += accepted)."""
    plan = plan if plan is not None else T.default_plan(cfg)
    start = jnp.asarray(state["length"], jnp.int32).reshape(-1)
    accepted = jnp.asarray(accepted, jnp.int32).reshape(-1)
    active = jnp.asarray(active).reshape(-1)
    n_per, n_rem = T.layer_layout(cfg)
    p_len = T.period_len(cfg)

    def period_fn(_, xs):
        plan_p, cache_p, stash_p = xs
        new_caches = {}
        for pos in range(p_len):
            new_caches[f"pos{pos}"] = T.block_verify_append(
                cfg, pos, plan_p[f"pos{pos}"], cache_p[f"pos{pos}"],
                stash_p[f"pos{pos}"], start=start, accepted=accepted,
                active=active, impl=impl, layout=layout)
        return (), new_caches

    new_len = jnp.where(active, start + accepted, start)
    new_state: dict[str, Any] = {
        "length": new_len.astype(jnp.asarray(state["length"]).dtype),
        "blocks": {}, "rem": {}}
    if n_per > 0:
        _, caches = jax.lax.scan(
            period_fn, (),
            (plan["blocks"], state["blocks"], stash["blocks"]))
        new_state["blocks"] = caches
    for r in range(n_rem):
        new_state["rem"][f"rem{r}"] = T.block_verify_append(
            cfg, r, plan["rem"][f"rem{r}"], state["rem"][f"rem{r}"],
            stash["rem"][f"rem{r}"], start=start, accepted=accepted,
            active=active, impl=impl, layout=layout)
    return new_state


def decode_step(cfg: ArchConfig, params, state, token, *, plan=None,
                do_select: bool = True, impl: str = "ref", layout=None,
                active=None, need_select=None):
    """One decode step.

    token: (B,) int32 (or (B, frontend_dim) embeddings for stub archs).
    Returns (logits (B, V), new state).

    ``state["length"]`` is a scalar on the uniform (lockstep) path and a
    (B,) per-slot vector on the continuous-batching ragged path, where
    ``active`` ((B,) bool) marks live slots — inactive slots neither
    append to their caches nor advance their length — and ``need_select``
    ((B,) bool, select variant only) is the per-slot share-window phase
    mask. Logits of inactive slots are garbage and must be ignored.
    """
    plan = plan if plan is not None else T.default_plan(cfg)
    length = state["length"]
    x = embed_input(cfg, params, token)
    with jax.named_scope("qkv"):
        # rope at each slot's own position: (1, half) lockstep / (B, half)
        # ragged
        rope1 = _rope(cfg, jnp.reshape(length, (-1,)))
        rope1 = (rope1[0][:, None], rope1[1][:, None])  # (·, 1, half)
    n_per, n_rem = T.layer_layout(cfg)
    p_len = T.period_len(cfg)
    # paged caches of a layout with a decode_rows hook stay out of the
    # scan's inputs and outputs: the scan reads their page leaves in place
    # and each layer's new rows are written once, after it
    pages = {}
    if get_layout(layout).decode_rows is not None:
        pages = {key: {f: getattr(c["paged"], f) for f in PAGE_LEAVES}
                 for key, c in state["blocks"].items()
                 if isinstance(c, dict) and "paged" in c}

    def without_pages(c):
        return {**c, "paged": dataclasses.replace(
            c["paged"], **dict.fromkeys(PAGE_LEAVES))}

    def period_fn(x, xs):
        params_p, plan_p, cache_p, layer = xs
        new_caches = {}
        for pos in range(p_len):
            key = f"pos{pos}"
            cache = cache_p[key]
            if key in pages:
                cache = {**cache, "paged": dataclasses.replace(
                    cache["paged"], **pages[key])}
            x, c = T.block_decode(cfg, pos, params_p[key], plan_p[key], x,
                                  rope1, cache, length=length,
                                  do_select=do_select, impl=impl,
                                  layout=layout, active=active,
                                  need_select=need_select,
                                  layer=layer if key in pages else None)
            new_caches[key] = without_pages(c) if key in pages else c
        return x, new_caches

    new_len = length + 1
    if active is not None:
        new_len = jnp.where(active, new_len, length)
    new_state: dict[str, Any] = {"length": new_len, "blocks": {},
                                 "rem": {}}
    if n_per > 0:
        blocks = {key: without_pages(c) if key in pages else c
                  for key, c in state["blocks"].items()}
        x, caches = jax.lax.scan(
            period_fn, x, (params["blocks"], plan["blocks"], blocks,
                           jnp.arange(n_per, dtype=jnp.int32)))
        for key, leaves in pages.items():
            c = dict(caches[key])
            rows = c.pop("rows")
            paged = dataclasses.replace(c["paged"], **leaves)
            if rows is not None:
                with jax.named_scope("attn.append"):
                    paged = paged_cache_append(paged, *rows, length,
                                               active=active)
            caches[key] = {**c, "paged": paged}
        new_state["blocks"] = caches
    for r in range(n_rem):
        x, c = T.block_decode(cfg, r, params["rem"][f"rem{r}"],
                              plan["rem"][f"rem{r}"], x, rope1,
                              state["rem"][f"rem{r}"], length=length,
                              do_select=do_select, impl=impl, layout=layout,
                              active=active, need_select=need_select)
        new_state["rem"][f"rem{r}"] = c
    logits = unembed(cfg, params, x)
    return logits, new_state
