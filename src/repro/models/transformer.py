"""Decoder stack with period-scan.

Architectures repeat a short *period* of block types (dense: 1; gemma3:
5 local + 1 global; zamba2: 5 mamba2 + 1 attention; xlstm: 2 mlstm +
1 slstm). Parameters are stacked per period position with a leading
``num_periods`` axis and the stack is driven by ``lax.scan`` — compact HLO
at any depth (kimi-k2's 61 layers lower as one scanned period), which is
what makes 40-cell × 512-device dry-runs compile in reasonable time.
Remainder layers (depth % period) run unrolled after the scan.

Block modes:
  train   — full/windowed attention (optionally α-gated for head
            identification), differentiable.
  prefill — hybrid sparse attention; emits the layer's serve caches.
  decode  — one token against the serve caches.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import (
    ArchConfig,
    MIXER_ATTENTION,
    MIXER_MAMBA2,
    MIXER_MLSTM,
    MIXER_SLSTM,
)
from repro.core import cache as cachelib
from repro.core import gating as gatinglib
from repro.core import hybrid_attention as hattn
from repro.core import layouts as layoutlib
from repro.models import moe as moelib
from repro.models import ssm as ssmlib
from repro.models import xlstm as xlstmlib
from repro.models.layers import (
    apply_rope,
    dense,
    init_dense,
    rms_norm,
    rope_cos_sin,
    swiglu,
)

Array = jax.Array


def period_len(cfg: ArchConfig) -> int:
    if cfg.mixer_pattern:
        return len(cfg.mixer_pattern)
    if cfg.attn_pattern == "local_global":
        return cfg.local_global_ratio + 1
    return 1


def layer_layout(cfg: ArchConfig) -> tuple[int, int]:
    """(num_periods, num_remainder_layers)."""
    p = period_len(cfg)
    return cfg.num_layers // p, cfg.num_layers % p


def attn_spec(cfg: ArchConfig, pos: int, impl: str) -> hattn.AttnSpec:
    """AttnSpec for period position ``pos`` (layer i ≡ pos mod period)."""
    window = 0
    if cfg.attn_pattern == "local_global" and not cfg.layer_is_global_attn(pos):
        window = cfg.local_window
    return hattn.AttnSpec(
        n_q=cfg.num_heads,
        n_kv=cfg.num_kv_heads,
        head_dim=cfg.resolved_head_dim,
        h2=cfg.h2eal,
        window=window,
        impl=impl,
    )


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_attn_block(key, cfg: ArchConfig, pos: int, dtype):
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 8)
    p: dict[str, Any] = {
        "ln1": jnp.zeros((d,), dtype),
        "wq": init_dense(ks[0], d, cfg.num_heads * hd, dtype=dtype),
        "wk": init_dense(ks[1], d, cfg.num_kv_heads * hd, dtype=dtype),
        "wv": init_dense(ks[2], d, cfg.num_kv_heads * hd, dtype=dtype),
        "wo": init_dense(ks[3], cfg.num_heads * hd, d, dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
    if cfg.layer_has_ffn(pos):
        p["ln2"] = jnp.zeros((d,), dtype)
        if cfg.moe.enabled:
            p["moe"] = moelib.init_moe(ks[4], cfg, dtype=dtype)
        else:
            p["ffn"] = {
                "w_gate": init_dense(ks[5], d, cfg.d_ff, dtype=dtype),
                "w_up": init_dense(ks[6], d, cfg.d_ff, dtype=dtype),
                "w_down": init_dense(ks[7], cfg.d_ff, d, dtype=dtype),
            }
    return p


def _init_block(key, cfg: ArchConfig, pos: int, dtype):
    mixer = cfg.mixer_for_layer(pos)
    if mixer == MIXER_ATTENTION:
        return _init_attn_block(key, cfg, pos, dtype)
    ks = jax.random.split(key, 2)
    if mixer == MIXER_MAMBA2:
        p = {"ln1": jnp.zeros((cfg.d_model,), dtype),
             "mamba": ssmlib.init_mamba2(ks[0], cfg, dtype=dtype)}
    elif mixer == MIXER_MLSTM:
        p = {"ln1": jnp.zeros((cfg.d_model,), dtype),
             "xl": xlstmlib.init_mlstm(ks[0], cfg, dtype=dtype)}
    elif mixer == MIXER_SLSTM:
        p = {"ln1": jnp.zeros((cfg.d_model,), dtype),
             "xl": xlstmlib.init_slstm(ks[0], cfg, dtype=dtype)}
    else:
        raise ValueError(mixer)
    if cfg.layer_has_ffn(pos):
        kf = jax.random.split(ks[1], 3)
        p["ln2"] = jnp.zeros((cfg.d_model,), dtype)
        if cfg.moe.enabled:
            p["moe"] = moelib.init_moe(kf[0], cfg, dtype=dtype)
        else:
            p["ffn"] = {
                "w_gate": init_dense(kf[0], cfg.d_model, cfg.d_ff, dtype=dtype),
                "w_up": init_dense(kf[1], cfg.d_model, cfg.d_ff, dtype=dtype),
                "w_down": init_dense(kf[2], cfg.d_ff, cfg.d_model, dtype=dtype),
            }
    return p


def init_params(cfg: ArchConfig, key, dtype=jnp.float32):
    n_per, n_rem = layer_layout(cfg)
    p_len = period_len(cfg)
    keys = jax.random.split(key, 3)

    params: dict[str, Any] = {}
    if not cfg.embed_frontend_stub:
        from repro.models.layers import init_embed
        params["embed"] = init_embed(keys[0], cfg.vocab_size, cfg.d_model,
                                     dtype=dtype)
    blocks = {}
    bkeys = jax.random.split(keys[1], p_len)
    for pos in range(p_len):
        if n_per > 0:
            stacked = [
                _init_block(jax.random.fold_in(bkeys[pos], per), cfg, pos, dtype)
                for per in range(n_per)
            ]
            blocks[f"pos{pos}"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *stacked)
    params["blocks"] = blocks
    rem = {}
    for r in range(n_rem):
        pos = r  # remainder layers continue the pattern
        rem[f"rem{r}"] = _init_block(
            jax.random.fold_in(keys[1], 10_000 + r), cfg, pos, dtype)
    params["rem"] = rem
    params["final_norm"] = jnp.zeros((cfg.d_model,), dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = init_dense(keys[2], cfg.d_model, cfg.vocab_size,
                                       dtype=dtype)
    return params


def default_plan(cfg: ArchConfig):
    """Per-layer kv-head permutation (retrieval heads first).

    The real permutation comes from gating (core/gating.py) + the scheduler
    (sched/tiling.py); the default is the identity on every layer.
    """
    n_per, n_rem = layer_layout(cfg)
    p_len = period_len(cfg)
    perm = jnp.arange(cfg.num_kv_heads, dtype=jnp.int32)
    plan = {"blocks": {}, "rem": {}}
    for pos in range(p_len):
        if n_per > 0:
            plan["blocks"][f"pos{pos}"] = {
                "perm": jnp.broadcast_to(perm, (n_per, cfg.num_kv_heads))}
    for r in range(n_rem):
        plan["rem"][f"rem{r}"] = {"perm": perm}
    return plan


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------


def _ffn_apply(cfg: ArchConfig, p, x):
    with jax.named_scope("ffn"):
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if "moe" in p:
            return x + moelib.moe_ffn(cfg, p["moe"], h)
        f = p["ffn"]
        return x + swiglu(h, f["w_gate"], f["w_up"], f["w_down"])


def _qkv(cfg: ArchConfig, p, h):
    hd = cfg.resolved_head_dim
    q = dense(h, p["wq"], p.get("bq"))
    k = dense(h, p["wk"], p.get("bk"))
    v = dense(h, p["wv"], p.get("bv"))
    if h.ndim == 3:  # (B, S, ·)
        b, s, _ = h.shape
        return (q.reshape(b, s, cfg.num_heads, hd),
                k.reshape(b, s, cfg.num_kv_heads, hd),
                v.reshape(b, s, cfg.num_kv_heads, hd))
    b, _ = h.shape
    return (q.reshape(b, cfg.num_heads, hd),
            k.reshape(b, cfg.num_kv_heads, hd),
            v.reshape(b, cfg.num_kv_heads, hd))


def block_train(cfg: ArchConfig, pos: int, p, plan, x, rope, *,
                impl="ref", alpha=None):
    """Training/eval forward for one block. x: (B, S, d)."""
    from repro.runtime import hints
    p = hints.unshard_block_params(p)
    x = hints.act(x)
    mixer = cfg.mixer_for_layer(pos)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if mixer == MIXER_ATTENTION:
        spec = attn_spec(cfg, pos, impl)
        q, k, v = _qkv(cfg, p, h)
        cos, sin = rope
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        if alpha is not None:
            o = gatinglib.gated_attention(
                q, k, v, alpha, sink=cfg.h2eal.sink, local=cfg.h2eal.local,
                impl=impl)
        else:
            from repro.kernels import ops as kops
            o = kops.flash_attention(q, k, v, causal=True,
                                     window=spec.window, impl=impl)
        b, s, _, _ = o.shape
        x = x + dense(o.reshape(b, s, -1), p["wo"])
    elif mixer == MIXER_MAMBA2:
        x = x + ssmlib.mamba2_forward(cfg, p["mamba"], h)
    elif mixer == MIXER_MLSTM:
        x = x + xlstmlib.mlstm_forward(cfg, p["xl"], h)
    elif mixer == MIXER_SLSTM:
        x = x + xlstmlib.slstm_forward(cfg, p["xl"], h)
    if cfg.layer_has_ffn(pos):
        x = _ffn_apply(cfg, p, x)
    return x


def block_prefill(cfg: ArchConfig, pos: int, p, plan, x, rope, *,
                  capacity: int, impl="ref", layout=None):
    """Prefill: like train but hybrid attention + emits serve cache."""
    from repro.runtime import hints
    p = hints.unshard_block_params(p)
    x = hints.act(x)
    mixer = cfg.mixer_for_layer(pos)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    cache: Any = ()
    if mixer == MIXER_ATTENTION:
        spec = attn_spec(cfg, pos, impl)
        q, k, v = _qkv(cfg, p, h)
        cos, sin = rope
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        s_len = q.shape[1]
        perm = plan["perm"]
        o = hattn.prefill_attention(spec, q, k, v, perm)
        if spec.h2.enabled and spec.window == 0:
            # the layout entry decides the physical page order (e.g.
            # coplace_shmap's round-robin striping sized to the ambient
            # mesh); see core/layouts.py
            cache = layoutlib.get_layout(layout).prefill(
                spec, k, v, s_len, capacity, perm)
        else:  # full-attention baseline / plain window layer
            ctx_cap = capacity
            full = cachelib.make_full_cache(
                q.shape[0], cfg.num_kv_heads, ctx_cap, spec.head_dim,
                dtype=k.dtype)
            kk = jnp.pad(k, ((0, 0), (0, ctx_cap - s_len), (0, 0), (0, 0)))
            vv = jnp.pad(v, ((0, 0), (0, ctx_cap - s_len), (0, 0), (0, 0)))
            full = cachelib.FullCache(k=kk.transpose(0, 2, 1, 3),
                                      v=vv.transpose(0, 2, 1, 3))
            cache = {"full": full}
        b, s, _, _ = o.shape
        x = x + dense(o.reshape(b, s, -1), p["wo"])
    elif mixer == MIXER_MAMBA2:
        # run chunked forward, then recompute final state via a short scan:
        # cheaper: run the recurrence on the last chunk only is not exact;
        # we run the full recurrent scan for the state (prefill happens once)
        y, st = _mamba2_prefill_with_state(cfg, p["mamba"], h)
        x = x + y
        cache = {"ssm": st}
    elif mixer in (MIXER_MLSTM, MIXER_SLSTM):
        y, st = _xlstm_prefill_with_state(cfg, mixer, p["xl"], h)
        x = x + y
        cache = {"xl": st}
    if cfg.layer_has_ffn(pos):
        x = _ffn_apply(cfg, p, x)
    return x, cache


def block_prefill_chunk(cfg: ArchConfig, pos: int, p, plan, x, rope, cache,
                        *, start, chunk_len, active, impl="ref",
                        layout=None):
    """Chunked prefill: one prompt chunk through one block. x: (B, C, d);
    ``rope`` is (cos, sin) at each slot's chunk positions (B, C, half);
    ``cache`` is the block's serve cache being grown in place. ``start``
    (B,) is each slot's context before the chunk, ``chunk_len`` (B,) its
    valid tokens, ``active`` (B,) the slots prefilling this step. Rows
    past chunk_len / inactive slots append nothing and produce garbage
    activations (attention masks keep them out of every other position,
    recurrent mixers freeze their scan state past chunk_len; the FFN is
    pointwise). Recurrent mixers resume their per-slot saved state
    (conv history + SSM/cell state) and write the advanced state back
    into the block cache — the chunk-resumable scan that lets every
    mixer share chunked admission."""
    from repro.runtime import hints
    p = hints.unshard_block_params(p)
    x = hints.act(x)
    mixer = cfg.mixer_for_layer(pos)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    b, cch = x.shape[0], x.shape[1]
    if mixer == MIXER_ATTENTION:
        spec = attn_spec(cfg, pos, impl)
        with jax.named_scope("qkv"):
            q, k, v = _qkv(cfg, p, h)
            cos, sin = rope
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        if spec.h2.enabled and spec.window == 0:
            inputs = layoutlib.PrefillInputs(
                q=q, k_new=k, v_new=v, start=start, chunk_len=chunk_len,
                active=active)
            o, cache = layoutlib.dispatch_prefill_chunk(
                layout, spec, cache, inputs, perm=plan["perm"])
        else:  # full-attention baseline / plain window layer
            from repro.core import paging
            with jax.named_scope("attn.append"):
                full = cachelib.full_cache_append_chunk(
                    cache["full"], k, v, start, chunk_len, active=active)
            with jax.named_scope("attn.prefill"):
                pos_q = paging.chunk_positions(start, cch)
                key_pos = jnp.arange(full.k.shape[2], dtype=jnp.int32)
                kp = key_pos[None, None, None, :]
                pq = pos_q[:, None, :, None]
                valid = jnp.broadcast_to(
                    kp <= pq, (b, full.k.shape[1], cch, full.k.shape[2]))
                if spec.window > 0:
                    valid = valid & (kp > pq - spec.window)
                from repro.kernels import ops as kops
                o = kops.chunk_attention(q, full.k, full.v, valid,
                                         impl=spec.impl)
            cache = {"full": full}
        with jax.named_scope("attn.out"):
            x = x + dense(o.reshape(b, cch, -1), p["wo"])
    elif mixer == MIXER_MAMBA2:
        y, st = ssmlib.mamba2_prefill_chunk(
            cfg, p["mamba"], cache["ssm"], h, chunk_len=chunk_len,
            active=active)
        x = x + y
        cache = {"ssm": st}
    elif mixer == MIXER_MLSTM:
        y, st = xlstmlib.mlstm_prefill_chunk(
            cfg, p["xl"], cache["xl"], h, chunk_len=chunk_len,
            active=active)
        x = x + y
        cache = {"xl": st}
    elif mixer == MIXER_SLSTM:
        y, st = xlstmlib.slstm_prefill_chunk(
            cfg, p["xl"], cache["xl"], h, chunk_len=chunk_len,
            active=active)
        x = x + y
        cache = {"xl": st}
    if cfg.layer_has_ffn(pos):
        x = _ffn_apply(cfg, p, x)
    return x, cache


def block_verify_chunk(cfg: ArchConfig, pos: int, p, plan, x, rope, cache,
                       *, start, active, need_select, impl="ref",
                       layout=None):
    """Speculative verify: k drafted tokens through one block as k decode
    steps in one chunked attention, WITHOUT touching the block's KV
    caches (selection/importance refresh only — see
    core/hybrid_attention.chunk_verify_attention). x: (B, k, d); ``rope``
    is (cos, sin) at positions start .. start+k-1. Returns
    (x, cache, (k_roped, v)) — the roped chunk KV is stashed so
    ``block_verify_append`` can commit the accepted prefix after the
    acceptance length is known, without recomputing projections.

    Speculation is gated to all-attention hybrid stacks at Engine
    construction, so unlike the other block modes there is no mixer
    branch here."""
    from repro.runtime import hints
    p = hints.unshard_block_params(p)
    x = hints.act(x)
    spec = attn_spec(cfg, pos, impl)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    b, kch = x.shape[0], x.shape[1]
    with jax.named_scope("qkv"):
        q, k, v = _qkv(cfg, p, h)
        cos, sin = rope
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    inputs = layoutlib.VerifyInputs(
        q=q, k_new=k, v_new=v, start=start, active=active,
        need_select=need_select)
    o, cache = layoutlib.dispatch_verify_chunk(
        layout, spec, cache, inputs, perm=plan["perm"])
    with jax.named_scope("attn.out"):
        x = x + dense(o.reshape(b, kch, -1), p["wo"])
    if cfg.layer_has_ffn(pos):
        x = _ffn_apply(cfg, p, x)
    return x, cache, (k, v)


def block_verify_append(cfg: ArchConfig, pos: int, plan, cache, kv, *,
                        start, accepted, active, impl="ref", layout=None):
    """Commit the accepted prefix of a verified chunk into one block's
    caches from the (k_roped, v) stash of ``block_verify_chunk``.
    Returns the new block cache."""
    spec = attn_spec(cfg, pos, impl)
    k, v = kv
    inputs = layoutlib.VerifyInputs(
        q=k, k_new=k, v_new=v, start=start, active=active)
    return layoutlib.dispatch_verify_append(
        layout, spec, cache, inputs, accepted, perm=plan["perm"])


def block_decode(cfg: ArchConfig, pos: int, p, plan, x, rope1, cache, *,
                 length, do_select: bool, impl="ref", layout=None,
                 active=None, need_select=None, layer=None):
    """Decode one token. x: (B, d). ``length`` is scalar (lockstep) or
    (B,) per-slot (continuous batching); ``active``/``need_select`` are the
    ragged path's per-slot masks (see core/hybrid_attention.py).

    ``layer`` (the layer scan's index; paged caches of layouts with a
    ``decode_rows`` hook only): the cache's paged leaves are the
    layer-stacked buffers, read in place, and the returned cache carries
    the retrieval heads' rows under ``"rows"`` for models/model.py to
    write after the scan."""
    from repro.runtime import hints
    p = hints.unshard_block_params(p)
    mixer = cfg.mixer_for_layer(pos)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if mixer == MIXER_ATTENTION:
        spec = attn_spec(cfg, pos, impl)
        with jax.named_scope("qkv"):
            q, k, v = _qkv(cfg, p, h)
            cos1, sin1 = rope1  # (B?, 1, half) at position `length`
            q = apply_rope(q[:, None], cos1, sin1)[:, 0]
            k = apply_rope(k[:, None], cos1, sin1)[:, 0]
            q = hints.decode_qkv(q)
            k = hints.decode_qkv(k)
            v = hints.decode_qkv(v)
        if "full" in cache:
            o, full = hattn.full_decode_attention(
                spec, q, k, v, cache["full"], length, active=active)
            cache = {"full": full}
        else:
            inputs = layoutlib.DecodeInputs(
                q=q, k_new=k, v_new=v, lengths=length, active=active,
                need_select=need_select)
            if layer is None:
                o, cache = layoutlib.dispatch_decode(
                    layout, spec, cache, inputs, do_select=do_select,
                    perm=plan["perm"])
            else:
                o, cache, rows = layoutlib.get_layout(layout).decode_rows(
                    spec, cache, inputs, layer=layer, do_select=do_select,
                    perm=plan["perm"])
                cache = {**cache, "rows": rows}
        b = o.shape[0]
        with jax.named_scope("attn.out"):
            x = x + dense(o.reshape(b, -1), p["wo"])
    elif mixer == MIXER_MAMBA2:
        y, st = ssmlib.mamba2_step(cfg, p["mamba"], cache["ssm"], h)
        x = x + y
        cache = {"ssm": _keep_active(st, cache["ssm"], active)}
    elif mixer == MIXER_MLSTM:
        y, st = xlstmlib.mlstm_step(cfg, p["xl"], cache["xl"], h)
        x = x + y
        cache = {"xl": _keep_active(st, cache["xl"], active)}
    elif mixer == MIXER_SLSTM:
        y, st = xlstmlib.slstm_step(cfg, p["xl"], cache["xl"], h)
        x = x + y
        cache = {"xl": _keep_active(st, cache["xl"], active)}
    if cfg.layer_has_ffn(pos):
        x = _ffn_apply(cfg, p, x)
    return x, cache


def _keep_active(new, old, active):
    """Freeze recurrent state for slots not decoding this ragged step —
    a slot mid-chunked-prefill keeps its saved chunk state intact across
    interleaved decode steps (the attention caches get the same
    protection from their append ops' ``active`` masks). ``active`` is
    None on the lockstep path: no-op."""
    if active is None:
        return new
    act = jnp.asarray(active).reshape(-1)
    keep = lambda n, o: jnp.where(
        act.reshape((act.shape[0],) + (1,) * (n.ndim - 1)), n, o)
    return jax.tree.map(keep, new, old)


def _mamba2_prefill_with_state(cfg, p, h):
    """Chunked forward + exact final SSM/conv state."""
    y = ssmlib.mamba2_forward(cfg, p, h)
    st = ssmlib.mamba2_final_state(cfg, p, h)
    return y, st


def _xlstm_prefill_with_state(cfg, mixer, p, h):
    """Run the scan and keep the final recurrent state."""
    if mixer == MIXER_MLSTM:
        b, L, d = h.shape
        nh = cfg.num_heads
        hd = d // nh
        qkv = dense(h, p["w_qkv"]).astype(jnp.float32)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        it, ft = xlstmlib._mlstm_gates(p, h)
        o = jax.nn.sigmoid(dense(h, p["w_o"]).astype(jnp.float32))

        def step(state, inp):
            qt, kt, vt, i_t, f_t = inp
            state, h_t = xlstmlib._mlstm_update(
                state, qt.reshape(b, nh, hd), kt.reshape(b, nh, hd),
                vt.reshape(b, nh, hd), i_t, f_t)
            return state, h_t

        s0 = xlstmlib.init_mlstm_state(cfg, b)
        s_fin, hs = jax.lax.scan(
            step, s0, (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                       v.transpose(1, 0, 2), it.transpose(1, 0, 2),
                       ft.transpose(1, 0, 2)))
        hs = hs.transpose(1, 0, 2, 3).reshape(b, L, d)
        y = (o * hs).astype(h.dtype)
        y = rms_norm(y, p["norm_w"], cfg.norm_eps)
        return dense(y, p["out_proj"]), s_fin
    # slstm
    b, L, d = h.shape
    wx = dense(h, p["w"])

    def step(state, wxt):
        return xlstmlib._slstm_step_inner(cfg, p, state, wxt)

    s0 = xlstmlib.init_slstm_state(cfg, b)
    s_fin, hs = jax.lax.scan(step, s0, wx.transpose(1, 0, 2))
    hs = hs.transpose(1, 0, 2, 3).reshape(b, L, d).astype(h.dtype)
    y = rms_norm(hs, p["norm_w"], cfg.norm_eps)
    return dense(y, p["out_proj"]), s_fin
