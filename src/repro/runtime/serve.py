"""Serving step builders: prefill + decode with H²EAL layouts.

The decode step comes in two compiled variants (select / reuse) realizing
the paper's shared page selection: the serving loop calls the `select`
variant every ``share_window`` steps and the cheaper `reuse` variant in
between — no lax.cond, so each variant's HLO (and roofline) is exact.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import model as M
from repro.runtime import sharding as shardlib


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    capacity: int                 # max context tokens the cache holds
    layout: str = "default"       # core/layouts registry name; the
                                  # legacy None/"auto" spellings resolve
                                  # with a one-shot DeprecationWarning
                                  # (state_shardings keeps its batch-size
                                  # auto rule for an explicit None)
    impl: str = "ref"             # attention kernels: "ref" | "pallas"
                                  # (kernels/ops.py; baked into the
                                  # compiled steps, never a runtime switch)


def _layout(scfg: ServeConfig) -> str:
    """Canonical layout name for the model step functions; raises on
    unknown names with the registered list (core/layouts.py)."""
    from repro.core import layouts as layoutlib

    return layoutlib.resolve_layout(scfg.layout)


def make_prefill(cfg: ArchConfig, scfg: ServeConfig):
    layout = _layout(scfg)

    def prefill(params, batch):
        return M.prefill(cfg, params, batch, capacity=scfg.capacity,
                         impl=scfg.impl, layout=layout)
    return prefill


def make_decode_step(cfg: ArchConfig, scfg: ServeConfig, *, do_select: bool):
    layout = _layout(scfg)

    def decode(params, state, token):
        return M.decode_step(cfg, params, state, token,
                             do_select=do_select, impl=scfg.impl,
                             layout=layout)
    return decode


def make_ragged_decode_step(cfg: ArchConfig, scfg: ServeConfig, *,
                            do_select: bool):
    """Decode step for the continuous-batching engine (repro.serving).

    ``state["length"]`` is per-slot (B,); ``active`` masks live slots. The
    select variant additionally takes ``need_select`` — the per-slot
    share-window phase mask — so each slot refreshes its page selection on
    its own cadence while sharing one compiled program.
    """
    layout = _layout(scfg)
    if do_select:
        def decode(params, state, token, active, need_select):
            return M.decode_step(cfg, params, state, token, do_select=True,
                                 impl=scfg.impl, layout=layout,
                                 active=active, need_select=need_select)
    else:
        def decode(params, state, token, active):
            return M.decode_step(cfg, params, state, token, do_select=False,
                                 impl=scfg.impl, layout=layout,
                                 active=active)
    return decode


def make_sample_step(cfg: ArchConfig, scfg: ServeConfig):
    """Batched per-slot sampler for the engine's decode loop (PR 8).

    (logits (B, V), base (B, 2) uint32, gen (B,) int32, temp/topp (B,),
    active (B,)) -> (tokens (B,) int32, gen') — greedy is the temp==0
    lane of the same compiled program, per-token keys derive in-graph
    from the request-owned base keys, and gen advances for active slots
    only, so one program serves every step (the zero-recompile
    invariant; serving/sampling.py has the RNG-ownership story).
    """
    del cfg, scfg  # sampling is model- and layout-independent

    def sample(logits, base, gen, temp, topp, active):
        from repro.serving import sampling

        with jax.named_scope("sample"):
            tok = sampling.sample_tokens(logits, base, gen, temp, topp)
            return tok, jnp.where(active, gen + 1, gen)
    return sample


def make_verify_step(cfg: ArchConfig, scfg: ServeConfig, *, k: int):
    """Speculative verify step at the static (B, k) bucket (PR 8).

    tokens (B, k) int32: row 0 each slot's pending feed token, rows
    1..k-1 the draft. One compiled program per k: verify-forward over the
    pre-append caches (models/model.verify_forward), coupled target
    sampling (each chunk position uses EXACTLY the per-request key the
    non-speculative sampler would — serving/sampling.sample_chunk), the
    rejection-sampling acceptance rule, and the accepted-prefix commit.

    For point-mass drafts the coupled rule (accept draft d_j iff it
    equals the target sampled from p_j with that position's key; on the
    first mismatch emit the target) IS leftover-probability rejection
    sampling — P(accept) = p_j(d_j), and the emitted token on reject is
    distributed as norm((p_j - q_j)+) — so the output trace is not just
    distributionally but samplewise identical to non-speculative
    sampling, and greedy (temp=0) degenerates to "accept while the draft
    matches argmax". ``max_emit`` (B,) is the engine's host-side clamp
    (share-window boundary, budget, capacity) — acceptance never crosses
    a selection-refresh boundary mid-chunk. Returns
    (targets (B, k), accepted (B,), next_tok (B,), gen', state').
    """
    from repro.serving import sampling

    layout = _layout(scfg)

    def verify(params, state, tokens, active, need_select, base, gen,
               temp, topp, max_emit):
        assert tokens.shape[1] == k, (tokens.shape, k)
        logits, state1, stash = M.verify_forward(
            cfg, params, state, tokens, active=active,
            need_select=need_select, impl=scfg.impl, layout=layout)
        with jax.named_scope("sample"):
            targets = sampling.sample_chunk(logits, base, gen, temp, topp)
        matches = tokens[:, 1:] == targets[:, :-1]          # (B, k-1)
        n_nat = 1 + jnp.sum(
            jnp.cumprod(matches.astype(jnp.int32), axis=1), axis=1)
        n = jnp.clip(n_nat, 1, jnp.maximum(max_emit, 1)).astype(jnp.int32)
        state2 = M.verify_commit(cfg, state1, stash, accepted=n,
                                 active=active, impl=scfg.impl,
                                 layout=layout)
        next_tok = jnp.take_along_axis(targets, (n - 1)[:, None],
                                       axis=1)[:, 0]
        new_gen = jnp.where(active, gen + n, gen)
        return targets, n, next_tok, new_gen, state2
    return verify


def make_prefill_chunk_step(cfg: ArchConfig, scfg: ServeConfig, *,
                            chunk: int):
    """Chunked-prefill half of the engine's mixed prefill+decode step.

    Feeds each prefilling slot's next prompt chunk (≤ ``chunk`` tokens,
    STATIC shape — the chunk-size bucket) directly into the slot's rows
    of the batched sharded serve state through the layout protocol
    (core/layouts.py ``prefill_chunk``). Per-slot chunk lengths and the
    prefilling mask are dynamic, so one compiled program serves every
    chunk schedule.
    """
    layout = _layout(scfg)

    def chunk_step(params, state, tokens, chunk_len, active):
        assert tokens.shape[1] == chunk, (tokens.shape, chunk)
        return M.prefill_chunk(cfg, params, state, tokens,
                               chunk_len=chunk_len, active=active,
                               impl=scfg.impl, layout=layout)
    return chunk_step


def make_fused_window_step(cfg: ArchConfig, scfg: ServeConfig, *,
                           window: int, chunk: int | None = None):
    """Fused decode window: ``window`` reuse steps as ONE program (PR 10).

    A ``lax.scan`` over the reuse-step body with sampling folded in-scan
    and device-side retirement: slot i emits exactly ``budgets[i]``
    tokens (sched/windows.window_budgets — the host-encoded stop
    conditions), then its lane of the carried ``active`` mask flips and
    the remaining iterations leave its rows untouched, bit-identically
    to the per-step loop going inactive. The scan realization routes
    through the layout registry (core/layouts.py ``decode_window``), so
    every entry — including the shard_map ``coplace_shmap`` body —
    inherits fusion without layout-specific engine code.

    Decode-only variant (``chunk=None``)::

        fused(params, state, tok, active, gen, budgets, base, temp, topp)
          -> (trace (window, B) int32, state', tok', gen')

    Mixed variant (``chunk=C``) additionally threads the engine's
    host-presimulated chunked-prefill schedule through the scan — per
    iteration a (B, C) token block + per-slot chunk lengths, applied
    BEFORE the decode half exactly like the per-step mixed step, plus a
    ``finish`` mask marking rows whose prompt completes that iteration
    (their first token is sampled from the chunk logits with gen=0, the
    same program lane as ``Engine._first_token``)::

        fused(params, state, tok, active, gen, budgets, base, temp, topp,
              chunk_tokens (window, B, C), chunk_lens (window, B),
              finish (window, B)) -> (trace, state', tok', gen')

    Rows of ``trace`` beyond a slot's budget hold its last token (the
    where-carry), never fresh samples; the engine slices per-slot
    prefixes on the host. Iterations past the useful length are full
    no-ops (all-inactive masks), so one compiled entry serves every
    boundary residue — the zero-recompile invariant.
    """
    from repro.core import layouts as layoutlib
    from repro.serving import sampling

    layout = _layout(scfg)

    def _decode_half(params, state, tok, act, gen, emitted, budgets,
                     base, temp, topp):
        logits, state = M.decode_step(cfg, params, state, tok,
                                      do_select=False, impl=scfg.impl,
                                      layout=layout, active=act)
        with jax.named_scope("sample"):
            t = sampling.sample_tokens(logits, base, gen, temp, topp)
            tok = jnp.where(act, t, tok)
            gen = jnp.where(act, gen + 1, gen)
        emitted = emitted + act.astype(jnp.int32)
        act = act & (emitted < budgets)
        return state, tok, act, gen, emitted

    if chunk is None:
        def fused(params, state, tok, active, gen, budgets, base, temp,
                  topp):
            def body(carry, _):
                state, tok, act, gen, emitted = carry
                state, tok, act, gen, emitted = _decode_half(
                    params, state, tok, act, gen, emitted, budgets,
                    base, temp, topp)
                return (state, tok, act, gen, emitted), tok

            carry0 = (state, tok, active, gen, jnp.zeros_like(budgets))
            carry, trace = layoutlib.dispatch_decode_window(
                layout, body, carry0, None, length=window)
            state, tok, _, gen, _ = carry
            return trace, state, tok, gen
    else:
        def fused(params, state, tok, active, gen, budgets, base, temp,
                  topp, chunk_tokens, chunk_lens, finish):
            assert chunk_tokens.shape[0] == window, chunk_tokens.shape
            assert chunk_tokens.shape[2] == chunk, chunk_tokens.shape

            def body(carry, xs):
                state, tok, act, gen, emitted = carry
                ctoks, clens, fin = xs
                logits_c, state = M.prefill_chunk(
                    cfg, params, state, ctoks, chunk_len=clens,
                    active=clens > 0, impl=scfg.impl, layout=layout)
                with jax.named_scope("sample"):
                    first = sampling.sample_tokens(
                        logits_c, base, jnp.zeros_like(gen), temp, topp)
                tok = jnp.where(fin, first, tok)
                gen = jnp.where(fin, jnp.ones_like(gen), gen)
                state, tok, act, gen, emitted = _decode_half(
                    params, state, tok, act, gen, emitted, budgets,
                    base, temp, topp)
                return (state, tok, act, gen, emitted), tok

            carry0 = (state, tok, active, gen, jnp.zeros_like(budgets))
            carry, trace = layoutlib.dispatch_decode_window(
                layout, body, carry0, (chunk_tokens, chunk_lens, finish),
                length=window)
            state, tok, _, gen, _ = carry
            return trace, state, tok, gen
    return fused


def jit_serve_steps(cfg: ArchConfig, scfg: ServeConfig, mesh: Mesh, params,
                    state, batch_size: int):
    """Returns (prefill_fn, decode_select_fn, decode_reuse_fn) jitted with
    explicit shardings."""
    ps = shardlib.param_shardings(cfg, mesh, params, mode="serve")
    ss = shardlib.state_shardings(cfg, mesh, state, layout=scfg.layout,
                                  batch_size=batch_size)
    bs = shardlib.batch_sharding(mesh, batch_size)
    scalar = NamedSharding(mesh, P())

    prefill = jax.jit(
        make_prefill(cfg, scfg),
        in_shardings=(ps, bs),
        out_shardings=(bs, ss),
    )
    dec_sel = jax.jit(
        make_decode_step(cfg, scfg, do_select=True),
        in_shardings=(ps, ss, bs),
        out_shardings=(bs, ss),
        donate_argnums=(1,),
    )
    dec_reuse = jax.jit(
        make_decode_step(cfg, scfg, do_select=False),
        in_shardings=(ps, ss, bs),
        out_shardings=(bs, ss),
        donate_argnums=(1,),
    )
    return prefill, dec_sel, dec_reuse
