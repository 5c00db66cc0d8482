"""Stochastic sampling + speculative decode: the equivalence battery.

Three layers of proof that PR 8 changes HOW tokens are produced but
never WHICH tokens:

  1. Unit coupling properties of serving/sampling.py — the verify-chunk
     sampler consumes EXACTLY the per-(seed, uid, generation-index) key
     stream of the step-by-step sampler, and temperature 0 is bitwise
     argmax.
  2. Engine equivalences — greedy speculative traces are bit-identical
     to the non-speculative engine for k in {1,2,4,8} across packed AND
     chunked admission amid slot churn; stochastic traces are invariant
     to slot assignment, admission order, and spec_tokens (property
     test over temperature/top_p/seed via hypothesis).
  3. Forced extremes via DraftProvider test doubles — all-reject
     (ConstantDraft) degenerates exactly to the baseline one-token
     step; all-accept (ReplayDraft + share_window >= k) emits k tokens
     per verify event, pinning the accepted-length stats and the
     steps_per_s vs tokens_per_s split.
"""
import dataclasses

import jax
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs import get_arch, reduced
from repro.models import model as M
from repro.serving import Engine, Request
from repro.serving import sampling as samplib
from repro.serving.draft import (ConstantDraft, NgramDraft,
                                 ReplayDraft, resolve_draft)

CAP = 64


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_arch("smollm-360m"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompt(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)


def _requests(cfg, *, n=5, temperature=0.0, top_p=1.0, seed=0):
    """Churny workload: ragged budgets through few slots recycles slots
    mid-run, so every equivalence below is also a slot-churn test."""
    return [Request(uid=i, prompt=_prompt(cfg, [16, 24][i % 2], 7 + i),
                    max_new=3 + 2 * i, temperature=temperature,
                    top_p=top_p, seed=seed)
            for i in range(n)]


def _run(cfg, params, reqs, *, max_batch=2, **kw):
    eng = Engine(cfg, params, max_batch=max_batch, capacity=CAP,
                 prompt_buckets=[16, 24], **kw)
    comps = eng.run(reqs)
    return {u: c.tokens for u, c in comps.items()}, eng


# ---------------------------------------------------------------------------
# 1. Unit properties of the sampler
# ---------------------------------------------------------------------------


def test_sampling_params_validate():
    samplib.SamplingParams().validate()
    samplib.SamplingParams(temperature=0.7, top_p=0.9, seed=3).validate()
    with pytest.raises(ValueError, match="temperature"):
        samplib.SamplingParams(temperature=-0.1).validate()
    with pytest.raises(ValueError, match="top_p"):
        samplib.SamplingParams(top_p=0.0).validate()
    with pytest.raises(ValueError, match="top_p"):
        samplib.SamplingParams(top_p=1.5).validate()


def test_greedy_lane_is_argmax():
    rng = np.random.default_rng(0)
    logits = jax.numpy.asarray(rng.normal(size=(4, 37)).astype(np.float32))
    base = jax.numpy.stack([samplib.request_key(0, u) for u in range(4)])
    toks = samplib.sample_tokens(
        logits, base, np.zeros(4, np.int32), np.zeros(4, np.float32),
        np.ones(4, np.float32))
    assert (np.asarray(toks) == np.argmax(np.asarray(logits), -1)).all()


def test_tiny_top_p_is_argmax():
    """top_p -> 0 keeps only the most probable token: the stochastic
    lane must then agree with argmax at any temperature."""
    rng = np.random.default_rng(1)
    logits = jax.numpy.asarray(rng.normal(size=(6, 53)).astype(np.float32))
    base = jax.numpy.stack([samplib.request_key(9, u) for u in range(6)])
    toks = samplib.sample_tokens(
        logits, base, np.arange(6, dtype=np.int32),
        np.full(6, 1.3, np.float32), np.full(6, 1e-6, np.float32))
    assert (np.asarray(toks) == np.argmax(np.asarray(logits), -1)).all()


@given(seed=st.integers(min_value=0, max_value=1 << 20),
       temperature=st.floats(min_value=0.0, max_value=2.0),
       top_p=st.floats(min_value=0.05, max_value=1.0))
@settings(max_examples=8, deadline=None)
def test_chunk_sampler_coupled_to_step_sampler(seed, temperature, top_p):
    """THE losslessness lemma: column j of ``sample_chunk`` equals the
    step-by-step ``sample_tokens`` at generation index gen + j — the
    verify step's targets ARE the tokens the non-speculative engine
    would sample, for every (seed, temperature, top_p)."""
    B, k, V = 3, 5, 41
    rng = np.random.default_rng(seed)
    logits = jax.numpy.asarray(rng.normal(size=(B, k, V)).astype(np.float32))
    base = jax.numpy.stack([samplib.request_key(seed % 97, u)
                           for u in range(B)])
    gen = np.asarray([0, 3, 11], np.int32)
    t = np.full(B, temperature, np.float32)
    p = np.full(B, top_p, np.float32)
    chunk = np.asarray(samplib.sample_chunk(logits, base, gen, t, p))
    for j in range(k):
        step = np.asarray(samplib.sample_tokens(
            logits[:, j], base, gen + j, t, p))
        assert (chunk[:, j] == step).all(), j


# ---------------------------------------------------------------------------
# 2. Engine equivalences
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def greedy_baseline(model):
    cfg, params = model
    toks, _ = _run(cfg, params, _requests(cfg))
    return toks


@pytest.mark.parametrize("prefill_chunk", [None, 8])
@pytest.mark.parametrize("k", [1, 2, 4, 8])
def test_greedy_speculative_trace_exact(model, greedy_baseline, k,
                                        prefill_chunk):
    """Greedy ``spec_tokens=k`` is bit-identical to ``spec_tokens=None``
    for every k, under packed AND chunked admission, amid slot churn —
    and never recompiles after its first drained workload."""
    cfg, params = model
    toks, eng = _run(cfg, params, _requests(cfg), spec_tokens=k,
                     prefill_chunk=prefill_chunk)
    assert toks == greedy_baseline
    assert eng.stats.spec_steps > 0
    sizes0 = eng.jit_cache_sizes()
    assert sizes0["verify"] == 1, sizes0
    eng.reset_metrics()
    comps = eng.run(_requests(cfg, n=3))
    assert {u: c.tokens for u, c in comps.items()} == {
        u: greedy_baseline[u] for u in comps}
    assert eng.jit_cache_sizes() == sizes0    # zero post-warmup recompiles


@pytest.fixture(scope="module")
def sampling_engines(model):
    """One engine per shape, reused across property examples so jits
    compile once: baseline 2-slot, reordered 4-slot, speculative k=4."""
    cfg, params = model
    base = Engine(cfg, params, max_batch=2, capacity=CAP,
                  prompt_buckets=[16, 24])
    churn = Engine(cfg, params, max_batch=4, capacity=CAP,
                   prompt_buckets=[16, 24])
    spec = Engine(cfg, params, max_batch=2, capacity=CAP,
                  prompt_buckets=[16, 24], prefill_chunk=8,
                  spec_tokens=4)
    return cfg, base, churn, spec


@given(seed=st.integers(min_value=0, max_value=1 << 16),
       temperature=st.floats(min_value=0.2, max_value=1.5),
       top_p=st.floats(min_value=0.3, max_value=1.0))
@settings(max_examples=5, deadline=None)
def test_stochastic_trace_invariances(sampling_engines, seed, temperature,
                                      top_p):
    """Stochastic traces are a pure function of (seed, uid, generation
    index): invariant to slot assignment and admission order (4-slot
    engine fed in reverse) and to ``spec_tokens`` (chunked speculative
    engine) — the RNG-ownership contract, for every drawn policy."""
    cfg, base, churn, spec = sampling_engines
    reqs = _requests(cfg, temperature=temperature, top_p=top_p, seed=seed)
    for eng in (base, churn, spec):
        eng.reset_metrics()
    ref = {u: c.tokens for u, c in base.run(
        [dataclasses.replace(r) for r in reqs]).items()}
    got_churn = {u: c.tokens for u, c in churn.run(
        [dataclasses.replace(r) for r in reversed(reqs)]).items()}
    got_spec = {u: c.tokens for u, c in spec.run(
        [dataclasses.replace(r) for r in reqs]).items()}
    assert got_churn == ref
    assert got_spec == ref
    # genuinely stochastic for at least one drawn policy is asserted by
    # test_stochastic_differs_from_greedy below; here only equality.


def test_stochastic_differs_from_greedy(model):
    """Sanity: temperature actually samples (the stochastic lane is not
    dead code) — some request's trace differs from argmax."""
    cfg, params = model
    greedy, _ = _run(cfg, params, _requests(cfg))
    stoch, _ = _run(cfg, params, _requests(cfg, temperature=1.0, seed=5))
    assert greedy != stoch


def test_per_request_seed_changes_trace(model):
    cfg, params = model
    a, _ = _run(cfg, params, _requests(cfg, temperature=1.0, seed=1))
    b, _ = _run(cfg, params, _requests(cfg, temperature=1.0, seed=2))
    assert a != b
    a2, _ = _run(cfg, params, _requests(cfg, temperature=1.0, seed=1))
    assert a == a2                       # deterministic replay


# ---------------------------------------------------------------------------
# 3. Forced extremes via DraftProvider doubles
# ---------------------------------------------------------------------------


def test_all_reject_degenerates_to_baseline(model, greedy_baseline):
    """ConstantDraft(-1): every draft token rejects, so each verify step
    emits exactly one coupled target — the trajectory AND the per-event
    accepted length pin to the baseline one-token step."""
    cfg, params = model
    toks, eng = _run(cfg, params, _requests(cfg), spec_tokens=4,
                     draft=ConstantDraft(-1))
    assert toks == greedy_baseline
    s = eng.stats
    assert s.spec_slot_steps > 0
    assert s.spec_accepted == s.spec_slot_steps      # 1 token per event
    assert s.mean_accepted_len == 1.0


@pytest.mark.parametrize("k", [2, 4])
def test_all_accept_emits_k_per_step(model, k):
    """ReplayDraft of the baseline trace + share_window == k: every
    draft position matches its coupled target and no clamp binds, so
    each verify event emits exactly k tokens — pinning
    ``mean_accepted_len == k`` and the steps_per_s vs tokens_per_s split
    (the PR-8 stats bugfix: one verify step != one token)."""
    cfg, params = model
    cfg_k = dataclasses.replace(
        cfg, h2eal=dataclasses.replace(cfg.h2eal, share_window=k))
    params_k = params
    max_new = 1 + 3 * k                  # prefill token + 3 full chunks
    req = Request(uid=0, prompt=_prompt(cfg, 16, 3), max_new=max_new)
    base, _ = _run(cfg_k, params_k, [dataclasses.replace(req)])
    toks, eng = _run(cfg_k, params_k, [dataclasses.replace(req)],
                     spec_tokens=k, draft=ReplayDraft({0: base[0]}))
    assert toks == base
    s = eng.stats
    assert s.spec_slot_steps == 3
    assert s.spec_accepted == 3 * k
    assert s.mean_accepted_len == k
    assert s.tokens_out == max_new
    # the rate split: tokens and steps share one wall clock, so their
    # ratio is exactly tokens-per-decode-step (> 1 under acceptance)
    assert s.wall_s > 0
    assert s.tokens_per_s / s.steps_per_s == pytest.approx(
        s.tokens_out / s.decode_steps)
    assert s.tokens_out / s.decode_steps > 1.0


def test_streaming_self_draft_lossless(model, greedy_baseline):
    """The self-draft provider (decode body with retrieval masked to
    sink+local) is lossless like any other draft, and its private jits
    compile once."""
    cfg, params = model
    toks, eng = _run(cfg, params, _requests(cfg, n=3), spec_tokens=2,
                     draft="streaming")
    assert toks == {u: greedy_baseline[u] for u in toks}
    sizes = eng.jit_cache_sizes()
    assert sizes["draft_mask"] == 1 and sizes["draft_decode"] == 1, sizes


def test_draft_resolution_and_gates(model):
    cfg, params = model
    assert isinstance(resolve_draft("ngram"), NgramDraft)
    with pytest.raises(ValueError, match="unknown draft"):
        resolve_draft("bogus")
    kw = dict(max_batch=1, capacity=CAP, prompt_buckets=[16])
    with pytest.raises(ValueError, match="h2eal.local"):
        Engine(cfg, params, spec_tokens=cfg.h2eal.local + 1, **kw)
    with pytest.raises(ValueError, match="tiered"):
        Engine(cfg, params, spec_tokens=2, hot_pages=4, **kw)
    hybrid = dataclasses.replace(cfg, mixer_pattern=("mamba2", "attention"))
    with pytest.raises(ValueError, match="all-attention"):
        Engine(hybrid, params, spec_tokens=2, **kw)


def test_ngram_lookup_prefers_longest_suffix():
    d = NgramDraft(max_n=3)
    #          0  1  2  3  4  5  6  7
    hist = [5, 1, 2, 3, 9, 1, 2, 3]
    # suffix (1,2,3) matches at 1..3 -> continuation starts with 9
    assert d._lookup(hist, 2) == [9, 1]
    # no repeat anywhere: pads with the last token
    assert d._lookup([4, 7, 8], 3) == [8, 8, 8]


def test_spec_admission_score_sees_chunk_horizon():
    """sched/balance: under spec_tokens=k a slot one token below a page
    boundary is scored as opening its next page (the verify chunk will
    commit it before the host can rebalance)."""
    from repro.sched import balance

    kw = dict(n_shards=2, page_size=8)
    plain = balance.admission_score([8], 8, **kw)
    spec = balance.admission_score([8], 8, spec_tokens=8, **kw)
    assert plain != spec                  # horizon crossed a page boundary
    assert balance.admission_score([8], 8, spec_tokens=None, **kw) == plain
    assert balance.admission_score([8], 8, spec_tokens=1, **kw) == plain
