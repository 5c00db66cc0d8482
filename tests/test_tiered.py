"""Tiered hot/cold KV page residency: the token-exactness property suite.

``Engine(hot_pages=N)`` keeps at most ~N pages per slot device-resident,
spills cold pages to the host far store (the simulated HB far bank), and
prefetches the hottest cold pages one share window ahead of each slot's
selection refresh. The exactness argument under test: page selection
depends ONLY on tau metadata + page_start + q — never on page contents —
so a spilled (zeroed) page is still *selected* bit-identically, the
engine detects the cold miss from the readback, fills the page from the
far store, and replays the same select step. A miss is served late,
never approximated and never skipped.

The property sweep drives random spill/prefetch schedules (hot-set
budget), chunk sizes {1, 8, 64}, and slot churn, asserting the tiered
engine's token traces are bit-identical to the all-resident oracle's.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models import model as M
from repro.serving import Engine, Request
from hypothesis import given, settings, strategies as st

CAP = 128          # 16 pages of 8 -- enough table for real spill traffic


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_arch("smollm-360m"))
    # shrink the local window and select budget so the selectable
    # (= spillable) section of the page table dominates: at the reduced
    # defaults nearly every page is pinned by sink/local and tiering
    # would be a no-op
    cfg = dataclasses.replace(cfg, h2eal=dataclasses.replace(
        cfg.h2eal, local=8, select_budget=16))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompt(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)


def _workload(cfg, seed):
    """Churny 3-request workload over 2 slots: staggered admissions and
    retirements, prompts deep enough to spill (8+ data pages)."""
    return [Request(uid=i, prompt=_prompt(cfg, 64, 100 * seed + i),
                    max_new=6 + 4 * i)
            for i in range(3)]


@pytest.fixture(scope="module")
def oracle(model):
    """All-resident reference traces, computed lazily and cached per
    (chunk, seed) so property examples that share a workload shape pay
    for one oracle run."""
    cfg, params = model
    cache = {}

    def get(chunk, seed):
        key = (chunk, seed)
        if key not in cache:
            eng = Engine(cfg, params, max_batch=2, capacity=CAP,
                         prompt_buckets=[64], prefill_chunk=chunk)
            comps = eng.run(_workload(cfg, seed))
            cache[key] = {u: c.tokens for u, c in comps.items()}
        return cache[key]

    return get


# ---------------------------------------------------------------------------
# The property sweep
# ---------------------------------------------------------------------------


@settings(max_examples=4, deadline=None)
@given(hot_pages=st.integers(min_value=4, max_value=12),
       chunk=st.sampled_from([1, 8, 64]),
       seed=st.integers(min_value=0, max_value=2))
def test_tiered_token_exact_property(model, oracle, hot_pages, chunk, seed):
    """Any hot-set budget x any prefill chunking x any admission seed:
    the tiered engine's token traces equal the all-resident oracle's,
    bit for bit. Tight budgets force dense spill/miss/fill schedules;
    loose budgets mostly prefetch — exactness must hold across the whole
    policy surface."""
    cfg, params = model
    ref = oracle(chunk, seed)
    eng = Engine(cfg, params, max_batch=2, capacity=CAP,
                 prompt_buckets=[64], prefill_chunk=chunk,
                 hot_pages=hot_pages)
    comps = eng.run(_workload(cfg, seed))
    assert sorted(comps) == sorted(ref)
    for uid in sorted(ref):
        assert comps[uid].tokens == ref[uid], (
            hot_pages, chunk, seed, uid)
    s = eng.stats
    assert s.tier_misses == s.tier_fills   # every miss demand-filled
    assert 0.0 <= s.tier_hit_rate <= 1.0
    if hot_pages <= 6:      # tight budget: spilling must actually happen
        assert s.tier_spills > 0, (hot_pages, chunk, seed)


# ---------------------------------------------------------------------------
# Deterministic anchors
# ---------------------------------------------------------------------------


def test_tiered_spills_prefetch_and_no_recompiles(model, oracle):
    """One tight-budget engine across two differently-shaped workloads:
    spill traffic occurs, the selection hit-rate stays meaningful, and —
    the zero-post-warmup-recompile invariant — the second workload
    reuses every compiled entry including the tier spill/fill jits."""
    cfg, params = model
    eng = Engine(cfg, params, max_batch=2, capacity=CAP,
                 prompt_buckets=[64], hot_pages=6)
    comps = eng.run(_workload(cfg, 0))
    ref = oracle(None, 0)
    for uid in sorted(ref):
        assert comps[uid].tokens == ref[uid]
    s = eng.stats
    assert s.tier_spills > 0
    assert s.tier_hits + s.tier_misses > 0
    sizes0 = eng.jit_cache_sizes()
    assert {"tier_gather", "tier_spill", "tier_fill"} <= set(sizes0)
    eng.reset_metrics()
    ref1 = oracle(None, 1)
    comps1 = eng.run(_workload(cfg, 1))
    for uid in sorted(ref1):
        assert comps1[uid].tokens == ref1[uid]
    assert eng.jit_cache_sizes() == sizes0   # no post-warmup recompiles


def test_forced_cold_miss_is_served_late_not_skipped(model):
    """Chaos hook: spill EVERY spillable page — including the currently
    selected ones — right before a slot's selection refresh. The refresh
    must detect the cold selection (tier_misses), demand-fill the pages
    (tier_fills), and still emit the all-resident token trace: the miss
    is served late, never silently skipped."""
    cfg, params = model
    req = lambda: Request(uid=0, prompt=_prompt(cfg, 64, 7), max_new=14)
    ref = Engine(cfg, params, max_batch=1, capacity=CAP,
                 prompt_buckets=[64]).run([req()])[0].tokens

    eng = Engine(cfg, params, max_batch=1, capacity=CAP,
                 prompt_buckets=[64], hot_pages=12)
    eng.submit(req())
    eng._admit()
    w = eng.share_window
    forced = 0
    steps = 0
    while eng.busy():
        b = eng.batch
        if (not forced and steps >= 4 and b.active[0]
                and b.phase[0] % w == 0):
            forced = eng.tier_force_spill(0)
        eng.step()
        steps += 1
    assert forced > 0
    eng.finalize()
    assert eng.completions[0].tokens == ref
    s = eng.stats
    assert s.tier_misses > 0, "forced-cold selection never missed"
    assert s.tier_fills == s.tier_misses     # each one demand-filled
    assert s.tier_hit_rate < 1.0
    # the refresh after the repaired selection re-fills the rest of the
    # (ample, hot_pages=12) want-set speculatively — the prefetch path
    assert s.tier_prefetch > 0


def test_tiered_validation(model):
    """Budget bounds fail at construction; hot_pages=None/0 disables
    tiering entirely (no tier jits, no counters)."""
    cfg, params = model
    with pytest.raises(ValueError, match="hot_pages"):
        Engine(cfg, params, max_batch=1, capacity=CAP,
               prompt_buckets=[64], hot_pages=99)
    with pytest.raises(ValueError, match="hot_pages"):
        Engine(cfg, params, max_batch=1, capacity=CAP,
               prompt_buckets=[64], hot_pages=-3)
    eng = Engine(cfg, params, max_batch=1, capacity=CAP,
                 prompt_buckets=[64], hot_pages=None)
    assert eng._tier is None
    assert "tier_fill" not in eng.jit_cache_sizes()
    with pytest.raises(ValueError, match="hot_pages"):
        eng.tier_force_spill(0)
