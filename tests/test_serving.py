"""Continuous-batching engine: slot lifecycle, ragged-masking exactness.

The central correctness property (the co-placement exactness check
applied to continuous batching): an active slot's decode trajectory must
be bit-identical whether it runs alone or while other slots join and
leave around it — per-slot lengths, masked appends, and need_select
blending make every cross-slot interaction a no-op.

The same property extends across layouts: the engine under
``layout="coplace_shmap"`` (shard_map partial attention over sharded
pages) must reproduce the default-layout engine's token trace for the
same admission trace (exercised on a host-local multi-device mesh; the
8-fake-device check runs as a slow subprocess test).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models import model as M
from repro.serving import Engine, Request

CAP = 64
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_arch("smollm-360m"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompt(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)


def test_admission_retirement_lifecycle(model):
    """5 requests through 2 slots: budgets honored, slots recycled,
    nothing recompiles per admission."""
    cfg, params = model
    eng = Engine(cfg, params, max_batch=2, capacity=CAP,
                 prompt_buckets=[16])
    reqs = [Request(uid=i, prompt=_prompt(cfg, 16, i), max_new=2 + i)
            for i in range(5)]
    comps = eng.run(reqs)
    assert sorted(comps) == [0, 1, 2, 3, 4]
    for i, c in comps.items():
        assert len(c.tokens) == 2 + i
        assert c.finished_step >= c.admitted_step
    assert not eng.batch.active.any()
    assert (eng.batch.uid == -1).all()
    assert eng.stats.admissions == 5
    assert eng.stats.prefill_chunks == 0      # packed admission
    assert eng.stats.prefills == 5            # deprecated alias
    # 5 admissions into 2 slots share ONE compile of each decode variant
    sizes = eng.jit_cache_sizes()
    for k in ("decode_select", "decode_reuse", "pack"):
        assert sizes[k] in (-1, 0, 1), sizes
    assert sizes["prefill"] in (-1, 1)


def test_engine_matches_lockstep_single(model):
    """A single request decodes bit-identically to the lockstep driver."""
    from repro.launch.serve import generate

    cfg, params = model
    prompt = _prompt(cfg, 24, 42)
    gen = 10
    toks_lock, _ = generate(cfg, params, jnp.asarray(prompt)[None],
                            gen=gen, capacity=CAP)
    toks_lock = np.asarray(toks_lock)[0].tolist()
    eng = Engine(cfg, params, max_batch=3, capacity=CAP,
                 prompt_buckets=[24])
    comps = eng.run([Request(uid=0, prompt=prompt, max_new=gen)])
    assert comps[0].tokens == toks_lock


def test_active_slot_invariant_to_churn(model):
    """Slot A's tokens are unchanged when B and C join/leave mid-flight."""
    cfg, params = model
    prompt = _prompt(cfg, 24, 42)
    gen = 10
    eng_solo = Engine(cfg, params, max_batch=3, capacity=CAP,
                      prompt_buckets=[24, 16])
    solo = eng_solo.run([Request(uid=0, prompt=prompt, max_new=gen)])
    ref = solo[0].tokens
    assert len(ref) == gen

    eng = Engine(cfg, params, max_batch=3, capacity=CAP,
                 prompt_buckets=[24, 16])
    eng.submit(Request(uid=0, prompt=prompt, max_new=gen))
    steps = 0
    while eng._queue or eng.batch.active.any():
        eng._admit()
        eng.step()
        steps += 1
        if steps == 2:  # B joins mid-flight, retires quickly
            eng.submit(Request(uid=1, prompt=_prompt(cfg, 16, 7),
                               max_new=3))
        if steps == 5:  # C joins as B leaves
            eng.submit(Request(uid=2, prompt=_prompt(cfg, 24, 8),
                               max_new=4))
    eng.finalize()
    assert eng.completions[0].tokens == ref
    assert len(eng.completions[1].tokens) == 3
    assert len(eng.completions[2].tokens) == 4


def test_capacity_truncation(model):
    """A request whose budget exceeds capacity is retired at the cache
    boundary instead of writing out of bounds: the prefill token plus one
    decode per writable position [s, CAP)."""
    cfg, params = model
    s = 16
    eng = Engine(cfg, params, max_batch=1, capacity=CAP,
                 prompt_buckets=[s])
    comps = eng.run([Request(uid=0, prompt=_prompt(cfg, s, 3),
                             max_new=10_000)])
    assert len(comps[0].tokens) == CAP - s + 1
    assert eng.batch.lengths[0] == CAP

    with pytest.raises(ValueError, match="max_new"):
        eng.submit(Request(uid=1, prompt=_prompt(cfg, s, 4), max_new=0))


def test_no_recompiles_across_arrival_patterns(model):
    """Steady state: a second, differently-shaped workload reuses every
    compiled function (the engine's no-recompile guarantee)."""
    cfg, params = model
    eng = Engine(cfg, params, max_batch=2, capacity=CAP,
                 prompt_buckets=[16, 24])
    eng.run([Request(uid=0, prompt=_prompt(cfg, 16, 0), max_new=4),
             Request(uid=1, prompt=_prompt(cfg, 24, 1), max_new=7)])
    sizes0 = eng.jit_cache_sizes()
    eng.reset_metrics()
    eng.run([Request(uid=10 + i, prompt=_prompt(cfg, [16, 24][i % 2], i),
                     max_new=2 + 3 * i) for i in range(5)])
    assert eng.jit_cache_sizes() == sizes0


def test_select_dispatch_rate_stays_aligned(model):
    """The PR-5 select-dispatch regression, pinned: slots admitted at
    arbitrary times must NOT stagger the batch's refresh phases. READY
    slots join only at a shared refresh boundary (Engine._promote_ready)
    and every slot starts at phase 0, so all active phases share one
    residue mod the share window and the ``select`` decode variant
    dispatches on ~1/w of decode steps — not nearly every step. Each
    slot's own schedule depends only on its own phase, so token traces
    are unchanged (covered by the churn-invariance tests)."""
    cfg, params = model
    w = cfg.h2eal.share_window
    eng = Engine(cfg, params, max_batch=2, capacity=CAP,
                 prompt_buckets=[16, 24])
    eng.run([Request(uid=i, prompt=_prompt(cfg, [16, 24][i % 2], i),
                     max_new=12) for i in range(4)])
    s = eng.stats
    assert s.select_steps + s.reuse_steps == s.decode_steps
    # aligned phases: one select per w decode steps, plus at most one
    # boundary re-select per admission batch when a join restarts the
    # residue (staggered phases would push this toward decode_steps)
    assert s.select_steps <= s.decode_steps // w + s.admissions + 1, (
        s.select_steps, s.decode_steps, s.admissions)
    assert s.reuse_steps >= s.decode_steps // 2 - s.admissions - 1, (
        s.reuse_steps, s.decode_steps)


def test_serve_cli_ragged_smoke():
    """launch/serve.py --workload ragged runs on the CPU reduced config."""
    from repro.launch.serve import main

    stats = main([
        "--arch", "smollm-360m", "--reduced", "--workload", "ragged",
        "--requests", "4", "--max-batch", "2", "--prompt-buckets", "16,24",
        "--gen-min", "2", "--gen-max", "6", "--report-balance",
    ])
    assert stats["decode_steps"] > 0
    assert 0.0 < stats["occupancy"] <= 1.0
    assert stats["jit_cache"]["decode_select"] in (-1, 1)
    assert stats["balance"]["imbalance_coplaced"] <= \
        stats["balance"]["imbalance_naive"] + 1e-9


def _mixed_workload(cfg, *, seed=2, n=5):
    """Bucketed prompts + ragged budgets; seed fixed so the greedy token
    traces of the compared engines stay off argmax near-ties (the layouts
    and attention impls differ only in float summation order — the
    exact-tie caveat, documented once in EXPERIMENTS.md §Serving
    experiments)."""
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=([16, 24][i % 2],)
                                        ).astype(np.int32),
                    max_new=3 + 2 * i)
            for i in range(n)]


def _run_both_layouts(cfg, params):
    """(default completions, coplace_shmap completions) for the same
    admission trace."""
    eng0 = Engine(cfg, params, max_batch=2, capacity=CAP,
                  prompt_buckets=[16, 24])
    c0 = eng0.run(_mixed_workload(cfg))
    eng1 = Engine(cfg, params, max_batch=2, capacity=CAP,
                  prompt_buckets=[16, 24], layout="coplace_shmap")
    c1 = eng1.run(_mixed_workload(cfg))
    return c0, c1, eng1


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="coplace_shmap needs a multi-device host mesh")
def test_engine_coplace_shmap_matches_default(model):
    """Ragged decode under the sharded co-placement layout emits the same
    tokens as the default-layout engine for the same admission trace
    (token-exact off argmax ties; EXPERIMENTS.md §Serving experiments)."""
    cfg, params = model
    c0, c1, eng1 = _run_both_layouts(cfg, params)
    assert sorted(c0) == sorted(c1)
    for uid in sorted(c0):
        assert c0[uid].tokens == c1[uid].tokens, uid
    assert eng1.stats.admissions == len(c1)


def test_engine_attn_impl_pallas_matches_ref(model):
    """Tier-1 pallas-interpret engine parity: the same admission trace
    served with attn impl "pallas" (Pallas kernels, interpret mode on CPU)
    emits exactly the ref engine's tokens, the impl is baked in at
    construction (no extra compiled entries per impl switch — there is no
    impl switch), and unknown impls are rejected. Token-exactness holds
    off argmax ties; see EXPERIMENTS.md §Serving experiments."""
    cfg, params = model
    e_ref = Engine(cfg, params, max_batch=2, capacity=CAP,
                   prompt_buckets=[16, 24], impl="ref")
    c_ref = e_ref.run(_mixed_workload(cfg, n=3))
    e_pal = Engine(cfg, params, max_batch=2, capacity=CAP,
                   prompt_buckets=[16, 24], impl="pallas")
    c_pal = e_pal.run(_mixed_workload(cfg, n=3))
    assert sorted(c_ref) == sorted(c_pal)
    for uid in sorted(c_ref):
        assert c_ref[uid].tokens == c_pal[uid].tokens, uid
    assert e_pal.attn_impl == "pallas"
    with pytest.raises(ValueError, match="valid impls"):
        Engine(cfg, params, max_batch=2, capacity=CAP,
               prompt_buckets=[16], impl="bogus")


COPLACE_ENGINE_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro.configs import get_arch, reduced
from repro.models import model as M
from tests.test_serving import CAP, _mixed_workload, _run_both_layouts
from repro.serving import Engine, Request

cfg = reduced(get_arch("smollm-360m"))
params = M.init_params(cfg, jax.random.PRNGKey(0))
c0, c1, eng1 = _run_both_layouts(cfg, params)
assert sorted(c0) == sorted(c1)
for uid in sorted(c0):
    assert c0[uid].tokens == c1[uid].tokens, (
        uid, c0[uid].tokens, c1[uid].tokens)
# steady state must also hold sharded: a second differently-shaped
# workload reuses every compiled entry (no post-warmup recompiles)
sizes0 = eng1.jit_cache_sizes()
eng1.reset_metrics()
eng1.run(_mixed_workload(cfg, seed=5, n=4))
assert eng1.jit_cache_sizes() == sizes0, (sizes0, eng1.jit_cache_sizes())
print("COPLACE_ENGINE_EXACT")
"""


@pytest.mark.slow
def test_engine_coplace_shmap_exact_8dev():
    """8-fake-device subprocess: the coplace_shmap engine's ragged decode
    is token-exact vs the default-layout engine and never recompiles
    after warmup."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", COPLACE_ENGINE_CODE],
                         env=env, capture_output=True, text=True,
                         timeout=520, cwd=REPO)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "COPLACE_ENGINE_EXACT" in out.stdout


INTERLEAVE_ENGINE_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
import jax.tree_util as jtu
from jax.sharding import PartitionSpec as P
from repro.configs import get_arch, reduced
from repro.models import model as M
from jax.sharding import AxisType
from tests.test_serving import CAP, _mixed_workload
from repro.serving import Engine

cfg = reduced(get_arch("smollm-360m"))
params = M.init_params(cfg, jax.random.PRNGKey(0))
eng0 = Engine(cfg, params, max_batch=2, capacity=CAP,
              prompt_buckets=[16, 24])
c0 = eng0.run(_mixed_workload(cfg))
# pages -> 'model' AND within-page tokens -> 'data': max_batch=2 cannot
# consume data=4, so the pages leaves genuinely stripe within-page tokens
mesh = jax.make_mesh((4, 2), ("data", "model"),
                      axis_types=(AxisType.Auto,) * 2)
eng1 = Engine(cfg, params, max_batch=2, capacity=CAP,
              prompt_buckets=[16, 24], layout="interleave", mesh=mesh,
              admission="balanced")
ss = eng1.plan.state_shardings(cfg, eng1.batch.serve, batch_size=2)
pages = [s.spec for p, s in jtu.tree_flatten_with_path(ss)[0]
         if "k_pages" in jtu.keystr(p)]
assert pages and all(
    sp == P(None, None, None, "model", "data", None) for sp in pages), pages
c1 = eng1.run(_mixed_workload(cfg))
assert sorted(c0) == sorted(c1)
for uid in sorted(c0):
    assert c0[uid].tokens == c1[uid].tokens, (
        uid, c0[uid].tokens, c1[uid].tokens)
# steady state must also hold sharded: a second differently-shaped
# workload reuses every compiled entry (no post-warmup recompiles)
sizes0 = eng1.jit_cache_sizes()
eng1.reset_metrics()
eng1.run(_mixed_workload(cfg, seed=5, n=4))
assert eng1.jit_cache_sizes() == sizes0, (sizes0, eng1.jit_cache_sizes())
print("INTERLEAVE_ENGINE_EXACT")
"""


@pytest.mark.slow
def test_engine_interleave_exact_8dev():
    """8-fake-device subprocess (the ISSUE-4 acceptance check): ragged
    decode under the GSPMD ``interleave`` layout (pages over 'model',
    within-page tokens striped over 'data') is token-exact vs the
    default-layout engine for the same admission trace, with zero
    post-warmup recompiles — served purely through the core/layouts
    registry entry (no interleave-specific engine code)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", INTERLEAVE_ENGINE_CODE],
                         env=env, capture_output=True, text=True,
                         timeout=520, cwd=REPO)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "INTERLEAVE_ENGINE_EXACT" in out.stdout


PALLAS_ENGINE_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro.configs import get_arch, reduced
from repro.models import model as M
from tests.test_serving import CAP, _mixed_workload
from repro.serving import Engine

cfg = reduced(get_arch("smollm-360m"))
params = M.init_params(cfg, jax.random.PRNGKey(0))
engines = {}
for impl in ("ref", "pallas"):
    engines[impl] = Engine(cfg, params, max_batch=2, capacity=CAP,
                           prompt_buckets=[16, 24],
                           layout="coplace_shmap", impl=impl)
comps = {impl: eng.run(_mixed_workload(cfg, n=4))
         for impl, eng in engines.items()}
assert sorted(comps["ref"]) == sorted(comps["pallas"])
for uid in sorted(comps["ref"]):
    assert comps["ref"][uid].tokens == comps["pallas"][uid].tokens, (
        uid, comps["ref"][uid].tokens, comps["pallas"][uid].tokens)
# the pallas engine must hold the zero-recompile invariant too: a second
# differently-shaped workload reuses every compiled entry
eng = engines["pallas"]
sizes0 = eng.jit_cache_sizes()
eng.reset_metrics()
eng.run(_mixed_workload(cfg, seed=5, n=3))
assert eng.jit_cache_sizes() == sizes0, (sizes0, eng.jit_cache_sizes())
print("PALLAS_ENGINE_EXACT")
"""


@pytest.mark.slow
def test_engine_coplace_shmap_pallas_exact_8dev():
    """8-fake-device subprocess (the ISSUE-3 acceptance check): engine
    decode with attn impl "pallas" (Pallas partial attention + fused
    combine, interpret mode) under coplace_shmap is token-exact vs
    impl "ref" for the same admission trace, with zero post-warmup
    recompiles."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", PALLAS_ENGINE_CODE],
                         env=env, capture_output=True, text=True,
                         timeout=520, cwd=REPO)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "PALLAS_ENGINE_EXACT" in out.stdout


# ---------------------------------------------------------------------------
# Chunked (slot-resident) prefill — ISSUE 5
# ---------------------------------------------------------------------------


def test_chunked_prefill_matches_packed_with_churn(model):
    """Chunked admission is token-exact vs prefill-then-pack for the same
    admission trace, across chunk sizes, prompt lengths, and slot churn
    (off argmax ties; EXPERIMENTS.md §Serving experiments). Also pins the
    zero-recompile invariant: one compiled chunk program serves every
    chunk schedule, including prompt lengths outside the buckets."""
    cfg, params = model
    eng0 = Engine(cfg, params, max_batch=2, capacity=CAP,
                  prompt_buckets=[16, 24])
    ref = {u: c.tokens for u, c in eng0.run(_mixed_workload(cfg)).items()}
    for chunk in (3, 8, 64):
        eng = Engine(cfg, params, max_batch=2, capacity=CAP,
                     prompt_buckets=[16, 24], prefill_chunk=chunk)
        got = eng.run(_mixed_workload(cfg))
        assert sorted(got) == sorted(ref), chunk
        for uid in sorted(ref):
            assert got[uid].tokens == ref[uid], (chunk, uid)
        assert eng.stats.admissions == len(ref)
        assert eng.stats.prefill_chunks > 0
        sizes0 = eng.jit_cache_sizes()
        assert sizes0["prefill_chunk"] in (-1, 1)
        assert sizes0["prefill"] in (-1, 0)       # pack path never used
        # non-bucket prompt lengths reuse the same compiled chunk fn
        eng.reset_metrics()
        rng = np.random.default_rng(chunk)
        eng.run([Request(uid=90 + i, prompt=_prompt(cfg, 5 + 7 * i, i),
                         max_new=2 + i) for i in range(3)])
        assert eng.jit_cache_sizes() == sizes0, chunk


def test_chunked_prefill_property_chunk_x_prompt(model):
    """Hypothesis property: for any chunk size and prompt length,
    feeding the prompt through M.prefill_chunk (against a reset slot of
    the batched state) reproduces the single-shot M.prefill: same greedy
    first token, logits to float tolerance, and identical KV caches up
    to reassociation-level float error."""
    from hypothesis import given, settings, strategies as st

    cfg, params = model
    from repro.runtime import serve as serve_rt
    from repro.serving.engine import _reset_slot

    scfg = serve_rt.ServeConfig(capacity=CAP)
    prefill = jax.jit(serve_rt.make_prefill(cfg, scfg))

    @settings(max_examples=5)
    @given(chunk=st.integers(min_value=1, max_value=40),
           plen=st.integers(min_value=4, max_value=30))
    def check(chunk, plen):
        prompt = _prompt(cfg, plen, seed=chunk * 100 + plen)
        logits1, packed = prefill(params, jnp.asarray(prompt)[None])
        # empty batch-1 state with the reset sentinels, grown chunk-wise
        shapes = jax.eval_shape(prefill, params, prompt[None])[1]
        state = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        state["length"] = jnp.zeros((1,), jnp.int32)
        state = _reset_slot(state, jnp.int32(0))
        step = jax.jit(serve_rt.make_prefill_chunk_step(cfg, scfg,
                                                        chunk=chunk))
        logits2 = None
        for lo in range(0, plen, chunk):
            n = min(chunk, plen - lo)
            toks = np.zeros((1, chunk), np.int32)
            toks[0, :n] = prompt[lo:lo + n]
            logits2, state = step(params, state, jnp.asarray(toks),
                                  jnp.asarray([n], np.int32),
                                  jnp.asarray([True]))
        assert int(state["length"][0]) == plen
        np.testing.assert_allclose(np.asarray(logits2[0]),
                                   np.asarray(logits1[0]),
                                   rtol=2e-4, atol=2e-4)
        assert int(jnp.argmax(logits2[0])) == int(jnp.argmax(logits1[0]))
        # cache equivalence: packed state is scalar-length batch-1; the
        # chunked state must hold the same KV (float tolerance), same
        # page bookkeeping, and the same stream ring occupancy
        import jax.tree_util as jtu
        flat1 = jtu.tree_flatten_with_path(packed)[0]
        flat2 = jtu.tree_flatten_with_path(state)[0]
        for (p1, a), (p2, b) in zip(flat1, flat2):
            ps = jtu.keystr(p1)
            assert ps == jtu.keystr(p2)
            if "length" in ps or "sel_idx" in ps or "importance" in ps:
                continue
            a, b = np.asarray(a), np.asarray(b)
            if a.dtype.kind == "f":
                fin = np.isfinite(a)
                assert (fin == np.isfinite(b)).all(), ps
                np.testing.assert_allclose(b[fin], a[fin], rtol=2e-4,
                                           atol=2e-4, err_msg=ps)
            else:
                np.testing.assert_array_equal(a, b, err_msg=ps)

    check()


def _poll_emits(eng):
    """One ``Engine.poll()``, read from ``Engine.batch`` before and after:
    the uids admitted, the uids whose first token it made, and the
    decode tokens it made per uid."""
    b = eng.batch
    uid0, len0, pref0 = b.uid.copy(), b.lengths.copy(), b.prefilling.copy()
    eng.poll()
    out = {"admitted": set(), "first": set(), "decoded": {}}
    for s in range(len(b.uid)):
        before, after = int(uid0[s]), int(b.uid[s])
        if after >= 0 and after != before:
            out["admitted"].add(after)
            if not b.prefilling[s]:   # its prompt is done within the poll
                out["first"].add(after)
        elif before >= 0:             # same occupant (-1 once retired)
            if pref0[s] and not b.prefilling[s]:
                out["first"].add(before)
            elif not pref0[s] and b.lengths[s] > len0[s]:
                out["decoded"][before] = int(b.lengths[s] - len0[s])
    return out


def test_chunked_decode_continues_during_long_prefill(model):
    """The no-head-of-line acceptance property, step-exact: while a
    max-bucket prompt chunk-prefills over several engine steps, a
    concurrently decoding slot emits one token per engine step. Under
    prefill-then-pack the same admission is atomic — zero tokens emitted
    between the long request's admission and its first token."""
    cfg, params = model

    def serve(prefill_chunk):
        eng = Engine(cfg, params, max_batch=2, capacity=CAP,
                     prompt_buckets=[16, 24],
                     prefill_chunk=prefill_chunk)
        eng.submit(Request(uid=0, prompt=_prompt(cfg, 16, 1), max_new=30))
        steps = 0
        polls = []
        while eng.busy():
            if steps == 2:   # long prompt arrives while uid 0 decodes
                eng.submit(Request(uid=1, prompt=_prompt(cfg, 24, 2),
                                   max_new=3))
            polls.append(_poll_emits(eng))
            steps += 1
        eng.finalize()
        # polls from the one that admitted uid 1 up to (not including)
        # the one that delivered its first token
        admit = next(i for i, p in enumerate(polls) if 1 in p["admitted"])
        first = next(i for i, p in enumerate(polls) if 1 in p["first"])
        during = sum(polls[i]["decoded"].get(0, 0)
                     for i in range(admit, first))
        return eng, during

    eng_c, during_c = serve(prefill_chunk=6)
    eng_p, during_p = serve(prefill_chunk=None)
    # chunked: ceil(24/6) = 4 chunk steps; decode ran in every one of the
    # strictly-between steps. packed: admission is atomic — none.
    assert during_c >= 2, during_c
    assert during_p == 0, during_p
    assert eng_c.completions[1].tokens == eng_p.completions[1].tokens
    assert eng_c.completions[0].tokens == eng_p.completions[0].tokens
    assert eng_c.stats.prefill_chunks >= 4


def test_chunked_prefill_validation(model):
    """Chunked mode rejects what it cannot serve, at construction or
    submit time: frontend-stub archs (no token prompts to chunk) and
    prompts that leave no room to decode. Bucket membership is NOT
    required (chunk compiles are per chunk bucket, not per prompt
    bucket), and recurrent mixers are NOT rejected — they resume their
    per-slot scan state across chunk boundaries (the ISSUE-6 refactor
    deleted the attention-only restriction)."""
    cfg, params = model
    eng = Engine(cfg, params, max_batch=1, capacity=CAP,
                 prompt_buckets=[16], prefill_chunk=4)
    with pytest.raises(ValueError, match="capacity"):
        eng.submit(Request(uid=0, prompt=_prompt(cfg, CAP, 0), max_new=1))
    comps = eng.run([Request(uid=1, prompt=_prompt(cfg, 13, 1), max_new=2)])
    assert len(comps[1].tokens) == 2          # non-bucket length is fine

    vcfg = reduced(get_arch("internvl2-1b"))  # frontend-stub (vlm)
    vparams = M.init_params(vcfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="frontend-stub"):
        Engine(vcfg, vparams, max_batch=1, capacity=CAP,
               prompt_buckets=[16], prefill_chunk=4)
    # packed admission for the same arch still constructs
    Engine(vcfg, vparams, max_batch=1, capacity=CAP, prompt_buckets=[16])

    zcfg = reduced(get_arch("zamba2-2.7b"))   # mamba2 mixers: now served
    zparams = M.init_params(zcfg, jax.random.PRNGKey(0))
    zeng = Engine(zcfg, zparams, max_batch=1, capacity=CAP,
                  prompt_buckets=[16], prefill_chunk=4)
    zc = zeng.run([Request(uid=0, prompt=_prompt(zcfg, 11, 5), max_new=3)])
    assert len(zc[0].tokens) == 3
    assert zeng.stats.prefill_chunks == 3     # ceil(11/4)


def _recurrent_cfgs():
    """(name, cfg) rows covering every recurrent mixer kind plus a
    hybrid that interleaves attention and mamba2 blocks."""
    return [
        ("mamba2", reduced(get_arch("zamba2-2.7b"))),
        ("xlstm", reduced(get_arch("xlstm-125m"))),
        ("hybrid", reduced(get_arch("zamba2-2.7b"),
                           mixer_pattern=("mamba2", "mamba2", "attention"),
                           num_layers=3)),
    ]


@pytest.mark.parametrize("name,cfg",
                         _recurrent_cfgs(),
                         ids=[n for n, _ in _recurrent_cfgs()])
def test_chunked_prefill_recurrent_matches_packed(name, cfg):
    """ISSUE-6 acceptance: chunked admission over recurrent mixers
    (mamba2 SSD scan, mlstm/slstm, and an attention+mamba2 hybrid) is
    token-exact vs prefill-then-pack at chunks {1, 8, 64}, with slot
    churn and zero post-warmup recompiles — per-slot scan state resumes
    across chunk boundaries and decode-state freezing protects slots
    that are mid-prefill while others decode."""
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng0 = Engine(cfg, params, max_batch=2, capacity=CAP,
                  prompt_buckets=[16, 24])
    ref = {u: c.tokens
           for u, c in eng0.run(_mixed_workload(cfg, n=4)).items()}
    for chunk in (1, 8, 64):
        eng = Engine(cfg, params, max_batch=2, capacity=CAP,
                     prompt_buckets=[16, 24], prefill_chunk=chunk)
        got = eng.run(_mixed_workload(cfg, n=4))
        assert sorted(got) == sorted(ref), (name, chunk)
        for uid in sorted(ref):
            assert got[uid].tokens == ref[uid], (name, chunk, uid)
        assert eng.stats.prefill_chunks > 0
        sizes0 = eng.jit_cache_sizes()
        eng.reset_metrics()
        eng.run(_mixed_workload(cfg, seed=9, n=2))
        assert eng.jit_cache_sizes() == sizes0, (name, chunk)


CHUNKED_ENGINE_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro.configs import get_arch, reduced
from repro.models import model as M
from tests.test_serving import CAP, _mixed_workload
from repro.serving import Engine

cfg = reduced(get_arch("smollm-360m"))
params = M.init_params(cfg, jax.random.PRNGKey(0))
eng0 = Engine(cfg, params, max_batch=2, capacity=CAP,
              prompt_buckets=[16, 24])
c0 = eng0.run(_mixed_workload(cfg))
for layout in ("coplace_shmap", "interleave"):
    eng1 = Engine(cfg, params, max_batch=2, capacity=CAP,
                  prompt_buckets=[16, 24], layout=layout,
                  admission="balanced", prefill_chunk=7)
    c1 = eng1.run(_mixed_workload(cfg))
    assert sorted(c0) == sorted(c1), layout
    for uid in sorted(c0):
        assert c0[uid].tokens == c1[uid].tokens, (
            layout, uid, c0[uid].tokens, c1[uid].tokens)
    assert eng1.stats.prefill_chunks > 0
    # zero post-warmup recompiles across mixed prefill+decode steps
    sizes0 = eng1.jit_cache_sizes()
    eng1.reset_metrics()
    eng1.run(_mixed_workload(cfg, seed=5, n=4))
    assert eng1.jit_cache_sizes() == sizes0, (
        layout, sizes0, eng1.jit_cache_sizes())
    print("CHUNKED_ENGINE_EXACT", layout)
"""


@pytest.mark.slow
def test_engine_chunked_sharded_exact_8dev():
    """8-fake-device subprocess (the ISSUE-5 acceptance check): chunked
    slot-resident prefill under BOTH sharded layouts (coplace_shmap
    shard_map co-placement and GSPMD interleave) is token-exact vs the
    default-layout prefill-then-pack engine for the same admission
    trace, with zero post-warmup recompiles across mixed prefill+decode
    steps — the prompt KV streams directly into the sharded paged cache
    through the layout protocol."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", CHUNKED_ENGINE_CODE],
                         env=env, capture_output=True, text=True,
                         timeout=520, cwd=REPO)
    assert out.returncode == 0, out.stderr[-4000:]
    assert out.stdout.count("CHUNKED_ENGINE_EXACT") == 2


CHUNKED_PALLAS_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro.configs import get_arch, reduced
from repro.models import model as M
from tests.test_serving import CAP, _mixed_workload
from repro.serving import Engine

cfg = reduced(get_arch("smollm-360m"))
params = M.init_params(cfg, jax.random.PRNGKey(0))
comps = {}
for impl in ("ref", "pallas"):
    eng = Engine(cfg, params, max_batch=2, capacity=CAP,
                 prompt_buckets=[16, 24], layout="coplace_shmap",
                 impl=impl, prefill_chunk=7)
    comps[impl] = eng.run(_mixed_workload(cfg, n=4))
    assert eng.stats.prefill_chunks > 0, impl
assert sorted(comps["ref"]) == sorted(comps["pallas"])
for uid in sorted(comps["ref"]):
    assert comps["ref"][uid].tokens == comps["pallas"][uid].tokens, (
        uid, comps["ref"][uid].tokens, comps["pallas"][uid].tokens)
# the chunked pallas engine must hold the zero-recompile invariant too
sizes0 = eng.jit_cache_sizes()
eng.reset_metrics()
eng.run(_mixed_workload(cfg, seed=5, n=3))
assert eng.jit_cache_sizes() == sizes0, (sizes0, eng.jit_cache_sizes())
# chunked recurrent state lives in the sharded batched pytree: a hybrid
# attention+mamba2 config serves chunked on the same 8-device mesh and
# matches its own packed trace token-for-token
hcfg = reduced(get_arch("zamba2-2.7b"),
               mixer_pattern=("mamba2", "mamba2", "attention"),
               num_layers=3)
hparams = M.init_params(hcfg, jax.random.PRNGKey(0))
h0 = Engine(hcfg, hparams, max_batch=2, capacity=CAP,
            prompt_buckets=[16, 24]).run(_mixed_workload(hcfg, n=4))
h1 = Engine(hcfg, hparams, max_batch=2, capacity=CAP,
            prompt_buckets=[16, 24],
            prefill_chunk=7).run(_mixed_workload(hcfg, n=4))
for uid in sorted(h0):
    assert h0[uid].tokens == h1[uid].tokens, uid
print("CHUNKED_PALLAS_EXACT")
"""


@pytest.mark.slow
def test_engine_chunked_pallas_exact_8dev():
    """8-fake-device subprocess (the ISSUE-6 acceptance check): chunked
    prefill through ops.chunk_attention / ops.chunk_attention_paged with
    impl "pallas" (interpret mode) under coplace_shmap is token-exact vs
    impl "ref" for the same admission trace, with zero post-warmup
    recompiles; a hybrid attention+mamba2 config chunk-prefills on the
    same mesh and matches its packed trace."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", CHUNKED_PALLAS_CODE],
                         env=env, capture_output=True, text=True,
                         timeout=520, cwd=REPO)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "CHUNKED_PALLAS_EXACT" in out.stdout


def test_balanced_admission_reorders(model):
    """admission="balanced" admits the queued request that flattens the
    per-device page load (sched/balance.admission_score) and still serves
    every request exactly once."""
    from repro.sched import admission_score, device_page_loads

    cfg, params = model
    p = cfg.h2eal.page_size
    # direct scoring: with 4 shards and 3 live pages, a 1-page candidate
    # lands on the already-loaded shard 0; a 5-page candidate wraps and
    # fills shard 3 — the flatter choice must score lower.
    assert device_page_loads([3 * p], n_shards=4, page_size=p) == [1, 1, 1, 0]
    tight = admission_score([3 * p], 5 * p, n_shards=4, page_size=p)
    loose = admission_score([3 * p], 1 * p, n_shards=4, page_size=p)
    assert tight < loose

    eng = Engine(cfg, params, max_batch=2, capacity=CAP,
                 prompt_buckets=[16, 24], admission="balanced",
                 balance_shards=4)
    comps = eng.run(_mixed_workload(cfg, seed=7, n=6))
    # the 16/24-token buckets produce different page remainders mod 4
    # shards, so at least one admission must deviate from FIFO
    assert eng.stats.admission_reorders > 0
    assert sorted(comps) == list(range(6))
    for i, c in comps.items():
        assert len(c.tokens) == 3 + 2 * i
    # FIFO engine on the same workload serves the same completions
    eng_f = Engine(cfg, params, max_batch=2, capacity=CAP,
                   prompt_buckets=[16, 24])
    comps_f = eng_f.run(_mixed_workload(cfg, seed=7, n=6))
    assert sorted(comps_f) == sorted(comps)


def test_slot_lpt_mapping():
    """map_slots: greedy LPT flattens whole-slot placement; imbalance is
    never worse than naive round-robin and totals are conserved."""
    from repro.sched import load_imbalance, map_slots

    loads = [40.0, 3.0, 29.0, 10.0, 12.0, 5.0]
    a = map_slots(loads, 3)
    assert sorted(s for bank in a.banks for s in bank) == list(range(6))
    assert sum(a.loads) == pytest.approx(sum(loads))
    rr = [sum(loads[i] for i in range(len(loads)) if i % 3 == b)
          for b in range(3)]
    assert a.imbalance <= load_imbalance(rr) + 1e-9
    # the 40-load slot alone pins the optimum at 40/33; LPT attains it
    assert a.imbalance == pytest.approx(40.0 / 33.0)


def test_ragged_balance_scoring():
    """sched/balance scores a ragged batch: loads cap at each slot's
    context, co-placement splits exactly, totals are conserved."""
    from repro.configs.base import H2ealConfig
    from repro.sched import (grid_coords, imbalance, occupancy,
                             ragged_loads, slot_head_load, solve_tiling)

    h2 = H2ealConfig()  # sink=4 local=256 select_budget=4096
    # short context: every head is capped at ctx tokens
    assert slot_head_load("streaming", h2, 17) == 17
    assert slot_head_load("retrieval", h2, 17) == pytest.approx(
        17 + 2.0 * 1 / h2.page_size)
    # long context: streaming saturates, retrieval pays the metadata scan
    assert slot_head_load("streaming", h2, 100_000) == h2.sink + h2.local
    long_r = slot_head_load("retrieval", h2, 100_000)
    assert long_r > h2.sink + h2.local + h2.select_budget

    coords = grid_coords(4, 4)
    retr, stream = coords[:4], coords[4:]
    tiles, _ = solve_tiling(retr, stream)
    kinds = {c: ("retrieval" if c in retr else "streaming") for c in coords}
    ctx = [17, 300, 5_000, 100_000]  # a properly ragged batch
    u = ragged_loads(tiles, kinds, h2, ctx, balanced=False)
    b = ragged_loads(tiles, kinds, h2, ctx, balanced=True)
    assert imbalance(b) < 1.01 < imbalance(u)
    assert sum(x.load for x in u) == pytest.approx(sum(x.load for x in b))
    assert occupancy([True, False, True, False]) == 0.5
