"""Per-kernel allclose sweeps: Pallas (interpret mode) vs pure-jnp oracle.

These parity tests are tier-1 (never behind the ``slow`` marker) so
CPU-only CI always exercises the Pallas kernel path — see scripts/ci.sh.
Tolerance bands are documented in EXPERIMENTS.md §Serving experiments.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.page_score import page_score
from repro.kernels.paged_attention import (combine_partials, paged_attention,
                                           paged_attention_partial)

KEY = jax.random.PRNGKey(0)


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


FLASH_CASES = [
    # b, sq, sk, hq, hkv, d, causal, window, sink
    (2, 256, 256, 4, 2, 64, True, 0, 0),
    (1, 128, 128, 4, 4, 64, True, 64, 4),
    (2, 200, 200, 6, 2, 32, True, 0, 0),       # non-block-multiple
    (1, 256, 256, 2, 1, 128, False, 0, 0),     # non-causal, MQA
    (1, 96, 96, 3, 1, 80, True, 32, 2),        # odd head_dim
    (1, 384, 384, 8, 8, 256, True, 0, 0),      # MHA, big head_dim
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    b, sq, sk, hq, hkv, d, causal, window, sink = case
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (b, sq, hq, d), dtype)
    k = _rand(ks[1], (b, sk, hkv, d), dtype)
    v = _rand(ks[2], (b, sk, hkv, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, sink=sink,
                          interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                  sink=sink)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol,
                               rtol=tol)


PAGED_CASES = [
    (2, 8, 2, 640, 64),
    (1, 4, 4, 500, 128),   # non-block-multiple T
    (2, 2, 1, 100, 32),
    (1, 16, 2, 1024, 64),  # large GQA group
]


@pytest.mark.parametrize("case", PAGED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_matches_ref(case, dtype):
    b, hq, hkv, t, d = case
    ks = jax.random.split(KEY, 4)
    q = _rand(ks[0], (b, hq, d), dtype)
    k = _rand(ks[1], (b, hkv, t, d), dtype)
    v = _rand(ks[2], (b, hkv, t, d), dtype)
    valid = jax.random.bernoulli(ks[3], 0.7, (b, hkv, t))
    out = paged_attention(q, k, v, valid, interpret=True)
    exp = ref.paged_attention_ref(q, k, v, valid)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol,
                               rtol=tol)


def test_paged_attention_all_invalid_is_zero():
    b, hq, hkv, t, d = 1, 4, 2, 64, 32
    q = _rand(KEY, (b, hq, d), jnp.float32)
    k = jnp.ones((b, hkv, t, d))
    v = jnp.ones((b, hkv, t, d))
    valid = jnp.zeros((b, hkv, t), bool)
    out = paged_attention(q, k, v, valid, interpret=True)
    assert np.all(np.asarray(out) == 0.0)


SCORE_CASES = [
    (2, 8, 2, 300, 64),
    (1, 4, 1, 1000, 128),
    (2, 6, 3, 64, 32),
    (1, 4, 4, 37, 16),     # tiny, non-aligned
]


@pytest.mark.parametrize("case", SCORE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_page_score_matches_ref(case, dtype):
    b, hq, hkv, c, d = case
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (b, hq, d), dtype)
    tn = _rand(ks[1], (b, hkv, c, d), jnp.float32) - 1.0
    tx = tn + jnp.abs(_rand(ks[2], (b, hkv, c, d), jnp.float32))
    out = page_score(q, tn, tx, interpret=True)
    exp = ref.page_score_ref(q, tn, tx)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=tol, rtol=tol)


def test_page_score_is_upper_bound():
    """max(q·τmin, q·τmax) ≥ q·k for every key in the page (the Quest
    guarantee that makes top-k selection sound)."""
    ks = jax.random.split(KEY, 2)
    keys = jax.random.normal(ks[0], (1, 2, 16, 8, 32))  # (B,H,pages,P,D)
    q = jax.random.normal(ks[1], (1, 4, 32))
    tn = keys.min(axis=3)
    tx = keys.max(axis=3)
    scores = ref.page_score_ref(q, tn, tx)  # (1, 2, 16)
    group = 2
    qg = np.asarray(q).reshape(1, 2, group, 32)
    per_key = np.einsum("bhgd,bhpkd->bhgpk", qg, np.asarray(keys))
    per_key_groupsum = per_key.sum(axis=2)  # (b, h, p, k)
    assert np.all(np.asarray(scores)[..., None] >= per_key_groupsum - 1e-4)


PARTIAL_CASES = [
    (2, 8, 2, 640, 64),
    (1, 4, 4, 500, 128),   # non-block-multiple T, MHA
    (2, 2, 1, 100, 32),    # MQA
    (1, 16, 2, 1024, 64),  # large GQA group
]


@pytest.mark.parametrize("case", PARTIAL_CASES)
@pytest.mark.parametrize("density", [0.0, 0.4, 1.0])
def test_paged_attention_partial_matches_ref(case, density):
    """Pallas partial decode attention (interpret) vs the pure-jnp oracle
    over ragged validity masks — the (m, l, o) shape contract of
    kernels.ref.paged_attention_partial_ref, tolerance band in
    EXPERIMENTS.md §Serving experiments. density=0.0 is the all-invalid
    identity (m=NEG_INF, l=0, o=0) every retired slot/empty shard hits."""
    b, hq, hkv, t, d = case
    ks = jax.random.split(jax.random.fold_in(KEY, int(density * 10)), 4)
    q = _rand(ks[0], (b, hq, d), jnp.float32)
    k = _rand(ks[1], (b, hkv, t, d), jnp.float32)
    v = _rand(ks[2], (b, hkv, t, d), jnp.float32)
    valid = jax.random.bernoulli(ks[3], density, (b, hkv, t))
    m, l, o = paged_attention_partial(q, k, v, valid, interpret=True)
    me, le, oe = ref.paged_attention_partial_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(m), np.asarray(me),
                               atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(l), np.asarray(le),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(o), np.asarray(oe),
                               atol=2e-5, rtol=2e-5)
    if density == 0.0:
        assert np.all(np.asarray(m) == ref.NEG_INF)
        assert np.all(np.asarray(l) == 0.0)
        assert np.all(np.asarray(o) == 0.0)


def test_combine_partials_kernel_matches_ref():
    """Fused combine epilogue (interpret) vs combine_partials_ref,
    including all-invalid shards in the stack."""
    n, b, hq, d = 8, 3, 4, 32
    ks = jax.random.split(KEY, 3)
    m = jax.random.normal(ks[0], (n, b, hq)) * 3
    l = jnp.abs(jax.random.normal(ks[1], (n, b, hq))) + 0.1
    o = jax.random.normal(ks[2], (n, b, hq, d))
    # two shards contribute nothing (the co-placement identity element)
    m = m.at[1].set(ref.NEG_INF).at[4].set(ref.NEG_INF)
    l = l.at[1].set(0.0).at[4].set(0.0)
    o = o.at[1].set(0.0).at[4].set(0.0)
    got = combine_partials(m, l, o, interpret=True)
    exp = ref.combine_partials_ref(m, l, o, axis=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               atol=2e-6, rtol=2e-6)
    # all shards empty -> zeros, no NaN
    z = combine_partials(jnp.full_like(m, ref.NEG_INF), jnp.zeros_like(l),
                         jnp.zeros_like(o), interpret=True)
    assert np.all(np.asarray(z) == 0.0)


def _partials_fixture(n, seed):
    """n per-shard partials over disjoint token ranges of one softmax."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    b, h, t, d = 2, 3, 16, 8
    logits = jax.random.normal(ks[0], (n, b, h, t)) * 3
    v = jax.random.normal(ks[1], (n, b, h, t, d))
    m = logits.max(axis=-1)
    p = jnp.exp(logits - m[..., None])
    l = p.sum(axis=-1)
    o = jnp.einsum("nbht,nbhtd->nbhd", p, v)
    # shard 0 all-invalid when n allows: identity must drop out exactly
    if n >= 3:
        m = m.at[0].set(ref.NEG_INF)
        l = l.at[0].set(0.0)
        o = o.at[0].set(0.0)
    return m, l, o


@settings(max_examples=10)
@given(n=st.integers(2, 6), seed=st.integers(0, 1 << 16))
def test_combine_partials_associative_and_permutation_invariant(n, seed):
    """The flash-partial merge is an associative, commutative monoid with
    identity (NEG_INF, 0, 0): combining shard partials in any grouping or
    order yields the same softmax output — the algebra that makes the
    co-placed decode independent of bank count and shard order."""
    m, l, o = _partials_fixture(n, seed)
    flat = ref.combine_partials_ref(m, l, o, axis=0)

    # shard-permutation invariance (commutativity)
    perm = jax.random.permutation(jax.random.PRNGKey(seed + 1), n)
    permuted = ref.combine_partials_ref(m[perm], l[perm], o[perm], axis=0)
    np.testing.assert_allclose(np.asarray(permuted), np.asarray(flat),
                               atol=1e-5, rtol=1e-5)

    # associativity: pre-merge any prefix into ONE partial, then combine
    for k in range(1, n):
        mm, lm, om = ref.merge_partials_ref(m[:k], l[:k], o[:k], axis=0)
        m2 = jnp.concatenate([mm[None], m[k:]], axis=0)
        l2 = jnp.concatenate([lm[None], l[k:]], axis=0)
        o2 = jnp.concatenate([om[None], o[k:]], axis=0)
        grouped = ref.combine_partials_ref(m2, l2, o2, axis=0)
        np.testing.assert_allclose(np.asarray(grouped), np.asarray(flat),
                                   atol=1e-5, rtol=1e-5)


def test_ops_impl_validation():
    """kernels.ops raises on unknown impl strings (it used to fall through
    to the kernel path silently) and accepts the legacy "kernel" alias."""
    from repro.kernels import ops

    b, hq, hkv, t, d = 1, 2, 1, 16, 32
    ks = jax.random.split(KEY, 4)
    q = _rand(ks[0], (b, hq, d), jnp.float32)
    k = _rand(ks[1], (b, hkv, t, d), jnp.float32)
    v = _rand(ks[2], (b, hkv, t, d), jnp.float32)
    valid = jnp.ones((b, hkv, t), bool)
    tau_min = _rand(ks[3], (b, hkv, t, d), jnp.float32)   # (B,Hkv,C,D)
    tau_max = tau_min + 1.0
    for fn in (lambda i: ops.paged_attention(q, k, v, valid, impl=i),
               lambda i: ops.paged_attention_partial(q, k, v, valid, impl=i),
               lambda i: ops.flash_attention(
                   q[:, None], k.transpose(0, 2, 1, 3),
                   v.transpose(0, 2, 1, 3), impl=i),
               lambda i: ops.page_score(q, tau_min, tau_max, impl=i)):
        with pytest.raises(ValueError, match="valid impls"):
            fn("cuda")
    with pytest.raises(ValueError, match="valid impls"):
        ops.combine_partials(jnp.zeros((2, 1, 2)), jnp.zeros((2, 1, 2)),
                             jnp.zeros((2, 1, 2, 4)), impl="triton")
    # legacy alias still dispatches to the pallas path
    out = ops.paged_attention(q, k, v, valid, impl="kernel")
    exp = ops.paged_attention(q, k, v, valid, impl="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


def test_kernel_alias_deprecation_warns_once():
    """The legacy impl="kernel" alias (previously silently accepted)
    emits a DeprecationWarning exactly once per process and still
    resolves to "pallas"."""
    import warnings

    from repro.kernels import ops

    ops._warned_aliases.discard("kernel")   # reset the once-per-process latch
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        assert ops.resolve_impl("kernel") == "pallas"
        assert ops.resolve_impl("kernel") == "pallas"   # second call: silent
    dep = [w for w in rec if issubclass(w.category, DeprecationWarning)
           and "deprecated alias" in str(w.message)]
    assert len(dep) == 1, [str(w.message) for w in rec]
    # canonical names never warn
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        assert ops.resolve_impl("pallas") == "pallas"
        assert ops.resolve_impl("ref") == "ref"
    assert not rec


def test_combine_partials_exact():
    """Cross-bank flash combine == softmax over the union (co-placement)."""
    ks = jax.random.split(KEY, 3)
    n, t, d = 4, 32, 16
    logits = jax.random.normal(ks[0], (n, t)) * 3
    v = jax.random.normal(ks[1], (n, t, d))
    m = logits.max(axis=1)
    p = jnp.exp(logits - m[:, None])
    l = p.sum(axis=1)
    o = jnp.einsum("nt,ntd->nd", p, v)
    got = ref.combine_partials_ref(m, l, o, axis=0)
    full = jax.nn.softmax(logits.reshape(-1))
    exp = jnp.einsum("t,td->d", full, v.reshape(-1, d))
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=1e-5)


@settings(max_examples=24)
@given(cq=st.sampled_from([1, 3, 8, 64]),
       density=st.sampled_from([0.0, 0.4, 1.0]),
       seed=st.integers(0, 1 << 16))
def test_chunk_attention_pallas_matches_ref(cq, density, seed):
    """Pallas-interpret chunk_attention vs chunk_attention_ref across
    chunk sizes × ragged validity masks (density=0.0 exercises the
    all-invalid rows -> 0 guard). Token-exactness of the chunked engine
    rides on this parity."""
    from repro.kernels.chunk_attention import chunk_attention

    b, hkv, g, t, d = 2, 2, 2, 37, 32
    hq = hkv * g
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = _rand(ks[0], (b, cq, hq, d), jnp.float32)
    k = _rand(ks[1], (b, hkv, t, d), jnp.float32)
    v = _rand(ks[2], (b, hkv, t, d), jnp.float32)
    valid = jax.random.bernoulli(ks[3], density, (b, hkv, cq, t))
    out = chunk_attention(q, k, v, valid, bt=16, interpret=True)
    exp = ref.chunk_attention_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)
    if density == 0.0:
        assert np.all(np.asarray(out) == 0.0)


def _paged_chunk_fixture(cq, seed, dtype=jnp.float32):
    """A pre-append paged buffer + chunk: slot 0 resumes at start=13
    (one full + one partial page), slot 1 is a fresh slot (start=0,
    no pages written — the garbage buffer must be fully masked)."""
    b, hr, g, d = 2, 2, 2, 32
    cpages, page = 4, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    start = jnp.asarray([13, 0], jnp.int32)
    ps = np.full((b, hr, cpages), -1, np.int32)
    ps[0, :, 0] = 0
    ps[0, :, 1] = 8
    return dict(
        q=_rand(ks[0], (b, cq, hr * g, d), dtype),
        k_pages=_rand(ks[1], (b, hr, cpages, page, d), dtype),
        v_pages=_rand(ks[2], (b, hr, cpages, page, d), dtype),
        page_start=jnp.asarray(ps),
        start=start,
        k_new=_rand(ks[3], (b, cq, hr, d), dtype),
        v_new=_rand(ks[4], (b, cq, hr, d), dtype))


@settings(max_examples=16)
@given(cq=st.sampled_from([1, 3, 8, 64]), seed=st.integers(0, 1 << 16))
def test_chunk_attention_paged_matches_post_append_oracle(cq, seed):
    """The fused pre-append body (ref AND pallas-interpret) equals the
    old formulation — chunk_attention_ref over the post-append buffer
    with an explicit positional mask — and the two impls agree: cache
    keys carry per-KEY validity (pos < start), the intra-chunk part a
    static causal triangle, and their union is the causal key set."""
    from repro.kernels.chunk_attention import chunk_attention_paged

    fx = _paged_chunk_fixture(cq, seed)
    got = ref.chunk_attention_paged_ref(**fx)
    pal = chunk_attention_paged(**fx, bt=8, interpret=True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(got),
                               atol=2e-5, rtol=2e-5)

    b, hr, cpages, page, d = fx["k_pages"].shape
    kb = fx["k_pages"].reshape(b, hr, cpages * page, d)
    vb = fx["v_pages"].reshape(b, hr, cpages * page, d)
    pos = (fx["page_start"][..., None] + jnp.arange(page)
           ).reshape(b, hr, cpages * page)
    ok = jnp.broadcast_to((fx["page_start"] >= 0)[..., None],
                          (b, hr, cpages, page)).reshape(b, hr, -1)
    cache_ok = ok & (pos < fx["start"][:, None, None])
    kc = jnp.concatenate([kb, fx["k_new"].transpose(0, 2, 1, 3)], axis=2)
    vc = jnp.concatenate([vb, fx["v_new"].transpose(0, 2, 1, 3)], axis=2)
    causal = jnp.arange(cq)[:, None] >= jnp.arange(cq)[None, :]
    mask = jnp.concatenate([
        jnp.broadcast_to(cache_ok[:, :, None, :],
                         (b, hr, cq, cpages * page)),
        jnp.broadcast_to(causal[None, None], (b, hr, cq, cq))], axis=-1)
    oracle = ref.chunk_attention_ref(fx["q"], kc, vc, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(oracle),
                               atol=2e-5, rtol=2e-5)


def test_chunk_attention_impl_routing():
    """ops.chunk_attention used to silently ignore ``impl`` (always the
    ref body): unknown impls must now raise like every other op, and
    impl="pallas" must dispatch the real kernel (parity with ref).
    Same contract for the fused ops.chunk_attention_paged."""
    from repro.kernels import ops

    b, cq, hkv, g, t, d = 1, 3, 2, 2, 24, 32
    ks = jax.random.split(KEY, 4)
    q = _rand(ks[0], (b, cq, hkv * g, d), jnp.float32)
    k = _rand(ks[1], (b, hkv, t, d), jnp.float32)
    v = _rand(ks[2], (b, hkv, t, d), jnp.float32)
    valid = jax.random.bernoulli(ks[3], 0.6, (b, hkv, cq, t))
    with pytest.raises(ValueError, match="valid impls"):
        ops.chunk_attention(q, k, v, valid, impl="cuda")
    out = ops.chunk_attention(q, k, v, valid, impl="pallas")
    exp = ops.chunk_attention(q, k, v, valid, impl="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)

    fx = _paged_chunk_fixture(cq=3, seed=7)
    with pytest.raises(ValueError, match="valid impls"):
        ops.chunk_attention_paged(**fx, impl="cuda")
    outp = ops.chunk_attention_paged(**fx, impl="pallas")
    expp = ops.chunk_attention_paged(**fx, impl="ref")
    np.testing.assert_allclose(np.asarray(outp), np.asarray(expp),
                               atol=2e-5, rtol=2e-5)


def test_chunk_attention_paged_casts_chunk_kv_to_cache_dtype():
    """A bf16 cache with f32 chunk KV must attend the ROUNDTRIPPED chunk
    keys (what a post-append body would read back), keeping chunked
    prefill invariant to when the append happens."""
    from repro.kernels import ops

    fx = _paged_chunk_fixture(cq=4, seed=11, dtype=jnp.bfloat16)
    fx32 = dict(fx, k_new=fx["k_new"].astype(jnp.float32),
                v_new=fx["v_new"].astype(jnp.float32))
    for impl in ("ref", "pallas"):
        a = ops.chunk_attention_paged(**fx, impl=impl)
        bb = ops.chunk_attention_paged(**fx32, impl=impl)
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(bb, np.float32))


def test_chunked_ref_matches_dense():
    import repro.kernels.ref as R
    old_t, old_q = R.CHUNK_THRESHOLD, R.Q_CHUNK
    R.CHUNK_THRESHOLD, R.Q_CHUNK = 64, 64
    try:
        for win, sink in [(0, 0), (64, 4), (32, 0)]:
            ks = jax.random.split(jax.random.fold_in(KEY, win), 3)
            q = _rand(ks[0], (2, 256, 4, 32), jnp.float32)
            k = _rand(ks[1], (2, 256, 2, 32), jnp.float32)
            v = _rand(ks[2], (2, 256, 2, 32), jnp.float32)
            a = R._flash_attention_ref_chunked(
                q, k, v, causal=True, window=win, sink=sink, q_offset=0)
            b = R._flash_attention_ref_dense(
                q, k, v, causal=True, window=win, sink=sink, q_offset=0)
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5)
    finally:
        R.CHUNK_THRESHOLD, R.Q_CHUNK = old_t, old_q
