"""Property tests (hypothesis) for the paging invariants.

The central invariant: the [sink | selected | local] sections are
mutually exclusive and, when top-k spans all selectable pages, their
union covers every in-context token exactly once.
"""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import paging

SINK, LOCAL, PAGE = 2, 16, 8


def _mk_state(ctx: int, capacity_pages: int):
    b, h = 1, 1
    page_start = jnp.full((b, h, capacity_pages), -1, jnp.int32)
    n_live = -(-ctx // PAGE)
    starts = jnp.arange(capacity_pages, dtype=jnp.int32) * PAGE
    page_start = jnp.where(jnp.arange(capacity_pages) < n_live, starts, -1)
    return jnp.broadcast_to(page_start, (b, h, capacity_pages))


@settings(deadline=None, max_examples=60)
@given(ctx=st.integers(min_value=1, max_value=400))
def test_partition_complete_and_disjoint(ctx):
    """With top_k = all pages: every token position in [0, ctx) is valid in
    exactly ONE section slot."""
    cap = -(-400 // PAGE) + 2
    page_start = _mk_state(ctx, cap)
    top_k = cap  # select everything selectable
    fake_scores = jnp.ones((1, 1, cap))
    n_sink, _ = paging.page_counts(sink=SINK, local=LOCAL, page=PAGE)
    first_local = max(ctx - LOCAL, 0) // PAGE
    pidx = np.asarray(page_start[0, 0]) // PAGE
    selectable = (np.asarray(page_start[0, 0]) >= 0) & (pidx >= n_sink) & \
        (pidx < first_local)
    masked = jnp.where(jnp.asarray(selectable)[None, None], fake_scores,
                       paging.NEG_INF)
    sel = paging.select_pages(masked, top_k)
    slots = paging.attended_page_slots(sel, jnp.int32(ctx), sink=SINK,
                                       local=LOCAL, page=PAGE)
    valid = paging.token_validity(slots, page_start, jnp.int32(ctx),
                                  sink=SINK, local=LOCAL, page=PAGE,
                                  top_k=top_k)
    # map each valid slot-token back to its absolute position
    slots_np = np.asarray(slots[0, 0])
    starts = np.asarray(page_start[0, 0])
    pos = (starts[np.maximum(slots_np, 0)][:, None]
           + np.arange(PAGE)[None, :]).reshape(-1)
    v = np.asarray(valid[0, 0])
    covered = pos[v]
    # disjoint: no duplicates
    assert len(covered) == len(set(covered.tolist())), (
        f"duplicated positions at ctx={ctx}")
    # complete: all in-context tokens covered
    assert set(covered.tolist()) == set(range(ctx)), (
        f"missing {set(range(ctx)) - set(covered.tolist())} at ctx={ctx}")


@settings(deadline=None, max_examples=30)
@given(ctx=st.integers(min_value=PAGE * 6, max_value=400),
       top_k=st.integers(min_value=1, max_value=8))
def test_sparse_selection_subset(ctx, top_k):
    """With small top_k, valid positions are a subset of full coverage and
    always include sink + local tokens."""
    cap = -(-400 // PAGE) + 2
    page_start = _mk_state(ctx, cap)
    key = jax.random.fold_in(jax.random.PRNGKey(0), ctx)
    raw = jax.random.normal(key, (1, 1, cap))
    n_sink, _ = paging.page_counts(sink=SINK, local=LOCAL, page=PAGE)
    first_local = max(ctx - LOCAL, 0) // PAGE
    pidx = np.asarray(page_start[0, 0]) // PAGE
    selectable = (np.asarray(page_start[0, 0]) >= 0) & (pidx >= n_sink) & \
        (pidx < first_local)
    masked = jnp.where(jnp.asarray(selectable)[None, None], raw,
                       paging.NEG_INF)
    sel = paging.select_pages(masked, top_k)
    slots = paging.attended_page_slots(sel, jnp.int32(ctx), sink=SINK,
                                       local=LOCAL, page=PAGE)
    valid = paging.token_validity(slots, page_start, jnp.int32(ctx),
                                  sink=SINK, local=LOCAL, page=PAGE,
                                  top_k=top_k)
    slots_np = np.asarray(slots[0, 0])
    starts = np.asarray(page_start[0, 0])
    pos = (starts[np.maximum(slots_np, 0)][:, None]
           + np.arange(PAGE)[None, :]).reshape(-1)
    v = np.asarray(valid[0, 0])
    covered = set(pos[v].tolist())
    # no duplicates
    assert len(pos[v]) == len(covered)
    # in-context only
    assert all(0 <= p < ctx for p in covered)
    # sink pages always covered
    for p in range(min(n_sink * PAGE, ctx)):
        assert p in covered, f"sink-page token {p} missing"
    # local window always covered
    for p in range(max(ctx - LOCAL, 0), ctx):
        assert p in covered, f"local token {p} missing (ctx={ctx})"


def test_importance_accumulates_only_live():
    imp = jnp.zeros((1, 1, 4))
    scores = jnp.array([[[1.0, paging.NEG_INF, 2.0, paging.NEG_INF]]])
    out = paging.accumulate_importance(imp, scores)
    np.testing.assert_allclose(np.asarray(out[0, 0]), [1.0, 0.0, 2.0, 0.0])


def test_evict_lowest_skips_dead_pages():
    imp = jnp.array([[[5.0, 1.0, 3.0, 0.1]]])
    page_start = jnp.array([[[0, 8, 16, -1]]])  # last slot dead
    slot = paging.evict_lowest(imp, page_start)
    assert int(slot[0, 0]) == 1  # lowest LIVE importance
