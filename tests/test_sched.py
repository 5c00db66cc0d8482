"""Scheduler properties: mapping (cases a/b/c), tiling, load balance."""
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import H2ealConfig
from repro.sched import (
    balanced_loads,
    grid_coords,
    head_load,
    imbalance,
    map_heads,
    manhattan,
    solve_tiling,
    unbalanced_loads,
)


@settings(deadline=None, max_examples=120)
@given(n_h=st.integers(1, 128), n_b=st.integers(1, 64))
def test_mapping_partitions_heads_exactly(n_h, n_b):
    plan = map_heads(n_h, n_b)
    plan.validate()  # internal asserts: exact head partition, bank counts
    # every stage uses all banks (work + idle == n_b)
    for s in plan.stages:
        assert len(s.heads) * s.banks_per_head + s.idle_banks == n_b


def test_mapping_paper_cases():
    """The paper's own examples: 40 (Vicuna-13B), 32 (LLaMA-2-7B), 16
    (DeepSeek-V2-Lite) KV heads on a 4x4 = 16-bank array."""
    p40 = map_heads(40, 16)
    assert [len(s.heads) for s in p40.stages] == [16, 16, 8]
    p32 = map_heads(32, 16)
    assert [len(s.heads) for s in p32.stages] == [16, 16]
    p16 = map_heads(16, 16)
    assert p16.num_stages == 1
    assert p16.stages[0].banks_per_head == 1
    # case (c): greedy distinct divisors (15 = 8+4+2+1)
    p15 = map_heads(15, 16)
    assert [len(s.heads) for s in p15.stages] == [8, 4, 2, 1]
    assert [s.banks_per_head for s in p15.stages] == [2, 4, 8, 16]
    # greedy-infeasible fallback with idle banks
    p59 = map_heads(5, 9)
    assert p59.total_idle == 4


def test_tiling_minimizes_distance_corner_case():
    """4 retrieval heads at corners of a 4x4 grid: optimal max distance is
    2 (each corner anchors its quadrant)."""
    coords = grid_coords(4, 4)
    retr = [(0, 0), (0, 3), (3, 0), (3, 3)]
    stream = [c for c in coords if c not in retr]
    tiles, d = solve_tiling(retr, stream)
    assert d == 2
    assert len(tiles) == 4
    assert all(len(t.members) == 4 for t in tiles)
    # every bank appears exactly once
    all_members = [m for t in tiles for m in t.members]
    assert sorted(all_members) == sorted(coords)


def test_tiling_adjacent_pairs():
    """n_r == n_s on a line: pairs of adjacent banks, distance 1."""
    retr = [(0, i) for i in range(0, 8, 2)]
    stream = [(0, i) for i in range(1, 8, 2)]
    tiles, d = solve_tiling(retr, stream)
    assert d == 1
    assert all(t.max_dist <= 1 for t in tiles)


@settings(deadline=None, max_examples=40)
@given(n_r=st.integers(1, 8), n_s=st.integers(1, 8))
def test_tiling_feasible_any_mix(n_r, n_s):
    coords = grid_coords(4, 4)[: n_r + n_s]
    retr, stream = coords[:n_r], coords[n_r:]
    tiles, d = solve_tiling(retr, stream)
    t_expect = min(n_r, n_s)
    assert len(tiles) == t_expect
    cap = -(-(n_r + n_s) // t_expect)
    assert all(len(t.members) <= cap for t in tiles)
    all_members = [m for t in tiles for m in t.members]
    assert sorted(all_members) == sorted(coords)


def test_balancing_removes_imbalance():
    """Paper Fig 11: co-placement balances retrieval vs streaming load."""
    coords = grid_coords(4, 4)
    retr = coords[:4]
    stream = coords[4:]
    tiles, _ = solve_tiling(retr, stream)
    kinds = {c: ("retrieval" if c in retr else "streaming") for c in coords}
    h2 = H2ealConfig()
    u = unbalanced_loads(tiles, kinds, h2, pages=8192)
    b = balanced_loads(tiles, kinds, h2, pages=8192)
    assert imbalance(u) > 2.0      # naive placement is badly imbalanced
    assert imbalance(b) < 1.01     # co-placement is exact
    # total work is conserved
    assert abs(sum(x.load for x in u) - sum(x.load for x in b)) < 1e-6


def test_head_load_model():
    h2 = H2ealConfig(sink=4, local=256, select_budget=4096, page_size=32)
    s = head_load("streaming", h2)
    r = head_load("retrieval", h2, metadata_scan_pages=8192)
    assert s == 260
    assert r > 4096  # dominated by the selected tokens
    assert r / s > 10  # the imbalance the paper's Fig 11 shows


# ---------------------------------------------------------------------------
# map_slots (greedy-LPT whole-slot placement) edge cases — the rebalance
# planner (sched/rebalance.py) uses its assignment as the migration target,
# so degenerate inputs must stay well-defined and deterministic.
# ---------------------------------------------------------------------------

def _assert_partition(asn, n_slots):
    placed = sorted(s for bank in asn.banks for s in bank)
    assert placed == list(range(n_slots))


def test_map_slots_tied_loads_deterministic():
    """All-equal loads: the sort is stable and the argmin breaks ties on
    the lowest bank index, so placement is index-round-robin and
    identical on every call."""
    from repro.sched import map_slots

    loads = [5.0] * 6
    a = map_slots(loads, 3)
    _assert_partition(a, 6)
    assert a.banks == ((0, 3), (1, 4), (2, 5))
    assert a.loads == (10.0, 10.0, 10.0)
    assert a.imbalance == 1.0
    for _ in range(3):
        b = map_slots(loads, 3)
        assert b.banks == a.banks and b.loads == a.loads


def test_map_slots_zero_loads():
    """Zero-load slots (e.g. freshly admitted, ctx 0) still partition
    exactly once and score as perfectly balanced, not a div-by-zero."""
    from repro.sched import map_slots

    a = map_slots([0.0, 0.0, 0.0, 0.0], 2)
    _assert_partition(a, 4)
    assert a.loads == (0.0, 0.0)
    assert a.imbalance == 1.0  # load_imbalance's zero-mean convention


def test_map_slots_more_banks_than_slots():
    """n_banks > len(slot_loads): every slot gets its own bank, the
    surplus banks stay empty at zero load, and total load is conserved."""
    from repro.sched import map_slots

    loads = [7.0, 3.0]
    a = map_slots(loads, 5)
    _assert_partition(a, 2)
    assert sum(len(b) for b in a.banks) == 2
    assert max(len(b) for b in a.banks) == 1
    empty = [l for b, l in zip(a.banks, a.loads) if not b]
    assert empty == [0.0, 0.0, 0.0]
    assert sum(a.loads) == pytest.approx(sum(loads))


def test_map_slots_empty_and_single():
    from repro.sched import map_slots

    none = map_slots([], 3)
    assert none.banks == ((), (), ())
    assert none.imbalance == 1.0
    one = map_slots([9.0], 3)
    _assert_partition(one, 1)
    assert one.banks[0] == (0,) and one.loads[0] == 9.0


@settings(deadline=None, max_examples=60)
@given(n_slots=st.integers(0, 24), n_banks=st.integers(1, 8),
       seed=st.integers(0, 1 << 16))
def test_map_slots_partition_and_determinism(n_slots, n_banks, seed):
    import random

    from repro.sched import map_slots

    loads = [random.Random(seed + i).uniform(0.0, 1e6)
             for i in range(n_slots)]
    a = map_slots(loads, n_banks)
    b = map_slots(list(loads), n_banks)
    _assert_partition(a, len(loads))
    assert a.banks == b.banks and a.loads == b.loads  # pure + deterministic
    assert sum(a.loads) == pytest.approx(sum(loads), abs=1e-6)
    # LPT never loads a bank beyond (max slot + mean) — the classic bound
    if loads:
        mean = sum(loads) / n_banks
        assert max(a.loads) <= mean + max(loads) + 1e-6
