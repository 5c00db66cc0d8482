"""AttentionLayout registry: resolution, planning, and the layout
conformance sweep.

The sweep is the point of the registry: ONE parameterized test iterates
every registered layout and asserts the engine-level contract — token
exactness vs the ``default`` layout, slot-churn invariance, and the
zero-recompile invariant — so any future ``register_layout()`` entry
gets its conformance tests for free.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.core import layouts as layoutlib
from repro.models import model as M
from repro.serving import Engine, Request
from tests.test_serving import CAP, REPO, _mixed_workload

LAYOUTS = layoutlib.available_layouts()


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_arch("smollm-360m"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# ---------------------------------------------------------------------------
# Registry + DecodeInputs
# ---------------------------------------------------------------------------


def test_registry_resolution():
    assert set(LAYOUTS) >= {"default", "head", "coplace", "interleave",
                            "coplace_shmap"}
    # deprecated aliases (one release): None and "auto" mean default
    assert layoutlib.resolve_layout(None) == "default"
    assert layoutlib.resolve_layout("auto") == "default"
    for name in LAYOUTS:
        assert layoutlib.get_layout(name).name == name
    with pytest.raises(ValueError, match="registered layouts"):
        layoutlib.get_layout("bogus")


def test_layout_alias_deprecation_warns_once():
    """The pre-registry spellings None/"auto" resolve with a one-shot
    DeprecationWarning per spelling (mirroring kernels/ops impl="kernel");
    canonical names resolve silently."""
    import warnings

    layoutlib._warned_aliases.clear()
    try:
        with pytest.warns(DeprecationWarning, match="deprecated alias"):
            assert layoutlib.resolve_layout(None) == "default"
        with pytest.warns(DeprecationWarning, match="deprecated alias"):
            assert layoutlib.resolve_layout("auto") == "default"
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            # second resolution of each alias is silent (warns once)
            assert layoutlib.resolve_layout(None) == "default"
            assert layoutlib.resolve_layout("auto") == "default"
            # canonical names never warn
            for name in layoutlib.available_layouts():
                assert layoutlib.resolve_layout(name) == name
            # the internal (model-layer) lookup never warns at all
            layoutlib._warned_aliases.clear()
            assert layoutlib.get_layout(None).name == "default"
    finally:
        # leave the one-shot set consumed so later tests that pass the
        # aliases internally stay quiet regardless of ordering
        layoutlib._warned_aliases.update(_ALIAS_KEYS)


_ALIAS_KEYS = (None, "auto")


def test_register_custom_layout():
    """A new entry is one register_layout() call away (and is listed)."""

    class Custom(layoutlib.DefaultLayout):
        name = "custom_test_layout"

    try:
        layoutlib.register_layout(Custom())
        assert "custom_test_layout" in layoutlib.available_layouts()
        assert isinstance(layoutlib.get_layout("custom_test_layout"), Custom)
    finally:
        del layoutlib._REGISTRY["custom_test_layout"]
    with pytest.raises(ValueError, match="registered layouts"):
        layoutlib.get_layout("custom_test_layout")


def test_decode_inputs_pytree():
    di = layoutlib.DecodeInputs(
        q=jnp.ones((2, 4, 8)), k_new=jnp.ones((2, 2, 8)),
        v_new=jnp.ones((2, 2, 8)), lengths=jnp.int32(5))
    assert not di.is_ragged
    leaves, treedef = jax.tree_util.tree_flatten(di)
    assert len(leaves) == 4  # None masks are empty subtrees
    di2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert di2.active is None and di2.lengths.shape == ()
    ragged = layoutlib.DecodeInputs(
        q=di.q, k_new=di.k_new, v_new=di.v_new,
        lengths=jnp.array([3, 5], jnp.int32),
        active=jnp.array([True, False]))
    assert ragged.is_ragged


def test_base_layout_ragged_unsupported():
    class NoRagged(layoutlib.AttentionLayout):
        name = "lockstep_only"

    with pytest.raises(NotImplementedError, match="ragged"):
        NoRagged().ragged_decode(None, {}, None, do_select=False)


# ---------------------------------------------------------------------------
# Construction-time planning (the Engine mesh-validation bugfix)
# ---------------------------------------------------------------------------


def test_plan_mesh_and_capacity(model):
    cfg, _ = model
    p = cfg.h2eal.page_size
    plan_d = layoutlib.get_layout("default").plan(cfg)
    assert plan_d.mesh is None and not plan_d.shard_state
    assert plan_d.round_capacity(61) == 61

    plan_i = layoutlib.get_layout("interleave").plan(cfg)
    assert plan_i.shard_state
    assert {"data", "model"} <= set(plan_i.mesh.axis_names)
    nsh = int(plan_i.mesh.shape["model"])
    assert plan_i.capacity_quantum == p * nsh
    assert plan_i.round_capacity(p * nsh + 1) == 2 * p * nsh
    assert plan_i.balance_shards == nsh

    plan_c = layoutlib.get_layout("coplace_shmap").plan(cfg)
    assert plan_c.shard_state and plan_c.capacity_quantum == p * int(
        plan_c.mesh.shape["model"])
    # head parallelism distributes heads, not pages: no rounding, FIFO
    plan_h = layoutlib.get_layout("head").plan(cfg)
    assert plan_h.capacity_quantum == 1 and plan_h.balance_shards == 1


def test_plan_validates_mesh_axes(model):
    """A layout whose mesh requirements aren't met fails at plan/Engine
    construction time, not at the first decode step."""
    from jax.sharding import AxisType

    cfg, params = model
    n = len(jax.devices())
    no_data = jax.make_mesh((n,), ("model",),
                             axis_types=(AxisType.Auto,))
    with pytest.raises(ValueError, match="'data'"):
        layoutlib.get_layout("interleave").plan(cfg, no_data)
    no_model = jax.make_mesh((n,), ("data",),
                              axis_types=(AxisType.Auto,))
    with pytest.raises(ValueError, match="'model'"):
        layoutlib.get_layout("coplace_shmap").plan(cfg, no_model)
    with pytest.raises(ValueError, match="'data'"):
        Engine(cfg, params, max_batch=1, capacity=CAP, prompt_buckets=[16],
               layout="interleave", mesh=no_data)


def test_engine_resolves_layout_through_registry(model):
    cfg, params = model
    with pytest.raises(ValueError, match="registered layouts"):
        Engine(cfg, params, max_batch=1, capacity=CAP, prompt_buckets=[16],
               layout="bogus")
    eng = Engine(cfg, params, max_batch=1, capacity=CAP, prompt_buckets=[16],
                 layout=None)   # deprecated alias
    assert eng.layout == "default" and eng.plan.layout == "default"


def test_state_shardings_resolve_through_registry(model):
    from repro.runtime import sharding as shardlib
    from jax.sharding import AxisType

    cfg, _ = model
    mesh = jax.make_mesh((1, len(jax.devices())), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    with pytest.raises(ValueError, match="registered layouts"):
        shardlib.state_shardings(cfg, mesh, {"x": jnp.zeros((4, 4))},
                                 layout="bogus")


# ---------------------------------------------------------------------------
# The conformance sweep: every registered layout, for free
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def default_trace(model):
    """Reference tokens from the default layout: one mixed (churny)
    workload + the first request served solo."""
    cfg, params = model
    eng = Engine(cfg, params, max_batch=2, capacity=CAP,
                 prompt_buckets=[16, 24])
    reqs = _mixed_workload(cfg, n=3)
    mixed = {u: c.tokens for u, c in eng.run(reqs).items()}
    eng.reset_metrics()
    solo = eng.run([Request(uid=100, prompt=reqs[0].prompt,
                            max_new=reqs[0].max_new)])
    return reqs, mixed, solo[100].tokens


@pytest.mark.parametrize("name", LAYOUTS)
def test_layout_conformance(model, default_trace, name):
    """Engine contract per registered layout: (1) token-exact vs the
    default layout for the same admission trace, (2) slot-churn
    invariance (a request's tokens are identical served solo or amid
    churn), (3) no recompiles across differently-shaped workloads.
    Token-exactness holds off argmax ties (EXPERIMENTS.md §Serving
    experiments)."""
    cfg, params = model
    reqs, mixed_ref, solo_ref = default_trace
    eng = Engine(cfg, params, max_batch=2, capacity=CAP,
                 prompt_buckets=[16, 24], layout=name)
    assert eng.layout == name
    mixed = eng.run(_mixed_workload(cfg, n=3))
    assert sorted(mixed) == sorted(mixed_ref)
    for uid in sorted(mixed_ref):
        assert mixed[uid].tokens == mixed_ref[uid], (name, uid)
    sizes0 = eng.jit_cache_sizes()
    eng.reset_metrics()
    solo = eng.run([Request(uid=100, prompt=reqs[0].prompt,
                            max_new=reqs[0].max_new)])
    assert solo[100].tokens == solo_ref, name          # vs default
    assert solo[100].tokens == mixed_ref[0], name      # churn invariance
    assert eng.jit_cache_sizes() == sizes0, name       # no recompiles


@pytest.mark.parametrize("name", LAYOUTS)
def test_layout_conformance_chunked(model, default_trace, name):
    """Chunked-prefill conformance, for free per registry entry: the
    engine with ``prefill_chunk`` set streams prompts into the layout's
    caches through its ``prefill_chunk`` hook and must reproduce the
    default-layout prefill-then-pack token trace for the same admission
    trace, with zero post-warmup recompiles. Future layouts inherit this
    sweep the moment they register."""
    cfg, params = model
    _, mixed_ref, _ = default_trace
    eng = Engine(cfg, params, max_batch=2, capacity=CAP,
                 prompt_buckets=[16, 24], layout=name, prefill_chunk=5)
    mixed = eng.run(_mixed_workload(cfg, n=3))
    assert sorted(mixed) == sorted(mixed_ref)
    for uid in sorted(mixed_ref):
        assert mixed[uid].tokens == mixed_ref[uid], (name, uid)
    assert eng.stats.prefill_chunks > 0
    sizes0 = eng.jit_cache_sizes()
    eng.reset_metrics()
    eng.run(_mixed_workload(cfg, seed=11, n=2))
    assert eng.jit_cache_sizes() == sizes0, name       # no recompiles


@pytest.mark.parametrize("name", LAYOUTS)
def test_layout_conformance_tiered(model, default_trace, name):
    """Tiered-residency conformance, for free per registry entry: the
    engine with ``hot_pages`` set spills/prefetches cold KV pages
    through the layout's residency plan (LayoutPlan.page_stripe_shards
    maps logical pins to physical pages under striped layouts) and must
    emit the ALL-RESIDENT default-layout token trace bit-identically,
    with zero post-warmup recompiles. Future layouts inherit this sweep
    the moment they register."""
    cfg, params = model
    _, mixed_ref, _ = default_trace
    eng = Engine(cfg, params, max_batch=2, capacity=CAP,
                 prompt_buckets=[16, 24], layout=name, hot_pages=4)
    mixed = eng.run(_mixed_workload(cfg, n=3))
    assert sorted(mixed) == sorted(mixed_ref)
    for uid in sorted(mixed_ref):
        assert mixed[uid].tokens == mixed_ref[uid], (name, uid)
    sizes0 = eng.jit_cache_sizes()
    assert {"tier_gather", "tier_spill", "tier_fill"} <= set(sizes0), name
    eng.reset_metrics()
    eng.run(_mixed_workload(cfg, seed=11, n=2))
    assert eng.jit_cache_sizes() == sizes0, name       # no recompiles


@pytest.fixture(scope="module")
def fused_trace(model):
    """Per-step reference on a share-window-widened config: the reduced
    config pins share_window=2, which leaves fused windows a single
    scan iteration — widening to 4 gives the fused scan real length.
    (share_window only changes the selection cadence, never parameter
    shapes, so the module params are reused.)"""
    import dataclasses

    cfg, params = model
    wcfg = dataclasses.replace(
        cfg, h2eal=dataclasses.replace(cfg.h2eal, share_window=4))
    eng = Engine(wcfg, params, max_batch=2, capacity=CAP,
                 prompt_buckets=[16, 24])
    mixed = {u: c.tokens
             for u, c in eng.run(_mixed_workload(wcfg, n=3)).items()}
    return wcfg, mixed


@pytest.mark.parametrize("name", LAYOUTS)
def test_layout_conformance_fused(model, fused_trace, name):
    """Fused decode-window conformance, for free per registry entry:
    ``Engine(decode_window=w)`` routes the share-window scan through
    the layout's ``decode_window`` hook (core/layouts.py — the default
    implementation jit-scans the layout's own reuse body), so every
    layout including the shard_map co-placement entry must reproduce
    the per-step token trace bit-identically, keep one compiled
    ``fused_window`` entry, and never recompile across
    differently-shaped workloads. Future layouts inherit this sweep the
    moment they register (docs/serving.md §Fused decode windows)."""
    _, params = model
    wcfg, mixed_ref = fused_trace
    eng = Engine(wcfg, params, max_batch=2, capacity=CAP,
                 prompt_buckets=[16, 24], layout=name, decode_window=4)
    mixed = eng.run(_mixed_workload(wcfg, n=3))
    assert sorted(mixed) == sorted(mixed_ref)
    for uid in sorted(mixed_ref):
        assert mixed[uid].tokens == mixed_ref[uid], (name, uid)
    assert eng.stats.fused_windows > 0, name
    sizes0 = eng.jit_cache_sizes()
    assert sizes0["fused_window"] in (-1, 1), (name, sizes0)
    eng.reset_metrics()
    eng.run(_mixed_workload(wcfg, seed=11, n=2))
    assert eng.jit_cache_sizes() == sizes0, name       # no recompiles


def _sampled_workload(cfg, *, n=3, seed=2, temperature=0.8, top_p=0.9):
    """The mixed churny workload with stochastic sampling params; RNG
    keys are owned by (request.seed, uid), so the same list reproduces
    the same trace on any engine configuration."""
    import dataclasses

    return [dataclasses.replace(r, temperature=temperature, top_p=top_p)
            for r in _mixed_workload(cfg, seed=seed, n=n)]


@pytest.fixture(scope="module")
def sampled_trace(model):
    """Reference stochastic tokens from the default layout."""
    cfg, params = model
    eng = Engine(cfg, params, max_batch=2, capacity=CAP,
                 prompt_buckets=[16, 24])
    return {u: c.tokens for u, c in eng.run(_sampled_workload(cfg)).items()}


@pytest.mark.parametrize("name", LAYOUTS)
def test_layout_conformance_sampled(model, sampled_trace, name):
    """Stochastic-sampling conformance, for free per registry entry:
    per-request RNG key lanes make the sampled trace a function of
    (seed, uid, generation index) only, so every layout must reproduce
    the default layout's stochastic tokens exactly, with zero
    post-warmup recompiles (temperature/top_p are jit INPUTS, so the
    greedy and sampled paths share one compiled program)."""
    cfg, params = model
    eng = Engine(cfg, params, max_batch=2, capacity=CAP,
                 prompt_buckets=[16, 24], layout=name)
    mixed = eng.run(_sampled_workload(cfg))
    assert sorted(mixed) == sorted(sampled_trace)
    for uid in sorted(sampled_trace):
        assert mixed[uid].tokens == sampled_trace[uid], (name, uid)
    sizes0 = eng.jit_cache_sizes()
    eng.reset_metrics()
    eng.run(_sampled_workload(cfg, seed=11, n=2))
    assert eng.jit_cache_sizes() == sizes0, name       # no recompiles


@pytest.mark.parametrize("name", LAYOUTS)
def test_layout_conformance_speculative(model, default_trace, sampled_trace,
                                        name):
    """Speculative-decode conformance, for free per registry entry: the
    coupled rejection sampler makes Engine(spec_tokens=k) emit the
    EXACT non-speculative trace — greedy (bit-identical argmax) AND
    stochastic — under every layout, through the layout's own
    verify_chunk/verify_append hooks. One engine serves both workloads:
    the verify jit compiles for one static (B, k) bucket and must not
    grow new entries when temperature flips from 0 to 0.8 or across
    differently-shaped reruns (the zero-post-warmup-recompile invariant
    with sampling + speculation enabled)."""
    cfg, params = model
    _, mixed_ref, _ = default_trace
    eng = Engine(cfg, params, max_batch=2, capacity=CAP,
                 prompt_buckets=[16, 24], layout=name, spec_tokens=4)
    mixed = eng.run(_mixed_workload(cfg, n=3))
    assert sorted(mixed) == sorted(mixed_ref)
    for uid in sorted(mixed_ref):
        assert mixed[uid].tokens == mixed_ref[uid], (name, uid)
    assert eng.stats.spec_steps > 0
    assert eng.stats.mean_accepted_len >= 1.0
    sizes0 = eng.jit_cache_sizes()
    assert sizes0["verify"] >= 1, name
    eng.reset_metrics()
    sampled = eng.run(_sampled_workload(cfg))
    assert sorted(sampled) == sorted(sampled_trace)
    for uid in sorted(sampled_trace):
        assert sampled[uid].tokens == sampled_trace[uid], (name, uid)
    # the greedy->stochastic flip and the rerun compiled NOTHING new:
    # draft/verify/accept all reuse the warm bodies
    assert eng.jit_cache_sizes() == sizes0, name


@pytest.fixture(scope="module")
def hybrid_model():
    """An attention+mamba2 hybrid: the recurrent chunk-resume path must
    conform on every layout, not just the attention-only config."""
    cfg = reduced(get_arch("zamba2-2.7b"),
                  mixer_pattern=("mamba2", "mamba2", "attention"),
                  num_layers=3)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, max_batch=2, capacity=CAP,
                 prompt_buckets=[16, 24])
    mixed = {u: c.tokens for u, c in eng.run(_mixed_workload(cfg, n=3)).items()}
    return cfg, params, mixed


@pytest.mark.parametrize("name", LAYOUTS)
def test_layout_conformance_chunked_recurrent(hybrid_model, name):
    """Chunked-prefill conformance on a recurrent hybrid, per registry
    entry: layouts own only the ATTENTION caches, so the per-slot scan
    state (mamba2 ssm/conv) must resume identically under every layout —
    token-exact vs the default-layout packed trace, zero post-warmup
    recompiles."""
    cfg, params, mixed_ref = hybrid_model
    eng = Engine(cfg, params, max_batch=2, capacity=CAP,
                 prompt_buckets=[16, 24], layout=name, prefill_chunk=5)
    mixed = eng.run(_mixed_workload(cfg, n=3))
    assert sorted(mixed) == sorted(mixed_ref)
    for uid in sorted(mixed_ref):
        assert mixed[uid].tokens == mixed_ref[uid], (name, uid)
    assert eng.stats.prefill_chunks > 0
    sizes0 = eng.jit_cache_sizes()
    eng.reset_metrics()
    eng.run(_mixed_workload(cfg, seed=11, n=2))
    assert eng.jit_cache_sizes() == sizes0, name       # no recompiles


# ---------------------------------------------------------------------------
# Tiered residency under real sharding (8-fake-device subprocess)
# ---------------------------------------------------------------------------


TIERED_SHMAP_CODE = """
import dataclasses
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro.configs import get_arch, reduced
from repro.models import model as M
from repro.serving import Engine
from tests.test_tiered import CAP, _workload

cfg = reduced(get_arch("smollm-360m"))
cfg = dataclasses.replace(cfg, h2eal=dataclasses.replace(
    cfg.h2eal, local=8, select_budget=16))
params = M.init_params(cfg, jax.random.PRNGKey(0))
# CAP=128 -> 16 pages over 8 shards: the physical striping is genuinely
# permuted (logical page p lives at (p % 8) * 2 + p // 8), so the tier
# bitmap, spills, and prefetches all run in remapped page space
eng0 = Engine(cfg, params, max_batch=2, capacity=CAP, prompt_buckets=[64])
c0 = eng0.run(_workload(cfg, 0))
eng1 = Engine(cfg, params, max_batch=2, capacity=CAP, prompt_buckets=[64],
              layout="coplace_shmap", hot_pages=6)
assert eng1.plan.page_stripe_shards == 8
c1 = eng1.run(_workload(cfg, 0))
assert sorted(c0) == sorted(c1)
for uid in sorted(c0):
    assert c0[uid].tokens == c1[uid].tokens, (
        uid, c0[uid].tokens, c1[uid].tokens)
assert eng1.stats.tier_spills > 0, "tiering never spilled"
sizes0 = eng1.jit_cache_sizes()
eng1.reset_metrics()
eng1.run(_workload(cfg, 1))
assert eng1.jit_cache_sizes() == sizes0, (sizes0, eng1.jit_cache_sizes())
print("TIERED_SHMAP_EXACT")
"""


@pytest.mark.slow
def test_layout_tiered_coplace_shmap_8dev():
    """8-fake-device subprocess: the TIERED coplace_shmap engine — tier
    residency tracked in the striped physical page space — is
    token-exact vs the all-resident default-layout engine, actually
    spills, and never recompiles after warmup."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", TIERED_SHMAP_CODE],
                         env=env, capture_output=True, text=True,
                         timeout=520, cwd=REPO)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "TIERED_SHMAP_EXACT" in out.stdout


SPEC_SHMAP_CODE = """
import dataclasses
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro.configs import get_arch, reduced
from repro.models import model as M
from repro.serving import Engine
from tests.test_layouts import _sampled_workload
from tests.test_serving import CAP, _mixed_workload

cfg = reduced(get_arch("smollm-360m"))
params = M.init_params(cfg, jax.random.PRNGKey(0))
# greedy + stochastic references from the non-speculative default engine
eng0 = Engine(cfg, params, max_batch=2, capacity=CAP, prompt_buckets=[16, 24])
g0 = eng0.run(_mixed_workload(cfg, n=3))
s0 = eng0.run(_sampled_workload(cfg))
# the speculative engine under REAL shard_map co-placement: the verify
# chunk flows through the layout's partial-attention body on 8 devices
eng1 = Engine(cfg, params, max_batch=2, capacity=CAP, prompt_buckets=[16, 24],
              layout="coplace_shmap", spec_tokens=4)
g1 = eng1.run(_mixed_workload(cfg, n=3))
assert sorted(g0) == sorted(g1)
for uid in sorted(g0):
    assert g0[uid].tokens == g1[uid].tokens, (
        uid, g0[uid].tokens, g1[uid].tokens)
assert eng1.stats.spec_steps > 0, "speculation never dispatched"
sizes0 = eng1.jit_cache_sizes()
assert sizes0["verify"] >= 1
s1 = eng1.run(_sampled_workload(cfg))
assert sorted(s0) == sorted(s1)
for uid in sorted(s0):
    assert s0[uid].tokens == s1[uid].tokens, (
        uid, s0[uid].tokens, s1[uid].tokens)
assert eng1.jit_cache_sizes() == sizes0, (sizes0, eng1.jit_cache_sizes())
print("SPEC_SHMAP_EXACT")
"""


@pytest.mark.slow
def test_layout_speculative_coplace_shmap_8dev():
    """8-fake-device subprocess: the SPECULATIVE coplace_shmap engine —
    the (B, k) verify chunk dispatched through shard_map partial
    attention with pinned out-shardings — emits the non-speculative
    default-layout trace bit-identically (greedy and stochastic), and
    the greedy->stochastic flip plus rerun compile nothing new."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", SPEC_SHMAP_CODE],
                         env=env, capture_output=True, text=True,
                         timeout=520, cwd=REPO)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "SPEC_SHMAP_EXACT" in out.stdout
