"""The decode step's layer scan reads the paged KV cache in place.

``models/model.decode_step`` keeps the retrieval heads' k/v pages and
their tau metadata out of the layer scan's inputs and outputs: each
layer gathers from the pages as they were before this step, patches
the step's token into the gathered copy, and the scan hands back one
row per layer, written into the stacked (donated) buffers once after
it. The contract under test:

  * bitwise the same logits and serve state, on both attention impls,
    as the formulation it replaced — append the token to each layer's
    pages, then score, gather and attend (``_append_then_attend`` below,
    an oracle kept here) — on lockstep and ragged batches, with inactive
    slots (whose rows stay bit-stable), tokens that open a page,
    contexts inside the sink page, select steps with a mixed
    ``need_select``, hybrid stacks (full-cache and recurrent layers
    beside paged ones) and the fused decode window;
  * the compiled select and reuse programs hold no copy of the serve
    state: their temporaries are below one layer's k pages and do not
    grow with the layer count.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.core import cache as cachelib
from repro.core import hybrid_attention as hattn
from repro.core import layouts as layoutlib
from repro.core import paging
from repro.kernels import ops as kops
from repro.models import model as M
from repro.runtime import serve as serve_rt

ORACLE = "append_first_oracle"
B = 4
CAP = 128          # 16 pages of 8: selection drops pages past ~ctx 56
STEPS = 40


def _append_then_attend(spec, q, k_new, v_new, paged, stream, length, *,
                        do_select, perm=None, active=None, need_select=None):
    """One decode step as written before the scan read pages in place:
    append the token to the pages first, then score, gather and attend.
    """
    h2, g, nr = spec.h2, spec.group, spec.n_retrieval
    if perm is None:
        perm = hattn.identity_perm(spec)
    qp = hattn._permute_q(q, perm, g)
    kp = hattn._permute_kv(k_new, perm)
    vp = hattn._permute_kv(v_new, perm)
    ctx = length + 1
    outs = []
    if nr > 0:
        paged = cachelib.paged_cache_append(paged, kp[:, :nr], vp[:, :nr],
                                            length, active=active)
        if do_select:
            scores = paging.score_pages(
                qp[:, : nr * g], paged.tau_min, paged.tau_max,
                paged.page_start, ctx, sink=h2.sink, local=h2.local,
                page=h2.page_size, impl=spec.impl)
            sel = paging.select_pages(scores, h2.top_k_pages)
            imp = paging.accumulate_importance(paged.importance, scores)
            if need_select is not None:
                ns = need_select[:, None, None]
                sel = jnp.where(ns, sel, paged.sel_idx)
                imp = jnp.where(ns, imp, paged.importance)
            paged = dataclasses.replace(paged, sel_idx=sel, importance=imp)
        slots = paging.attended_page_slots(
            paged.sel_idx, ctx, sink=h2.sink, local=h2.local,
            page=h2.page_size)
        gk, gv = paging.gather_pages(paged.k_pages, paged.v_pages, slots)
        valid = paging.token_validity(
            slots, paged.page_start, ctx, sink=h2.sink, local=h2.local,
            page=h2.page_size, top_k=h2.top_k_pages)
        outs.append(kops.paged_attention(qp[:, : nr * g], gk, gv, valid,
                                         impl=spec.impl))
    if spec.n_streaming > 0:
        stream = cachelib.stream_cache_append(
            stream, kp[:, nr:], vp[:, nr:], length, sink=h2.sink,
            active=active)
        ctx_b = jnp.broadcast_to(jnp.asarray(ctx, jnp.int32),
                                 (q.shape[0],))[:, None, None]
        valid_s = (stream.pos >= 0) & (
            (stream.pos < h2.sink) | (stream.pos >= ctx_b - h2.local))
        outs.append(kops.paged_attention(qp[:, nr * g:], stream.k, stream.v,
                                         valid_s, impl=spec.impl))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    return hattn._permute_q(out, hattn._inverse_perm(perm), g), paged, stream


class _AppendFirst(layoutlib.DefaultLayout):
    """The default layout with the oracle body and no ``decode_rows``:
    the layer scan threads every cache through its inputs and outputs."""

    name = ORACLE
    decode_rows = None

    def decode(self, spec, state, inputs, *, do_select, perm=None):
        out, paged, stream = _append_then_attend(
            spec, inputs.q, inputs.k_new, inputs.v_new, state["paged"],
            state["stream"], inputs.lengths, do_select=do_select, perm=perm,
            active=inputs.active, need_select=inputs.need_select)
        return out, {"paged": paged, "stream": stream}

    ragged_decode = decode


@pytest.fixture(scope="module", autouse=True)
def oracle_layout():
    layoutlib.register_layout(_AppendFirst())
    yield
    del layoutlib._REGISTRY[ORACLE]


def _arch(name):
    if name == "dense":
        return reduced(get_arch("smollm-360m"), num_layers=3)
    if name == "local_global":   # 5 full-cache window layers + 1 paged
        return reduced(get_arch("gemma3-1b"), num_layers=7)
    # mamba2 + attention periods and a mamba2 remainder layer
    return reduced(get_arch("zamba2-2.7b"),
                   mixer_pattern=("mamba2", "attention"), num_layers=5)


def _setup(arch, prompt, batch=B, seed=0):
    cfg = _arch(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, prompt),
                              0, cfg.vocab_size)
    _, state = M.prefill(cfg, params, toks, capacity=CAP)
    return cfg, params, state


def _assert_same(a, b):
    for (path, x), y in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                            jax.tree.leaves(b)):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=jax.tree_util.keystr(path))


def _rows(state, b):
    """Slot ``b``'s row of every serve-state leaf (scan-stacked "blocks"
    leaves carry the batch on axis 1)."""
    return [np.take(np.asarray(x), b, axis=1 if "blocks" in
                    jax.tree_util.keystr(p) else 0)
            for p, x in jax.tree_util.tree_flatten_with_path(state)[0]]


def _ragged_steps(cfg, prompt):
    """(token, active, need_select, do_select) per step: slot 0 always
    decodes, slot 1 rests every third step, slot 2 for a stretch, slot 3
    joins late; selection every share window with a per-slot phase."""
    rng = np.random.default_rng(prompt)
    sw = cfg.h2eal.share_window
    for t in range(STEPS):
        active = np.array([True, t % 3 != 2, not 8 <= t < 20, t >= 6])
        need = active & np.array([(t // sw + b) % 2 == 0 for b in range(B)])
        tok = rng.integers(0, cfg.vocab_size, (B,), dtype=np.int32)
        yield tok, active, need, t % sw == 0


@pytest.mark.parametrize("arch,prompt,impl", [
    ("dense", 5, "ref"), ("dense", 60, "ref"), ("dense", 60, "pallas"),
    ("local_global", 5, "ref"), ("hybrid", 60, "ref")])
def test_ragged_decode_matches_append_first(arch, prompt, impl):
    cfg, params, state = _setup(arch, prompt)
    state = {**state, "length": jnp.full((B,), prompt, jnp.int32)}
    page = cfg.h2eal.page_size
    n_sink, _ = paging.page_counts(sink=cfg.h2eal.sink,
                                   local=cfg.h2eal.local, page=page)

    def steps(layout):
        scfg = serve_rt.ServeConfig(capacity=CAP, layout=layout, impl=impl)
        return {sel: jax.jit(serve_rt.make_ragged_decode_step(
            cfg, scfg, do_select=sel)) for sel in (True, False)}

    new, old = steps("default"), steps(ORACLE)
    st_new = st_old = state
    seen = set()
    for tok, active, need, sel in _ragged_steps(cfg, prompt):
        extra = (jnp.asarray(need),) if sel else ()
        args = (jnp.asarray(tok), jnp.asarray(active)) + extra
        length = np.asarray(st_new["length"])
        before = st_new
        lg_new, st_new = new[sel](params, st_new, *args)
        lg_old, st_old = old[sel](params, st_old, *args)
        live = np.flatnonzero(active)
        np.testing.assert_array_equal(np.asarray(lg_new)[live],
                                      np.asarray(lg_old)[live])
        _assert_same(st_new, st_old)
        for b in np.flatnonzero(~active):
            for x, y in zip(_rows(before, b), _rows(st_new, b)):
                np.testing.assert_array_equal(x, y)
        seen |= {"inactive"} if not active.all() else set()
        seen |= {"opens_page"} if (active & (length % page == 0)).any() \
            else set()
        seen |= {"in_sink"} if (active & (length < n_sink * page)).any() \
            else set()
        seen |= {"mixed_select"} if sel and 0 < need.sum() < \
            active.sum() else set()
    want = {"inactive", "opens_page", "mixed_select"}
    want |= {"in_sink"} if prompt < n_sink * page else set()
    assert want <= seen, want - seen


def test_lockstep_decode_matches_append_first():
    cfg, params, state = _setup("dense", 5, batch=2)
    scfg = lambda layout: serve_rt.ServeConfig(capacity=CAP, layout=layout)
    new = {sel: jax.jit(serve_rt.make_decode_step(
        cfg, scfg("default"), do_select=sel)) for sel in (True, False)}
    old = {sel: jax.jit(serve_rt.make_decode_step(
        cfg, scfg(ORACLE), do_select=sel)) for sel in (True, False)}
    rng = np.random.default_rng(1)
    st_new = st_old = state
    for t in range(STEPS):
        tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (2,), np.int32))
        sel = t % cfg.h2eal.share_window == 0
        lg_new, st_new = new[sel](params, st_new, tok)
        lg_old, st_old = old[sel](params, st_old, tok)
        np.testing.assert_array_equal(np.asarray(lg_new), np.asarray(lg_old))
        _assert_same(st_new, st_old)


def test_fused_window_matches_append_first_steps():
    """A fused decode window (its inner layer scan is decode_step's) is
    bitwise the oracle's per-step loop, sampling and retirement included."""
    from repro.serving import sampling

    window = 3
    cfg, params, state = _setup("dense", 21)
    state = {**state, "length": jnp.array([21, 23, 24, 21], jnp.int32)}
    tok = jnp.array([3, 5, 7, 11], jnp.int32)
    active = jnp.array([True, True, True, False])
    gen = jnp.array([4, 0, 2, 0], jnp.int32)
    budgets = jnp.array([3, 1, 2, 3], jnp.int32)
    base = jax.random.key_data(jax.random.split(jax.random.PRNGKey(5), B))
    base = base.astype(jnp.uint32)
    temp = jnp.array([0.0, 0.8, 0.0, 0.8], jnp.float32)
    topp = jnp.array([1.0, 0.9, 1.0, 0.9], jnp.float32)

    fused = jax.jit(serve_rt.make_fused_window_step(
        cfg, serve_rt.ServeConfig(capacity=CAP), window=window))
    trace, st_f, tok_f, gen_f = fused(params, state, tok, active, gen,
                                      budgets, base, temp, topp)

    step = jax.jit(serve_rt.make_ragged_decode_step(
        cfg, serve_rt.ServeConfig(capacity=CAP, layout=ORACLE),
        do_select=False))
    sample = jax.jit(sampling.sample_tokens)
    st, act, emitted, rows = state, active, jnp.zeros_like(budgets), []
    for _ in range(window):
        logits, st = step(params, st, tok, act)
        tok = jnp.where(act, sample(logits, base, gen, temp, topp), tok)
        gen = jnp.where(act, gen + 1, gen)
        emitted = emitted + act.astype(jnp.int32)
        act = act & (emitted < budgets)
        rows.append(tok)
    np.testing.assert_array_equal(np.asarray(trace), np.stack(rows))
    _assert_same((st_f, tok_f, gen_f), (st, tok, gen))


def _temp_bytes(cfg, *, do_select, batch=B, capacity=4096):
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    state = jax.eval_shape(lambda p: M.prefill(
        cfg, p, jnp.zeros((batch, 8), jnp.int32), capacity=capacity)[1],
        params)
    state["length"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
    vec = lambda dt: jax.ShapeDtypeStruct((batch,), dt)
    fn = jax.jit(serve_rt.make_ragged_decode_step(
        cfg, serve_rt.ServeConfig(capacity=capacity), do_select=do_select),
        donate_argnums=(1,))
    args = (params, state, vec(jnp.int32), vec(bool))
    args += (vec(bool),) if do_select else ()
    mem = fn.lower(*args).compile().memory_analysis()
    k_pages = state["blocks"]["pos0"]["paged"].k_pages
    layer_bytes = k_pages.size * k_pages.dtype.itemsize // cfg.num_layers
    return mem.temp_size_in_bytes, layer_bytes


@pytest.mark.parametrize("do_select", [True, False],
                         ids=["select", "reuse"])
def test_decode_temporaries_hold_no_state_copy(do_select):
    """f32 keeps XLA:CPU's bf16 converts out of the temporaries."""
    cfg = reduced(get_arch("smollm-360m"))
    temp2, layer_pages = _temp_bytes(dataclasses.replace(cfg, num_layers=2),
                                     do_select=do_select)
    temp8, _ = _temp_bytes(dataclasses.replace(cfg, num_layers=8),
                           do_select=do_select)
    assert temp2 < layer_pages and temp8 < layer_pages, (
        temp2, temp8, layer_pages)
    assert temp8 - temp2 < layer_pages, (temp2, temp8, layer_pages)
