"""Tracing marks inside the program, on the CPU at a reduced size.

* Host spans: ``Engine.poll()`` opens an ``engine.poll`` step span with
  one child per phase of the step; ``Engine.sync()`` an ``engine.sync``
  span. Read back from a ``jax.profiler`` trace of a few polls.
* Device scopes: the lowered decode (select and reuse), prefill-chunk
  and sample programs carry every ``jax.named_scope`` of the program in
  their ``op_name`` locations, as exact path segments; the layer scan's
  own state slicing and stacking stays under none.
"""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models import model as M
from repro.serving import Engine, Request

SCOPES = ("embed", "qkv", "attn.append", "attn.select", "attn.gather",
          "attn.retrieval", "attn.streaming", "attn.prefill", "attn.out",
          "ffn", "head", "sample")
PHASES = ("engine.admit", "engine.schedule", "engine.chunk",
          "engine.decode", "engine.sample", "engine.account")
ATTN = {"attn.append", "attn.gather", "attn.retrieval", "attn.streaming",
        "attn.out"}
EXPECTED = {
    "decode_select": {"embed", "qkv", "attn.select", "ffn", "head"} | ATTN,
    "decode_reuse": {"embed", "qkv", "ffn", "head"} | ATTN,
    "chunk_step": {"embed", "qkv", "attn.append", "attn.prefill",
                   "attn.out", "ffn", "head"},
    "sample": {"sample"},
}


@pytest.fixture(scope="module")
def engine():
    cfg = reduced(get_arch("smollm-360m"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, max_batch=2, capacity=128,
                 prompt_buckets=(16,), prefill_chunk=16)
    rng = np.random.default_rng(0)
    for uid, n in ((0, 20), (1, 40)):
        eng.submit(Request(uid=uid, max_new=12, prompt=rng.integers(
            0, cfg.vocab_size, size=n).astype(np.int32)))
    return eng


def _events(log_dir):
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    prof = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in prof.planes:
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith("engine."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


def test_engine_spans_nest_under_poll(engine, tmp_path):
    engine.poll()                      # compile outside the trace
    engine.sync()
    jax.profiler.start_trace(str(tmp_path))
    steps = []
    for _ in range(4):
        steps.append(engine.stats.engine_steps)
        engine.poll()
        engine.sync()
    jax.profiler.stop_trace()
    evs = _events(str(tmp_path))
    polls = [e for e in evs if e[0] == "engine.poll"]
    syncs = [e for e in evs if e[0] == "engine.sync"]
    assert len(polls) == 4 and len(syncs) == 4
    assert [int(p[3]["step_num"]) for p in polls] == steps
    for name, s, e, _ in evs:
        inside = [p for p in polls if p[1] <= s and e <= p[2]]
        if name in PHASES:
            assert len(inside) == 1, name
        elif name == "engine.sync":
            assert not inside
        else:
            assert name == "engine.poll"
    # each sync follows its poll
    for p, y in zip(polls, syncs):
        assert p[2] <= y[1]
    seen = {e[0] for e in evs}
    assert set(PHASES) <= seen, sorted(set(PHASES) - seen)


def _locations(text):
    """The named locations (op_name paths) of a lowered program."""
    return set(re.findall(r'loc\("([^"]*)"', text))


@pytest.mark.parametrize("program", sorted(EXPECTED))
def test_programs_carry_scopes(engine, program):
    b = engine.batch
    act = jnp.asarray(np.ones((b.max_batch,), bool))
    args = {
        "decode_select": (engine._dec_sel, (engine.params, b.serve,
                                            engine._tok, act, act)),
        "decode_reuse": (engine._dec_reuse, (engine.params, b.serve,
                                             engine._tok, act)),
        "chunk_step": (engine._chunk, (
            engine.params, b.serve,
            jnp.zeros((b.max_batch, engine.prefill_chunk), jnp.int32),
            jnp.ones((b.max_batch,), jnp.int32), act)),
        "sample": (engine._sample, (
            jnp.zeros((b.max_batch, engine.cfg.vocab_size), jnp.float32),
            b.samp_base, b.samp_gen, b.samp_temp, b.samp_topp, act)),
    }
    fn, fargs = args[program]
    locs = _locations(fn.lower(*fargs).as_text(debug_info=True))
    have = set().union(*(loc.split("/") for loc in locs)) & set(SCOPES)
    assert have == EXPECTED[program], (program, sorted(have))
    if program.startswith("decode"):
        # the layer scan slices and stacks the per-layer state under no
        # scope: that remainder is read as ``unscoped``
        for op in ("dynamic_slice", "dynamic_update_slice"):
            assert f"jit(decode)/while/body/{op}" in locs, op
