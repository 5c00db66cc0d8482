"""Compile every main-path Pallas kernel for a described TPU v5e chip.

Interpret mode (what the other kernel tests run) accepts block shapes
the TPU compiler refuses, so each kernel is also compiled here by the
real Mosaic compiler for a v5e chip that is described, not attached, at
smollm-360m widths (Hq 15, Hkv 5, head_dim 64, pages of 32; the paper's
H2EAL split: 3 retrieval kv heads, 2 streaming). Nothing runs; a
compile that passes is not a chip run. Each compiled program must
contain the kernel (``tpu_custom_call``).
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import chunk_attention as ck
from repro.kernels import flash_attention as fk
from repro.kernels import page_score as sk
from repro.kernels import paged_attention as pk

B = 4                    # engine slots
D, PAGE = 64, 32
HR, HS, G = 3, 2, 3      # retrieval / streaming kv heads, GQA group
SINK, LOCAL, TOP_K = 4, 256, 128
PAGES = 256              # an 8k-token paged cache per head
SEQ = 2048               # packed prefill length
CHUNK = 512              # chunked-prefill budget
# gathered decode buffer: [sink page | top-k pages | local pages]
T_SEL = (1 + TOP_K + LOCAL // PAGE + 1) * PAGE
T_STREAM = SINK + LOCAL + PAGE


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _cases(dt):
    f32, i32, b1 = jnp.float32, jnp.int32, jnp.bool_
    return {
        "flash_attention": (
            lambda q, k, v: fk.flash_attention(q, k, v, causal=True),
            [(1, SEQ, HR * G, D, dt), (1, SEQ, HR, D, dt),
             (1, SEQ, HR, D, dt)]),
        "flash_attention_sink_local": (
            lambda q, k, v: fk.flash_attention(q, k, v, causal=True,
                                               window=LOCAL, sink=SINK),
            [(1, SEQ, HS * G, D, dt), (1, SEQ, HS, D, dt),
             (1, SEQ, HS, D, dt)]),
        "chunk_attention": (
            ck.chunk_attention,
            [(B, CHUNK, HS * G, D, dt), (B, HS, T_STREAM + CHUNK, D, dt),
             (B, HS, T_STREAM + CHUNK, D, dt),
             (B, HS, CHUNK, T_STREAM + CHUNK, b1)]),
        "chunk_attention_paged": (
            ck.chunk_attention_paged,
            [(B, CHUNK, HR * G, D, dt), (B, HR, PAGES, PAGE, D, dt),
             (B, HR, PAGES, PAGE, D, dt), (B, HR, PAGES, i32), (B, i32),
             (B, CHUNK, HR, D, dt), (B, CHUNK, HR, D, dt)]),
        "paged_attention": (
            pk.paged_attention,
            [(B, HR * G, D, dt), (B, HR, T_SEL, D, dt),
             (B, HR, T_SEL, D, dt), (B, HR, T_SEL, b1)]),
        "paged_attention_stream": (
            pk.paged_attention,
            [(B, HS * G, D, dt), (B, HS, T_STREAM, D, dt),
             (B, HS, T_STREAM, D, dt), (B, HS, T_STREAM, b1)]),
        "paged_attention_partial": (
            pk.paged_attention_partial,
            [(B, HR * G, D, dt), (B, HR, T_SEL, D, dt),
             (B, HR, T_SEL, D, dt), (B, HR, T_SEL, b1)]),
        "page_score": (
            sk.page_score,
            [(B, HR * G, D, dt), (B, HR, PAGES, D, f32),
             (B, HR, PAGES, D, f32)]),
        # the partials are f32 whatever the model dtype: 4 banks' worth
        "combine_partials": (
            pk.combine_partials,
            [(4, B, HR * G, f32), (4, B, HR * G, f32),
             (4, B, HR * G, D, f32)]),
    }


# every kernel in both model dtypes, but the dtype-free combine once
CASES = [(k, dt) for k in _cases(jnp.float32)
         for dt in ("bfloat16", "float32")
         if not (k == "combine_partials" and dt == "bfloat16")]


@pytest.mark.parametrize("kernel,dtype", CASES)
def test_kernel_compiles_for_v5e(one_chip, kernel, dtype):
    fn, shapes = _cases(jnp.dtype(dtype))[kernel]
    args = [jax.ShapeDtypeStruct(s[:-1], s[-1], sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("do_select", [True, False], ids=["select", "reuse"])
def test_decode_step_temporaries_hold_no_state_copy_v5e(one_chip, do_select,
                                                        monkeypatch):
    """The ragged decode program at Mistral-7B widths (two layers, bf16,
    8 slots of 32,768 tokens, the paper's H2EAL setting) compiles for v5e
    with temporaries below one layer's k pages: the layer scan reads the
    paged cache in place instead of copying the serve state. (One layer
    trips a check of the TPU compiler's scatter emitter, with or without
    the in-place scan.)"""
    from repro.configs import get_arch
    from repro.kernels import ops
    from repro.models import model as M
    from repro.runtime import serve as serve_rt

    # the kernels ask the default backend (the CPU) whether to interpret
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    b, cap = 8, 32768
    cfg = dataclasses.replace(get_arch("mistral-7b"), num_layers=2)
    params = jax.eval_shape(lambda: M.init_params(
        cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16))
    state = jax.eval_shape(lambda p: M.prefill(
        cfg, p, jnp.zeros((b, 64), jnp.int32), capacity=cap)[1], params)
    state["length"] = jax.ShapeDtypeStruct((b,), jnp.int32)
    put = lambda t: jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), t)
    vec = lambda dt: jax.ShapeDtypeStruct((b,), dt, sharding=one_chip)
    fn = jax.jit(serve_rt.make_ragged_decode_step(
        cfg, serve_rt.ServeConfig(capacity=cap, impl="pallas"),
        do_select=do_select), donate_argnums=(1,))
    args = (put(params), put(state), vec(jnp.int32), vec(bool))
    args += (vec(bool),) if do_select else ()
    compiled = fn.lower(*args).compile()
    k_pages = state["blocks"]["pos0"]["paged"].k_pages
    layer_bytes = k_pages.size * k_pages.dtype.itemsize // cfg.num_layers
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert "tpu_custom_call" in compiled.as_text()
    assert temp < layer_bytes, (temp, layer_bytes)
