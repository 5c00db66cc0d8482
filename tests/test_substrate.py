"""Data pipeline, optimizer, checkpointing, gradient compression."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import ckpt
from repro.data import lm_batch, niah_batch
from repro.optim import (
    AdamWConfig,
    apply_updates,
    cosine_schedule,
    grad_compress,
    init_state,
)


def test_data_deterministic_and_seekable():
    """batch(step) is a pure function — restart-exactness for free."""
    a = lm_batch(jnp.int32(7), batch=4, seq=32, vocab=100)
    b = lm_batch(jnp.int32(7), batch=4, seq=32, vocab=100)
    c = lm_batch(jnp.int32(8), batch=4, seq=32, vocab=100)
    np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                  np.asarray(b["tokens"]))
    assert not np.array_equal(np.asarray(a["tokens"]),
                              np.asarray(c["tokens"]))
    assert a["tokens"].min() >= 0 and a["tokens"].max() < 100
    # labels are next-token shifted with -100 terminator
    np.testing.assert_array_equal(np.asarray(a["labels"][:, :-1]),
                                  np.asarray(a["tokens"][:, 1:]))
    assert np.all(np.asarray(a["labels"][:, -1]) == -100)


def test_niah_batch_structure():
    b = niah_batch(jnp.int32(0), batch=4, seq=64, vocab=256,
                   depth_frac=0.5)
    toks = np.asarray(b["tokens"])
    pos = b["needle_pos"]
    # needle key/value planted; query repeats the key at the end
    np.testing.assert_array_equal(toks[:, pos], toks[:, -1])
    assert np.all(np.asarray(b["answer"]) == toks[:, pos + 1])


def test_adamw_converges_on_quadratic():
    params = {"w": jnp.array([5.0, -3.0])}
    state = init_state(params)
    cfg = AdamWConfig(lr=0.2, weight_decay=0.0)

    def loss(p):
        return jnp.sum(jnp.square(p["w"]))

    for step in range(200):
        g = jax.grad(loss)(params)
        params, state, _ = apply_updates(params, g, state, cfg)
    assert float(loss(params)) < 1e-3


def test_cosine_schedule_shape():
    warm = cosine_schedule(jnp.int32(0), warmup=10, total=100)
    mid = cosine_schedule(jnp.int32(10), warmup=10, total=100)
    end = cosine_schedule(jnp.int32(100), warmup=10, total=100)
    assert float(warm) == 0.0
    assert float(mid) == pytest.approx(1.0, abs=1e-3)
    assert float(end) == pytest.approx(0.1, abs=1e-2)


def test_checkpoint_roundtrip(tmp_path):
    tree = {
        "a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
        "b": {"c": jnp.ones((2, 2), jnp.bfloat16),
              "d": jnp.int32(7)},
    }
    d = str(tmp_path / "ck")
    ckpt.save(d, tree, step=3, metadata={"step": 3, "note": "x"})
    restored, meta = ckpt.restore(d, tree)
    assert meta["note"] == "x"
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]))
    assert restored["b"]["c"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(restored["b"]["c"], np.float32),
        np.asarray(tree["b"]["c"], np.float32))
    assert int(restored["b"]["d"]) == 7


def test_checkpoint_atomicity_and_prune(tmp_path):
    d = str(tmp_path / "ck")
    tree = {"w": jnp.zeros((4,))}
    for s in (1, 2, 3, 4):
        ckpt.save(d, tree, step=s, metadata={"step": s})
    assert ckpt.latest_step(d) == 4
    ckpt.prune_old(d, keep=2)
    steps = sorted(int(x.split("_")[1]) for x in os.listdir(d)
                   if x.startswith("step_"))
    assert steps == [3, 4]
    # a stale tmp dir never shadows a committed checkpoint
    os.makedirs(os.path.join(d, "tmp.99"), exist_ok=True)
    assert ckpt.latest_step(d) == 4


@settings(deadline=None, max_examples=25)
@given(scale=st.floats(1e-3, 1e3))
def test_int8_quantization_bounded_error(scale):
    g = jnp.array(np.random.default_rng(0).normal(size=(64,)) * scale,
                  jnp.float32)
    q, s = grad_compress.quantize_int8(g)
    deq = grad_compress.dequantize_int8(q, s)
    max_err = float(jnp.max(jnp.abs(deq - g)))
    assert max_err <= float(s) / 2 + 1e-6  # half-ULP of the int8 grid


def test_error_feedback_drives_bias_to_zero():
    """With a CONSTANT gradient, error feedback makes the long-run mean of
    the compressed stream converge to the true gradient."""
    g = {"w": jnp.array([0.3e-2, -1.7e-2, 0.9e-2])}
    err = grad_compress.init_error_feedback(g)
    total = jnp.zeros(3)
    n = 50
    for _ in range(n):
        qtree, err = grad_compress.compress_with_feedback(g, err)
        q, s = qtree["w"]
        total = total + grad_compress.dequantize_int8(q, s)
    mean = total / n
    np.testing.assert_allclose(np.asarray(mean), np.asarray(g["w"]),
                               rtol=0.02)
