"""The work counts agree with what the reference attends."""
import jax
import jax.numpy as jnp
import numpy as np

from harness import spec, work

TINY = spec.load_json(spec.BENCH / "tests" / "data" / "tiny.json")


def test_attended_matches_reference_masks():
    ref = spec.load_module("reference", TINY["reference"])
    t, prompt = 256, 100
    hk, hq, hd = (TINY["num_key_value_heads"], TINY["num_attention_heads"],
                  TINY["head_dim"])
    kq = jax.random.split(jax.random.key(0))
    q = jax.random.normal(kq[0], (t, hq, hd))
    k = jax.random.normal(kq[1], (t, hk, hd))
    sel = ref._selection(TINY, q, k, prompt)
    pos = jnp.arange(prompt, t)
    allow = np.asarray(ref.allowed(TINY, pos, t, sel, prompt, t))
    n_ret = hk - round(hk * TINY["h2eal"]["static_sparsity"])
    for i, p in enumerate(range(prompt, t)):
        want_r, want_s = work.attended(TINY, p + 1)
        assert allow[i, :n_ret].sum(axis=1).tolist() == [want_r] * n_ret
        assert allow[i, n_ret:].sum(axis=1).tolist() == \
            [want_s] * (hk - n_ret)


def test_decode_bytes_count_every_weight_once():
    b = work.decode_step_bytes(TINY, [])
    n = (TINY["num_hidden_layers"] * work.layer_params(TINY)
         + work.head_params(TINY) + TINY["hidden_size"])
    assert b == 2 * n
