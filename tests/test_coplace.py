"""Distributed co-placement (shard_map) decode: exactness vs the
single-device path, on 8 fake devices (subprocess)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core.hybrid_attention import (AttnSpec, init_decode_state,
                                         decode_attention,
                                         decode_attention_coplace)
from repro.configs.base import H2ealConfig
from repro.runtime.hints import sharding_hints
from jax.sharding import AxisType

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
B, Hq, Hkv, D = 2, 4, 2, 32
S, P_, sink, local = 96, 8, 2, 16
h2 = H2ealConfig(sink=sink, local=local, page_size=P_, select_budget=32,
                 share_window=2)
spec = AttnSpec(n_q=Hq, n_kv=Hkv, head_dim=D, h2=h2)
key = jax.random.PRNGKey(0)
ks = jax.random.split(key, 2)
k = jax.random.normal(ks[0], (B, S, Hkv, D))
v = jax.random.normal(ks[1], (B, S, Hkv, D))
pg_s, st_s = init_decode_state(spec, k, v, S, capacity=128)
pg_c, st_c = init_decode_state(spec, k, v, S, capacity=128,
                               interleave_shards=4)
L = jnp.int32(S)
with jax.set_mesh(mesh), sharding_hints(True):
    f_std = jax.jit(lambda q, kn, vn, pg, st, l, s: decode_attention(
        spec, q, kn, vn, pg, st, l, do_select=s), static_argnums=(6,))
    f_cop = jax.jit(lambda q, kn, vn, pg, st, l, s: decode_attention_coplace(
        spec, q, kn, vn, pg, st, l, do_select=s), static_argnums=(6,))
    for step in range(6):
        kk = jax.random.split(jax.random.fold_in(key, 100 + step), 3)
        qn = jax.random.normal(kk[0], (B, Hq, D))
        kn = jax.random.normal(kk[1], (B, Hkv, D))
        vn = jax.random.normal(kk[2], (B, Hkv, D))
        sel = step % 2 == 0  # exercise shared-selection reuse too
        o1, pg_s, st_s = f_std(qn, kn, vn, pg_s, st_s, L, sel)
        o2, pg_c, st_c = f_cop(qn, kn, vn, pg_c, st_c, L, sel)
        err = float(jnp.max(jnp.abs(o1 - o2)))
        assert err < 1e-4, (step, err)
        L = L + 1
print("COPLACE_EXACT")
"""


@pytest.mark.slow
def test_coplace_decode_exact_vs_standard():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", CODE], env=env,
                         capture_output=True, text=True, timeout=520)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "COPLACE_EXACT" in out.stdout
