"""Sharding rules + dry-run mini (subprocess with fake devices)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_py(code: str, devices: int = 16, timeout: int = 520):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_param_and_state_shardings_valid():
    """Every param/state leaf gets a sharding consistent with its shape on
    a (2 data x 2 model) mesh; device_put-compatible."""
    code = """
import jax, jax.numpy as jnp
from repro.configs import get_arch, reduced
from repro.models import model as M
from repro.runtime import sharding as shardlib, serve as serve_rt
from jax.sharding import AxisType
from repro.launch import specs as S

mesh = jax.make_mesh((2, 2), ("data", "model"),
                      axis_types=(AxisType.Auto,) * 2)
for name in ("smollm-360m", "qwen3-moe-235b-a22b", "zamba2-2.7b",
             "xlstm-125m"):
    cfg = reduced(get_arch(name))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    ps = shardlib.param_shardings(cfg, mesh, params)
    placed = jax.device_put(params, ps)          # would raise on mismatch
    scfg = serve_rt.ServeConfig(capacity=64)
    batch = jnp.zeros((4, 32), jnp.int32) if not cfg.embed_frontend_stub \
        else jnp.zeros((4, 32, cfg.d_model))
    state = jax.eval_shape(serve_rt.make_prefill(cfg, scfg), params, batch)[1]
    ss = shardlib.state_shardings(cfg, mesh, state, batch_size=4)
    jax.tree.map(lambda l, s: s.shard_shape(l.shape), state, ss)
    print(name, "ok")
print("ALL_OK")
"""
    out = _run_py(code, devices=4)
    assert "ALL_OK" in out


@pytest.mark.slow
def test_dryrun_single_cell_production_mesh():
    """Full production-mesh (16x16=256 fake devices) lower+compile of one
    assigned cell, plus a multi-pod (2x16x16) cell."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
from repro.launch.dryrun import lower_cell
r1 = lower_cell("smollm-360m", "decode_32k", multi_pod=False)
assert "error" not in r1 and r1["roofline"]["memory_s"] > 0
r2 = lower_cell("smollm-360m", "long_500k", multi_pod=True)
assert r2["chips"] == 512
print("DRYRUN_OK", r1["roofline"]["dominant"], r2["roofline"]["dominant"])
"""
    out = _run_py(code, devices=512)
    assert "DRYRUN_OK" in out


def test_hlo_stats_collective_parser():
    from repro.runtime import hlo_stats
    hlo = """
ENTRY %main () -> f32[8] {
  %x = f32[128,16]{1,0} parameter(0)
  %ag = f32[256,16]{1,0} all-gather(%x), replica_groups={}
  %ar = (f32[8]{0}, f32[8]{0}) all-reduce(%a, %b), to_apply=%sum
  %rs = bf16[64]{0} reduce-scatter(%y), dimensions={0}
  %ars = f32[4]{0} all-reduce-start(%c)
  %ard = f32[4]{0} all-reduce-done(%ars)
}
"""
    s = hlo_stats.collective_stats(hlo)
    assert s["all-gather"]["bytes"] == 256 * 16 * 4
    assert s["all-reduce"]["bytes"] == 8 * 4 * 2 + 4 * 4  # tuple + start
    assert s["all-reduce"]["count"] == 2                   # done skipped
    assert s["reduce-scatter"]["bytes"] == 64 * 2


def test_perfmodel_sanity():
    """Analytical byte model: H²EAL decode ≪ full-attention decode."""
    from repro.configs import get_arch
    from repro.configs.base import SHAPES
    from repro.runtime import perfmodel
    import dataclasses

    cfg = get_arch("llama2-7b")
    shape = SHAPES["decode_32k"]
    mesh = perfmodel.MeshModel(chips=256, data=16, model=16)
    sparse = perfmodel.decode_bytes(cfg, shape, mesh, layout="head")
    cfg_full = dataclasses.replace(
        cfg, h2eal=dataclasses.replace(cfg.h2eal, enabled=False))
    full = perfmodel.decode_bytes(cfg_full, shape, mesh, layout="head")
    ratio = full["total"] / sparse["total"]
    assert ratio > 3, f"sparse attention should cut decode bytes, r={ratio}"
