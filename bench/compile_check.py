#!/usr/bin/env python3
"""Compile each cell's decode (select and reuse), prefill-chunk and
sample programs at their real sizes for a described TPU v5e, without a
chip: what the chip's compiler refuses here costs no chip time.

    JAX_PLATFORMS=cpu python3 bench/compile_check.py [cell ...]

Builds the same step functions the engine jits (runtime/serve.py) with
the cell's settings, from shapes only (``jax.eval_shape``; no weights or
caches are allocated), and prints each program's compile time and
``memory_analysis()``. A compile that passes is not a chip run.
"""
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def compile_cell(name, one_chip):
    import jax
    import jax.numpy as jnp

    from harness import spec
    from repro.models import model as M
    from repro.runtime import serve as serve_rt

    cell = spec.load_cell(name)
    conf, e = cell.config, cell.engine
    ref = spec.load_module("reference", conf["reference"])
    prog = spec.load_module("programs", conf["program"])
    cfg = prog.arch_config(conf)
    lo, hi = ref.seed_words(0)
    params = jax.eval_shape(lambda a, b: prog.params(conf, ref.make_weights(
        conf, a, b)), lo, hi)
    b, cap, chunk = e["max_batch"], e["capacity"], e["prefill_chunk"]
    probe = jax.ShapeDtypeStruct((b, chunk), jnp.int32)
    state = jax.eval_shape(lambda p, t: M.prefill(cfg, p, t, capacity=cap),
                           params, probe)[1]
    state["length"] = jax.ShapeDtypeStruct((b,), jnp.int32)
    put = lambda t: jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), t)
    params, state = put(params), put(state)
    vec = lambda dt: jax.ShapeDtypeStruct((b,), dt, sharding=one_chip)
    scfg = serve_rt.ServeConfig(capacity=cap, layout="default",
                                impl=e["impl"])
    progs = {
        "decode_select": (jax.jit(serve_rt.make_ragged_decode_step(
            cfg, scfg, do_select=True), donate_argnums=(1,)),
            (params, state, vec(jnp.int32), vec(bool), vec(bool))),
        "decode_reuse": (jax.jit(serve_rt.make_ragged_decode_step(
            cfg, scfg, do_select=False), donate_argnums=(1,)),
            (params, state, vec(jnp.int32), vec(bool))),
        "chunk_step": (jax.jit(serve_rt.make_prefill_chunk_step(
            cfg, scfg, chunk=chunk), donate_argnums=(1,)),
            (params, state,
             jax.ShapeDtypeStruct((b, chunk), jnp.int32, sharding=one_chip),
             vec(jnp.int32), vec(bool))),
    }
    nbytes = lambda t: sum(x.size * x.dtype.itemsize
                           for x in jax.tree.leaves(t))
    print(f"{name}: weights {nbytes(params) / 1e9:.3f} GB, serve state "
          f"{nbytes(state) / 1e9:.3f} GB ({nbytes(state) / b / 1e9:.4f} GB "
          f"a slot)", flush=True)
    for pname, (fn, args) in progs.items():
        t0 = time.time()
        compiled = fn.lower(*args).compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        print(f"  {pname}: compiled in {time.time() - t0:.1f} s, "
              f"tpu_custom_call {'tpu_custom_call' in text}, "
              f"temp {mem.temp_size_in_bytes / 1e9:.3f} GB, "
              f"args {mem.argument_size_in_bytes / 1e9:.3f} GB, "
              f"out {mem.output_size_in_bytes / 1e9:.3f} GB, "
              f"alias {mem.alias_size_in_bytes / 1e9:.3f} GB", flush=True)


def main():
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness import spec
    from repro.kernels import ops

    # the kernels ask the default backend (here the CPU) whether to run
    # interpreted; the programs compiled here are for the TPU
    ops.interpret_mode = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    names = sys.argv[1:] or [w["name"] for w in spec.load_json(
        spec.ROOT / "BENCHMARK.json")["workloads"]]
    for name in names:
        compile_cell(name, one_chip)


if __name__ == "__main__":
    main()
