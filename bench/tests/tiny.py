"""A toy cell for the CPU tests: a 2-layer Llama-style block whose
contexts reach past the select budget, so pages are dropped."""
import time

import jax
import jax.numpy as jnp

from harness import runner, spec

DATA = spec.BENCH / "tests" / "data"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
# the f32 program against the f32 reference differs by float
# reassociation alone; every fault below moves a gap by O(0.1) or more
LIMIT = 1e-3


def cell(kind: str, impl: str = "ref", limit: float = LIMIT,
         requests=2):
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    return spec.Cell(
        name=f"tiny_{kind}", chips=1,
        config=spec.load_json(DATA / "tiny.json"),
        traffic=spec.load_json(DATA / f"tiny_{kind}.json"),
        settings={"engine": {"max_batch": 4, "capacity": 256,
                             "prefill_chunk": 64, "impl": impl},
                  "check": {"requests": requests,
                            "logit_gap_mean_limit": limit}},
        end_to_end=list(bench["end_to_end"]),
        per_layer=list(bench["per_layer"]))


def run(c, *, seed=2 ** 31 + 11, seconds=2.0, f32=True, **kw):
    """One run on the CPU. ``f32`` serves float32 weights, so that the
    program and the reference differ by rounding only."""
    orig = runner._params_fn
    if f32:
        def params_fn(conf, ref, prog):
            f = orig(conf, ref, prog)
            return lambda lo, hi: jax.tree.map(
                lambda x: x.astype(jnp.float32), f(lo, hi))
        runner._params_fn = params_fn
    try:
        return runner.run(c, seed=seed, seconds=seconds, trace=False,
                          devices=jax.devices()[:1], peaks=PEAKS,
                          t_start=time.perf_counter(), **kw)
    finally:
        runner._params_fn = orig
