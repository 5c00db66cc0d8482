import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this lowers the real step function (train_step for training
shapes, prefill/serve_step for inference shapes) with the production
shardings, compiles it, and records memory/cost/collective statistics for
the roofline analysis (EXPERIMENTS.md §Dry-run / §Roofline).

Usage:
  python -m repro.launch.dryrun --arch smollm-360m --shape train_4k
  python -m repro.launch.dryrun --arch all --shape all --multi-pod both \
      --out results/dryrun.json
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED, SHAPES, get_arch
from repro.launch import specs as S
from repro.launch.mesh import make_production_mesh
from repro.runtime import hlo_stats
from repro.runtime import serve as serve_rt
from repro.runtime import sharding as shardlib
from repro.runtime import train as train_rt

# TPU v5e hardware model (roofline constants)
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link

# The dry-run lowers in f32: XLA:CPU float-normalizes bf16 compute into
# convert-wrapped f32 (absent on TPU where bf16 is MXU-native), which
# pollutes the byte/collective model with 3x phantom traffic. Lowering f32
# end-to-end produces a convert-free module; the production wire format is
# bf16, so data-proportional terms are scaled by 0.5.
DRYRUN_DTYPE = "float32"
BF16_WIRE_FACTOR = 0.5


def _train_tcfg(cfg):
    # MoE dispatch buffers, dense-72B activations, and the mamba2 chunk
    # decay tensors all need microbatching at global_batch 256
    mb = 8 if (cfg.moe.enabled or cfg.d_model >= 6144
               or cfg.family == "hybrid") else 1
    return train_rt.TrainConfig(microbatches=mb, remat=True,
                                grad_dtype="bf16")


def _round_capacity(cfg, capacity: int, mesh) -> int:
    """Round page capacity up so the page dim divides the model axis
    (required by the coplace_shmap layout; harmless otherwise)."""
    p = max(cfg.h2eal.page_size, 1)
    m = mesh.shape["model"]
    pages = -(-capacity // p)
    pages = -(-pages // m) * m
    return pages * p


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               layout: str | None = None, h2eal_on: bool = True):
    """Lower + compile one cell; returns stats dict."""
    import dataclasses

    from repro.configs.base import H2ealConfig

    cfg = get_arch(arch)
    if not h2eal_on:
        cfg = dataclasses.replace(
            cfg, h2eal=dataclasses.replace(cfg.h2eal, enabled=False))
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()

    from repro.runtime.hints import set_sp_residual, sharding_hints
    # per-workload strategy selection (each measured; EXPERIMENTS.md §Perf):
    #  * sequence-parallel residual/attention: always for inference
    #    (forward-only — SP prefill is 30-60x cheaper); for training only
    #    when heads don't divide the model axis (otherwise dk/dv
    #    partial-sums in backward cost more than GSPMD's native TP plan)
    #  * ZeRO-3 use-constraints: off for MoE training (expert dispatch +
    #    per-microbatch regathers underperform GSPMD's default plan there)
    set_sp_residual(shape.kind != "train"
                    or cfg.num_heads % mesh.shape["model"] != 0)
    hints_on = not (shape.kind == "train" and cfg.moe.enabled)
    with jax.set_mesh(mesh), sharding_hints(hints_on):
        if shape.kind == "train":
            params = S.param_specs(cfg, dtype=jnp.float32)
            batch = S.train_specs(cfg, shape)
            tcfg = _train_tcfg(cfg)
            opt = {
                "mu": jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32),
                    params),
                "nu": jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32),
                    params),
                "count": jax.ShapeDtypeStruct((), jnp.int32),
            }
            step_fn = train_rt.jit_train_step(
                cfg, tcfg, mesh, params, opt, shape.global_batch)
            lowered = step_fn.lower(
                params, opt, batch, jax.ShapeDtypeStruct((), jnp.int32))
        elif shape.kind == "prefill":
            params = S.param_specs(cfg, dtype=jnp.float32)
            batch = S.prefill_specs(cfg, shape, dtype=jnp.float32)
            scfg = serve_rt.ServeConfig(
                capacity=_round_capacity(cfg, shape.seq_len + 64, mesh),
                layout=layout)
            state = jax.eval_shape(
                serve_rt.make_prefill(cfg, scfg), params, batch)[1]
            prefill, _, _ = serve_rt.jit_serve_steps(
                cfg, scfg, mesh, params, state, shape.global_batch)
            lowered = prefill.lower(params, batch)
        else:  # decode
            params = S.param_specs(cfg, dtype=jnp.float32)
            batch = S.prefill_specs(cfg, shape, dtype=jnp.float32)
            scfg = serve_rt.ServeConfig(
                capacity=_round_capacity(cfg, shape.seq_len + 64, mesh),
                layout=layout)
            state = jax.eval_shape(
                serve_rt.make_prefill(cfg, scfg), params, batch)[1]
            _, dec_sel, _ = serve_rt.jit_serve_steps(
                cfg, scfg, mesh, params, state, shape.global_batch)
            token = S.decode_token_specs(cfg, shape, dtype=jnp.float32)
            lowered = dec_sel.lower(params, state, token)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    hlo = compiled.as_text()
    # trip-corrected accounting: XLA's cost_analysis counts while bodies
    # ONCE; our programs scan over layers/microbatches, so dot FLOPs and
    # collectives are re-counted from the HLO with known_trip_count
    # multiplication (hlo_stats.computation_multiplicities).
    coll = hlo_stats.collective_stats_with_trips(hlo)
    cost = hlo_stats.cost_stats(compiled)  # raw (uncorrected) diagnostics
    cost["flops_raw_body_once"] = cost.get("flops", 0.0)
    cost["flops"] = hlo_stats.flops_with_trips(hlo)
    mem = hlo_stats.memory_stats(compiled)
    chips = mesh.devices.size

    # roofline terms (seconds). all per-device (post-SPMD);
    # data-proportional terms scaled to the bf16 production wire format.
    compute_s = cost.get("flops", 0.0) / PEAK_FLOPS

    # memory term: analytical byte model (see runtime/perfmodel.py for why
    # XLA's gather/fusion byte charging is unusable for paged decode);
    # the raw HLO number stays in cost["bytes"] as a diagnostic.
    from repro.runtime import perfmodel
    mm = perfmodel.MeshModel(
        chips=int(chips),
        data=mesh.shape["data"] * mesh.shape.get("pod", 1),
        model=mesh.shape["model"])
    eff_layout = layout or (
        "interleave" if shape.global_batch < mm.data else "head")
    model_bytes = perfmodel.cell_bytes(
        cfg, shape, mm, layout=eff_layout,
        microbatches=_train_tcfg(cfg).microbatches)
    cost["bytes_model"] = model_bytes["total"]
    memory_s = model_bytes["total"] / HBM_BW
    coll_s = coll.get("total_bytes", 0) * BF16_WIRE_FACTOR / ICI_BW
    model_flops = 6 * cfg.active_param_count() * (
        shape.global_batch * shape.seq_len if shape.kind == "train"
        else (shape.global_batch if shape.kind == "decode"
              else shape.global_batch * shape.seq_len))
    if shape.kind == "train":
        model_flops = model_flops  # fwd+bwd ≈ 6ND already
    else:
        model_flops = model_flops / 3  # inference: 2ND
    return {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind,
        "chips": int(chips),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "cost": cost,
        "collectives": coll,
        "memory": mem,
        "roofline": {
            "compute_s": compute_s,
            "memory_s": memory_s,
            "collective_s": coll_s,
            "dominant": max(
                (("compute", compute_s), ("memory", memory_s),
                 ("collective", coll_s)), key=lambda kv: kv[1])[0],
        },
        "model_flops_global": model_flops,
        "hlo_flops_global": cost.get("flops", 0.0) * chips,
        "bytes_breakdown": {k: float(v) for k, v in model_bytes.items()},
        "layout": eff_layout if shape.kind == "decode" else None,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", choices=["on", "off", "both"],
                    default="off")
    ap.add_argument("--layout", default=None,
                    choices=[None, "head", "coplace", "interleave"])
    ap.add_argument("--h2eal", choices=["on", "off"], default="on")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = list(ASSIGNED) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    pods = {"on": [True], "off": [False], "both": [False, True]}[
        args.multi_pod]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                tag = f"{arch} × {shape} × {'2x16x16' if mp else '16x16'}"
                try:
                    r = lower_cell(arch, shape, multi_pod=mp,
                                   layout=args.layout,
                                   h2eal_on=args.h2eal == "on")
                    rl = r["roofline"]
                    print(f"[ok] {tag}: compile={r['compile_s']}s "
                          f"compute={rl['compute_s']:.3e}s "
                          f"mem={rl['memory_s']:.3e}s "
                          f"coll={rl['collective_s']:.3e}s "
                          f"dominant={rl['dominant']}", flush=True)
                    results.append(r)
                except Exception as e:
                    print(f"[FAIL] {tag}: {e}", flush=True)
                    traceback.print_exc()
                    results.append({"arch": arch, "shape": shape,
                                    "mesh": "2x16x16" if mp else "16x16",
                                    "error": str(e)})
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")
    n_fail = sum(1 for r in results if "error" in r)
    print(f"{len(results) - n_fail}/{len(results)} cells OK")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
