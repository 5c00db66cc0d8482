"""Serving driver: lockstep batches or continuous batching.

Two workload modes:

``--workload uniform`` (the original driver): one fixed batch, every
request shares one prompt length and one generation length. Page
selection runs every ``share_window`` steps (the `select` compiled
variant), cheaper `reuse` steps in between. Greedy sampling.

``--workload ragged``: slot-based continuous batching via
``repro.serving.Engine``. Requests draw prompt lengths from a small set
of buckets and generation lengths from [gen-min, gen-max]; the engine
admits them into free slots of a fixed max-batch compiled shape,
retires finished slots without recompiling, and keeps per-slot
share-window selection cadence. ``--prefill-chunk N`` switches
admission from prefill-then-pack to chunked slot-resident prefill: at
most N prompt tokens per engine step stream directly into the slot's
(possibly sharded) caches, interleaved with decode — bounded
time-to-first-token on long prompts (docs/serving.md). Reports
throughput, batch occupancy, admissions/chunk counts, per-function jit
compile counts, and (with ``--report-balance``) the sched/balance
imbalance score of the final ragged batch on a 4x4 bank grid.

``--layout`` accepts any core/layouts registry entry:
``coplace_shmap`` runs the ragged workload under shard_map
memory-compute co-placement on a host-local mesh (pages sharded over the
'model' axis; paper §IV-B), ``interleave`` under GSPMD within-page token
striping (paper Fig 7b); ``--admission balanced`` adds the
balance-aware admission order (sched/balance.admission_score) for any
page-sharding layout.
``--attn-impl pallas`` swaps the attention bodies for the Pallas kernels
(kernels/ops.py dispatch; interpret mode on CPU) — including the
partial-attention + fused-combine pair inside the coplace_shmap decode.
The impl is fixed at engine construction, never switched per step.
``--rebalance retire|interval`` arms live slot migration
(sched/cost.py + sched/rebalance.py): the engine re-plans slot
placement when retirements skew the per-bank compute and moves cache
rows between slot indices without recompiling or changing any token
(docs/serving.md §Rebalancing).
``--decode-window w`` fuses up to ``w`` reuse steps between selection
boundaries into ONE dispatched lax.scan with in-scan sampling and
device-side retirement (docs/serving.md §Fused decode windows); token
traces stay bit-exact vs per-step dispatch.

CPU demo (reduced config):
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
      --reduced --prompt-len 96 --gen 32 --batch 2
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
      --reduced --workload ragged --requests 8 --max-batch 4 \
      --prompt-buckets 32,64 --gen-min 4 --gen-max 24
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
      --reduced --workload ragged --layout coplace_shmap \
      --admission balanced
"""
from __future__ import annotations

import argparse
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, reduced
from repro.launch.mesh import make_local_mesh
from repro.models import model as M
from repro.runtime import serve as serve_rt
from repro.runtime.compile_cache import enable_compile_cache


def generate(cfg, params, prompts, *, gen: int, capacity: int,
             mesh=None, layout="default", h2eal=True, greedy=True,
             attn_impl: str = "ref"):
    """Lockstep generation. prompts: (B, S) int32.
    Returns (tokens (B, gen), stats dict)."""
    import dataclasses

    if not h2eal:
        cfg = dataclasses.replace(
            cfg, h2eal=dataclasses.replace(cfg.h2eal, enabled=False))
    scfg = serve_rt.ServeConfig(capacity=capacity, layout=layout,
                                impl=attn_impl)
    b = prompts.shape[0]
    if mesh is not None:
        params_s = params
        state = jax.eval_shape(
            serve_rt.make_prefill(cfg, scfg), params, prompts)[1]
        prefill, dec_sel, dec_reuse = serve_rt.jit_serve_steps(
            cfg, scfg, mesh, params_s, state, b)
    else:
        prefill = jax.jit(serve_rt.make_prefill(cfg, scfg))
        dec_sel = jax.jit(serve_rt.make_decode_step(cfg, scfg,
                                                    do_select=True))
        dec_reuse = jax.jit(serve_rt.make_decode_step(cfg, scfg,
                                                      do_select=False))

    # the shard_map layouts read the ambient mesh while tracing
    with (jax.set_mesh(mesh) if mesh is not None
          else contextlib.nullcontext()):
        t0 = time.time()
        logits, state = prefill(params, prompts)
        logits.block_until_ready()
        t_prefill = time.time() - t0

        w = max(cfg.h2eal.share_window, 1)
        outs = []
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        t0 = time.time()
        for i in range(gen):
            outs.append(tok)
            fn = dec_sel if (i % w == 0) else dec_reuse
            logits, state = fn(params, state, tok)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        jax.block_until_ready(logits)
        t_decode = time.time() - t0
    stats = {
        "prefill_s": t_prefill,
        "decode_s": t_decode,
        "tokens_per_s": b * gen / t_decode if t_decode > 0 else float("inf"),
    }
    return jnp.stack(outs, axis=1), stats


def make_ragged_requests(cfg, *, n: int, prompt_buckets, gen_min: int,
                         gen_max: int, seed: int = 0):
    """Seeded ragged workload: bucketed prompt lengths, variable gen."""
    from repro.serving import Request

    rng = np.random.default_rng(seed)
    reqs = []
    for uid in range(n):
        s = int(rng.choice(prompt_buckets))
        g = int(rng.integers(gen_min, gen_max + 1))
        prompt = rng.integers(0, cfg.vocab_size, size=(s,)).astype(np.int32)
        reqs.append(Request(uid=uid, prompt=prompt, max_new=g))
    return reqs


def run_ragged(cfg, params, requests, *, max_batch: int, capacity: int,
               prompt_buckets, report_balance: bool = False,
               layout="default", admission: str = "fifo",
               attn_impl: str = "ref", prefill_chunk=None,
               rebalance: str = "off", decode_window=None):
    """Serve ``requests`` with the continuous-batching engine.

    ``layout`` is any core/layouts registry entry (e.g. "coplace_shmap"
    builds a host-local mesh with every device on the 'model' axis and
    runs the sharded partial-attention decode; "interleave" stripes
    within-page tokens over the 'data' axis under GSPMD);
    ``attn_impl="pallas"`` swaps the decode body for the Pallas kernels
    (interpret mode on CPU) — fixed at engine construction, never per
    step. ``prefill_chunk=N`` switches admission from prefill-then-pack
    to chunked slot-resident prefill (≤ N prompt tokens per engine step,
    interleaved with decode — docs/serving.md). ``rebalance`` arms the
    live slot-migration planner (sched/rebalance.py): "retire" re-plans
    when a retirement frees a slot, "interval" every
    ``rebalance_interval`` steps — token traces are bit-exact either way
    (docs/serving.md §Rebalancing). ``decode_window=w`` fuses up to w
    reuse steps per dispatch with device-side retirement
    (docs/serving.md §Fused decode windows).
    Returns (completions, stats dict)."""
    from repro.core import layouts as layoutlib
    from repro.serving import Engine

    if admission == "balanced" and \
            not layoutlib.get_layout(layout).shards_pages:
        raise ValueError(
            "--admission balanced scores per-device page load and only has "
            "an effect for layouts that shard pages (e.g. --layout "
            "coplace_shmap or interleave)")
    eng = Engine(cfg, params, max_batch=max_batch, capacity=capacity,
                 prompt_buckets=prompt_buckets, layout=layout,
                 admission=admission, impl=attn_impl,
                 prefill_chunk=prefill_chunk, rebalance=rebalance,
                 decode_window=decode_window)
    completions = eng.run(requests)
    s = eng.stats
    stats = {
        "wall_s": s.wall_s,
        "tokens_per_s": s.tokens_per_s,
        "decode_steps": s.decode_steps,
        "engine_steps": s.engine_steps,
        "select_steps": s.select_steps,
        "reuse_steps": s.reuse_steps,
        "admissions": s.admissions,
        "prefill_chunks": s.prefill_chunks,
        "occupancy": s.occupancy,
        "tokens_out": s.tokens_out,
        "admission_reorders": s.admission_reorders,
        "dispatches": s.dispatches,
        "steps_per_dispatch": s.steps_per_dispatch,
        "jit_cache": eng.jit_cache_sizes(),
    }
    if decode_window:
        stats["fused"] = {
            "decode_window": decode_window,
            "fused_windows": s.fused_windows,
            "fused_steps": s.fused_steps,
        }
    if rebalance != "off":
        stats["rebalance"] = {
            "trigger": rebalance,
            "checks": s.rebalance_checks,
            "rebalances": s.rebalances,
            "skipped": s.rebalance_skipped,
            "migrations": s.migrations,
            "migrated_tokens": s.migrated_tokens,
            "imbalance_pre": s.imbalance_pre,
            "imbalance_post": s.imbalance_post,
        }
    if report_balance:
        stats["balance"] = _balance_report(cfg, eng)
    return completions, stats


def _balance_report(cfg, eng):
    """Score the engine's current/last ragged batch with the paper's
    tiling + co-placement load split on a 4x4 bank grid, plus the sharded
    page-load view (device_page_loads), the whole-slot LPT placement
    (map_slots) the balanced admission policy optimizes against, and the
    rebalancer's own per-bank cost-model view (sched/cost.py via
    Engine.compute_loads) with its migration counters."""
    from repro.sched import (device_page_loads, grid_coords, imbalance,
                             load_imbalance, map_slots, ragged_loads,
                             slot_head_load, solve_tiling)

    ctx = [int(c) for c in eng.batch.lengths if c > 0]
    s = eng.stats
    base = {"admissions": s.admissions, "prefill_chunks": s.prefill_chunks}
    loads = eng.compute_loads()
    if loads:
        base["cost_loads"] = [round(x, 1) for x in loads]
        base["cost_imbalance"] = load_imbalance(loads)
    if eng.rebalance != "off":
        base.update(migrations=s.migrations, rebalances=s.rebalances,
                    imbalance_pre=s.imbalance_pre,
                    imbalance_post=s.imbalance_post)
    if not ctx:
        return base
    coords = grid_coords(4, 4)[: cfg.num_kv_heads]
    spec_nr = max(cfg.num_kv_heads
                  - round(cfg.num_kv_heads * cfg.h2eal.static_sparsity), 0)
    retr, stream = coords[:spec_nr], coords[spec_nr:]
    tiles, _ = solve_tiling(retr, stream)
    kinds = {c: ("retrieval" if c in retr else "streaming") for c in coords}
    u = ragged_loads(tiles, kinds, cfg.h2eal, ctx, balanced=False)
    b = ragged_loads(tiles, kinds, cfg.h2eal, ctx, balanced=True)
    n_sh = (int(eng.mesh.shape["model"])
            if eng.mesh is not None and "model" in eng.mesh.axis_names
            else 4)
    pages = device_page_loads(ctx, n_shards=max(n_sh, 1),
                              page_size=cfg.h2eal.page_size)
    lpt = map_slots([slot_head_load("retrieval", cfg.h2eal, c) for c in ctx],
                    max(n_sh, 1))
    return dict(base,
                imbalance_naive=imbalance(u),
                imbalance_coplaced=imbalance(b),
                page_load_imbalance=load_imbalance(pages),
                slot_lpt_imbalance=lpt.imbalance)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--workload", choices=["uniform", "ragged"],
                    default="uniform")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=96)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--h2eal", choices=["on", "off"], default="on")
    ap.add_argument("--seed", type=int, default=0)
    # ragged-workload knobs
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--prompt-buckets", default="32,64",
                    help="comma-separated allowed prompt lengths")
    ap.add_argument("--gen-min", type=int, default=4)
    ap.add_argument("--gen-max", type=int, default=24)
    ap.add_argument("--capacity", type=int, default=0,
                    help="cache capacity in tokens (0 = auto)")
    ap.add_argument("--report-balance", action="store_true")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked slot-resident prefill: feed at most N "
                         "prompt tokens per engine step, interleaved with "
                         "decode (bounded TTFT, no head-of-line blocking "
                         "on long prompts). 0 = prefill-then-pack "
                         "admission (docs/serving.md)")
    from repro.core.layouts import available_layouts
    ap.add_argument("--layout",
                    choices=["auto"] + list(available_layouts()),
                    default="default",
                    help="serve-cache layout (ragged workload), a "
                         "core/layouts registry entry ('auto' is a "
                         "deprecated alias for default). "
                         "coplace_shmap = shard_map co-placement, "
                         "interleave = GSPMD within-page token striping, "
                         "both on a host-local mesh")
    ap.add_argument("--admission", choices=["fifo", "balanced"],
                    default="fifo",
                    help="ragged admission order (balanced = per-device "
                         "page-load aware, sched/balance.py)")
    ap.add_argument("--rebalance", choices=["off", "retire", "interval"],
                    default="off",
                    help="live slot-migration trigger (sched/rebalance.py): "
                         "retire = re-plan when a retirement frees a slot, "
                         "interval = every 16 engine steps. Token traces "
                         "stay bit-exact (docs/serving.md §Rebalancing)")
    ap.add_argument("--decode-window", type=int, default=0,
                    help="fuse up to N reuse steps between selection "
                         "boundaries into one dispatched scan with "
                         "device-side retirement (0 = per-step dispatch; "
                         "docs/serving.md §Fused decode windows)")
    ap.add_argument("--share-window", type=int, default=0,
                    help="override cfg.h2eal.share_window (selection "
                         "cadence). The reduced configs pin it to 2, "
                         "leaving a single reuse step per window; widen "
                         "it to give --decode-window room to fuse")
    ap.add_argument("--attn-impl", choices=["ref", "pallas"], default="ref",
                    help="attention kernel impl (kernels/ops.py): ref = "
                         "pure-jnp oracle, pallas = Pallas kernels "
                         "(interpret mode on CPU). Fixed at engine "
                         "construction; see docs/serving.md")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.share_window:
        import dataclasses
        cfg = dataclasses.replace(
            cfg, h2eal=dataclasses.replace(cfg.h2eal,
                                           share_window=args.share_window))
    key = jax.random.PRNGKey(args.seed)
    params = M.init_params(cfg, key)

    if args.workload == "ragged":
        buckets = [int(x) for x in args.prompt_buckets.split(",")]
        capacity = args.capacity or (
            max(buckets) + args.gen_max + cfg.h2eal.page_size)
        reqs = make_ragged_requests(
            cfg, n=args.requests, prompt_buckets=buckets,
            gen_min=args.gen_min, gen_max=args.gen_max, seed=args.seed)
        completions, stats = run_ragged(
            cfg, params, reqs, max_batch=args.max_batch, capacity=capacity,
            prompt_buckets=buckets, report_balance=args.report_balance,
            layout=args.layout, admission=args.admission,
            attn_impl=args.attn_impl,
            prefill_chunk=args.prefill_chunk or None,
            rebalance=args.rebalance,
            decode_window=args.decode_window or None)
        print(f"[serve] arch={cfg.name} workload=ragged "
              f"layout={args.layout} admission={args.admission} "
              f"attn_impl={args.attn_impl} rebalance={args.rebalance} "
              f"prefill_chunk={args.prefill_chunk or 'packed'} "
              f"requests={len(completions)} steps={stats['decode_steps']} "
              f"occupancy={stats['occupancy']:.2f} "
              f"({stats['tokens_per_s']:.1f} tok/s)")
        print(f"[serve] select/reuse steps: {stats['select_steps']}/"
              f"{stats['reuse_steps']}; admissions/chunks: "
              f"{stats['admissions']}/{stats['prefill_chunks']}; "
              f"admission reorders: {stats['admission_reorders']}; "
              f"jit compiles: {stats['jit_cache']}")
        if "fused" in stats:
            fu = stats["fused"]
            print(f"[serve] fused decode windows: w={fu['decode_window']} "
                  f"windows={fu['fused_windows']} "
                  f"fused_steps={fu['fused_steps']} "
                  f"dispatches={stats['dispatches']} "
                  f"steps/dispatch={stats['steps_per_dispatch']:.2f}")
        if "rebalance" in stats:
            r = stats["rebalance"]
            print(f"[serve] rebalance trigger={r['trigger']} "
                  f"checks={r['checks']} applied={r['rebalances']} "
                  f"skipped={r['skipped']} migrations={r['migrations']} "
                  f"imbalance {r['imbalance_pre']:.3f} -> "
                  f"{r['imbalance_post']:.3f}")
        if "balance" in stats and stats["balance"]:
            bal = stats["balance"]
            if "imbalance_naive" in bal:
                print(f"[serve] bank imbalance naive="
                      f"{bal['imbalance_naive']:.2f} "
                      f"coplaced={bal['imbalance_coplaced']:.2f} "
                      f"page_load={bal['page_load_imbalance']:.2f} "
                      f"slot_lpt={bal['slot_lpt_imbalance']:.2f}")
            if "cost_imbalance" in bal:
                print(f"[serve] cost-model bank loads "
                      f"{bal['cost_loads']} "
                      f"(imbalance {bal['cost_imbalance']:.2f})")
        if completions:
            some = completions[min(completions)]
            print(f"[serve] sample tokens (uid {some.uid}): "
                  f"{some.tokens[:16]}")
        return stats

    prompts = jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size)
    toks, stats = generate(
        cfg, params, prompts, gen=args.gen,
        capacity=args.prompt_len + args.gen + cfg.h2eal.page_size,
        h2eal=args.h2eal == "on", attn_impl=args.attn_impl)
    print(f"[serve] arch={cfg.name} b={args.batch} "
          f"prefill={stats['prefill_s']:.2f}s "
          f"decode={stats['decode_s']:.2f}s "
          f"({stats['tokens_per_s']:.1f} tok/s)")
    print(f"[serve] sample tokens: {toks[0, :16].tolist()}")
    return stats


if __name__ == "__main__":
    main()
