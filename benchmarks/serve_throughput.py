"""Continuous batching vs lockstep batching at equal token budget.

Workload: N requests with bucketed prompt lengths and ragged generation
lengths (seeded). Two ways to serve it:

  lockstep — the pre-engine driver: group requests into fixed batches of
             ``max_batch``, pad prompts to the largest bucket, run every
             group for its LONGEST member's generation length (finished
             slots keep burning decode steps).
  ragged   — repro.serving.Engine: slots retire as soon as their request
             finishes and are immediately backfilled from the queue.

Both serve exactly the same requests (equal useful-token budget), so
tok/s is directly comparable. The engine also must not recompile after
warmup: jit cache sizes are captured post-warmup and asserted stable
through the measured phase.

``--layout`` takes a comma-separated list of core/layouts registry
entries and produces one ragged row per layout (page-sharding layouts
get balance-aware admission automatically): ``coplace_shmap`` runs the
engine under shard_map memory-compute co-placement (pages sharded over
the mesh 'model' axis, paper §IV-B), ``interleave`` under GSPMD
within-page token striping (paper Fig 7b) — the multi-device rows. The
no-recompile check applies to every row. Force a multi-device CPU run
with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

``--prefill-chunk N`` adds, per layout, a chunked-prefill engine row
(admission interleaved with decode, ≤ N prompt tokens per engine step
through the layout protocol) with a ``tokens_match_packed`` check
against the prefill-then-pack row — same admission trace, token-exact
off argmax ties.

``--arrival poisson`` runs the bursty-arrival LATENCY harness instead
of the batch drain: seeded Poisson arrivals with periodic max-bucket
long prompts, engine driven step-by-step with a device sync so the
per-step timestamps are honest. Reports p50/p99 time-to-first-token and
inter-token latency for packed vs chunked admission, plus
``decode_tokens_during_long_prefill`` — the step-exact no-head-of-line
metric (tokens other slots emitted while a long prompt was being
admitted: always 0 for the atomic prefill-then-pack, > 0 for chunked).
On CPU the wall-clock percentiles are dispatch-noise bound (correctness
rows, like the interpret-mode pallas rows); the step-exact metric is
the portable signal. See EXPERIMENTS.md §Serving experiments.

``--json PATH`` additionally writes the machine-readable row list
(tok/s per layout x impl x admission mode, occupancy, recompile flags,
latency percentiles) — the BENCH_serve.json artifact; scripts/ci.sh
smokes this invocation so the perf trajectory is captured on every full
CI run.

``--rebalance`` adds the live slot-migration row pair: a churn workload
(ragged prompts AND ragged budgets) served with rebalance off vs the
retire-triggered planner (sched/cost.py + sched/rebalance.py). The
rebalanced row must reproduce the off row token-for-token
(``tokens_match_norebalance``) and strictly reduce the cost-model bank
imbalance at the rebalance checks (``load_imbalance_pre`` vs
``load_imbalance_post`` — the bench_bands.json imbalance gate), with
the migration NoC traffic priced by hbsim.rebalance_overhead.

``--decode-window w`` adds the fused decode-window row trio (PR 10) on
a widened share window: a lockstep baseline for that config, a per-step
engine row, and the ``Engine(decode_window=w)`` row whose reuse steps
between selection boundaries run as ONE dispatched scan — with a
``tokens_match_unfused`` exact check against the per-step row, the
dispatch counters (``dispatches``, ``steps_per_dispatch``), and the
fused >= per-step tokens/s ratio gated in bench_bands.json.

``--attn-impl pallas`` adds the ref-vs-pallas comparison row: the same
workload is served a second time with the Pallas attention kernels
(partial attention + fused combine under coplace_shmap; interpret mode
on CPU, so the CPU row is a correctness row, not a perf row — see
EXPERIMENTS.md). It reports tok/s for both impls, whether the greedy
token traces match (exact-tie caveat in EXPERIMENTS.md), and the
pallas engine's own no-recompile check.

Run: PYTHONPATH=src python benchmarks/serve_throughput.py
     PYTHONPATH=src python benchmarks/serve_throughput.py \
         --layout coplace_shmap --attn-impl pallas
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def build_requests(cfg, *, n, buckets, gen_min, gen_max, seed):
    from repro.launch.serve import make_ragged_requests

    return make_ragged_requests(cfg, n=n, prompt_buckets=buckets,
                                gen_min=gen_min, gen_max=gen_max, seed=seed)


def make_lockstep_runner(cfg, params, *, capacity):
    """Lockstep server with the step triple compiled ONCE and reused
    across groups (same steady-state compile budget as the engine)."""
    from repro.runtime import serve as serve_rt

    scfg = serve_rt.ServeConfig(capacity=capacity)
    prefill = jax.jit(serve_rt.make_prefill(cfg, scfg))
    dec_sel = jax.jit(serve_rt.make_decode_step(cfg, scfg, do_select=True))
    dec_reuse = jax.jit(serve_rt.make_decode_step(cfg, scfg,
                                                  do_select=False))
    w = max(cfg.h2eal.share_window, 1)

    def serve(requests, *, max_batch, pad_to):
        t0 = time.time()
        useful = 0
        steps = 0
        for i in range(0, len(requests), max_batch):
            group = requests[i:i + max_batch]
            gen = max(r.max_new for r in group)
            prompts = np.zeros((max_batch, pad_to), np.int32)
            for j, r in enumerate(group):
                prompts[j, :len(r.prompt)] = r.prompt
                prompts[j, len(r.prompt):] = r.prompt[-1]  # repeat-pad
            logits, state = prefill(params, jnp.asarray(prompts))
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            for s in range(gen):
                fn = dec_sel if (s % w == 0) else dec_reuse
                logits, state = fn(params, state, tok)
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            jax.block_until_ready(logits)
            useful += sum(r.max_new for r in group)
            steps += gen
        dt = time.time() - t0
        return {"useful_tokens": useful, "decode_steps": steps,
                "wall_s": dt, "tokens_per_s": useful / dt}

    return serve


def run_engine(cfg, params, requests, *, max_batch, capacity, buckets,
               reps=1, layout="default", admission="fifo", attn_impl="ref",
               prefill_chunk=None, hot_pages=None, spec_tokens=None,
               draft="ngram", sampling=None, rebalance="off",
               warm_requests=None, decode_window=None):
    from repro.serving import Engine, Request

    eng = Engine(cfg, params, max_batch=max_batch, capacity=capacity,
                 prompt_buckets=buckets, layout=layout, admission=admission,
                 impl=attn_impl, prefill_chunk=prefill_chunk,
                 hot_pages=hot_pages, spec_tokens=spec_tokens, draft=draft,
                 rebalance=rebalance, decode_window=decode_window)
    # sampling=(temperature, top_p) stamps every measured request; the
    # per-request RNG key is owned by (seed, uid), so the same request
    # list produces the same stochastic trace on ANY engine configuration
    # (the losslessness invariant the spec rows assert)
    temp, topp = sampling if sampling else (0.0, 1.0)

    def stamp(rs):
        return [dataclass_copy(r, temperature=temp, top_p=topp)
                for r in rs]

    if warm_requests is not None:
        # replay a full workload as warmup (uids offset out of the
        # measured range): the rebalance rows need a warmup that
        # actually MIGRATES, or the migrate jit would compile inside
        # the measured phase and trip the no-recompile check
        warm = [dataclass_copy(r, uid=10_000 + r.uid, temperature=temp,
                               top_p=topp) for r in warm_requests]
    else:
        # warmup: touch every prompt bucket and both decode variants
        warm = [Request(uid=10_000 + i, prompt=np.zeros((b,), np.int32),
                        max_new=cfg.h2eal.share_window + 2,
                        temperature=temp, top_p=topp)
                for i, b in enumerate(buckets)]
    eng.run(warm)
    warm_sizes = eng.jit_cache_sizes()

    best = None
    for _ in range(max(reps, 1)):
        eng.reset_metrics()
        t0 = time.time()
        completions = eng.run(stamp(requests))
        dt = time.time() - t0
        if best is None or dt < best[0]:
            best = (dt, completions, dataclass_copy(eng.stats))
    dt, completions, s = best
    sizes = eng.jit_cache_sizes()
    recompiled = any(sizes[k] != warm_sizes[k] for k in sizes
                     if sizes[k] >= 0)
    useful = sum(len(c.tokens) for c in completions.values())
    out = {"useful_tokens": useful, "decode_steps": s.decode_steps,
           "wall_s": dt, "tokens_per_s": useful / dt,
           "steps_per_s": s.decode_steps / dt,
           "tokens_per_step": useful / max(s.decode_steps, 1),
           # dispatch accounting (PR 10): decode_steps stops doubling as
           # the dispatch count once windows fuse — report the logical
           # step rate and the directly-observable dispatch reduction
           "engine_steps": s.engine_steps,
           "engine_steps_per_s": s.engine_steps / dt,
           "dispatches": s.dispatches,
           "steps_per_dispatch": s.decode_steps / max(s.dispatches, 1),
           "occupancy": s.occupancy, "recompiled_after_warmup": recompiled,
           "jit_cache": sizes,
           "tokens": {uid: list(c.tokens)
                      for uid, c in completions.items()}}
    if decode_window:
        out.update({
            "decode_window": decode_window,
            "fused_windows": s.fused_windows,
            "fused_steps": s.fused_steps,
        })
    if sampling:
        out["sampling"] = {"temperature": temp, "top_p": topp}
    if spec_tokens:
        out.update({
            "spec_tokens": spec_tokens,
            "draft": getattr(eng.draft, "name", str(draft)),
            "spec_steps": s.spec_steps,
            "spec_drafted": s.spec_drafted,
            "spec_accepted": s.spec_accepted,
            "mean_accepted_len": s.mean_accepted_len,
        })
    if hot_pages is not None:
        out.update({
            "hot_pages": hot_pages,
            "tier_hits": s.tier_hits, "tier_misses": s.tier_misses,
            "tier_spills": s.tier_spills, "tier_fills": s.tier_fills,
            "tier_prefetch": s.tier_prefetch,
            "tier_hit_rate": s.tier_hit_rate,
            # batched-transfer accounting (PR 10): one batched fill +
            # one batched spill per refresh plan
            "tier_fill_batches": s.tier_fill_batches,
            "tier_spill_batches": s.tier_spill_batches,
            "tier_gather_batches": s.tier_gather_batches,
            "tier_batch_pages_max": s.tier_batch_pages_max,
            "tier_fill_batch_mean": s.tier_fill_batch_mean,
            "tier_spill_batch_mean": s.tier_spill_batch_mean,
        })
    if rebalance != "off":
        out.update({
            "rebalance": rebalance,
            "rebalance_checks": s.rebalance_checks,
            "rebalances": s.rebalances,
            "migrations": s.migrations,
            "migrated_tokens": s.migrated_tokens,
            "load_imbalance_pre": s.imbalance_pre,
            "load_imbalance_post": s.imbalance_post,
        })
    return out


def dataclass_copy(x, **changes):
    import dataclasses
    return dataclasses.replace(x, **changes)


def run_latency(cfg, params, *, requests, max_batch, capacity, buckets,
                gen_min, gen_max, seed, layout="default", admission="fifo",
                prefill_chunk=None, arrival_rate=0.5, long_every=3,
                long_len=None):
    """Bursty-arrival latency run: p50/p99 time-to-first-token and
    inter-token latency under Poisson arrivals with periodic max-bucket
    long prompts (the head-of-line blocking scenario chunked prefill
    targets).

    Requests arrive by a seeded Poisson process (``arrival_rate``
    requests per engine step, exponential inter-arrivals); every
    ``long_every``-th request is a max-bucket prompt, the rest draw from
    the smaller buckets. The engine is driven step-by-step with a device
    sync per step so the per-step timestamps are honest — this is a
    latency harness, not a throughput number (the sync serializes
    dispatch). TTFT = first-token wall time minus submit wall time; ITL
    = wall time between a request's consecutive tokens. With
    prefill-then-pack admission the whole prompt prefills inside one
    loop iteration, so a long arrival stalls every concurrent decode
    (the ITL tail); chunked prefill bounds the stall by one chunk.
    """
    from repro.launch.serve import make_ragged_requests
    from repro.serving import Engine, Request

    # the long-prompt bucket must dwarf a decode step for the
    # head-of-line stall to be visible above dispatch noise
    long_len = long_len or 8 * max(buckets)
    capacity = max(capacity, long_len + gen_max + cfg.h2eal.page_size)
    all_buckets = sorted(set(buckets) | {long_len})
    eng = Engine(cfg, params, max_batch=max_batch, capacity=capacity,
                 prompt_buckets=all_buckets, layout=layout,
                 admission=admission, prefill_chunk=prefill_chunk)
    warm = [Request(uid=10_000 + i, prompt=np.zeros((b,), np.int32),
                    max_new=cfg.h2eal.share_window + 2)
            for i, b in enumerate(all_buckets)]
    eng.run(warm)
    warm_sizes = eng.jit_cache_sizes()
    eng.reset_metrics()

    rng = np.random.default_rng(seed)
    reqs = make_ragged_requests(cfg, n=requests, prompt_buckets=buckets,
                                gen_min=gen_min, gen_max=gen_max, seed=seed)
    for r in reqs[long_every - 1::long_every]:   # bursty long prompts
        r.prompt = rng.integers(0, cfg.vocab_size,
                                size=(long_len,)).astype(np.int32)
    arrive = np.cumsum(rng.exponential(1.0 / arrival_rate, size=requests))
    pending = list(zip(arrive, reqs))

    t0 = time.time()
    times = [t0]                 # times[k] = wall clock after engine step k
    submit_t = {}
    while pending or eng.busy():
        step_no = eng.stats.engine_steps
        while pending and pending[0][0] <= step_no:
            _, r = pending.pop(0)
            submit_t[r.uid] = time.time()
            eng.submit(r)
        if not eng.busy():
            if not pending:
                break
            _, r = pending.pop(0)    # idle: fast-forward the arrival clock
            submit_t[r.uid] = time.time()
            eng.submit(r)
        if eng.poll():
            eng.sync()
            times.append(time.time())  # times[k] = wall after engine step k
    eng.finalize()

    ttft, itl = [], []
    for comp in eng.completions.values():
        if comp.uid not in submit_t:
            continue
        t_first = times[min(comp.first_token_step, len(times) - 1)]
        ttft.append(t_first - submit_t[comp.uid])
        prev = t_first
        for es in eng.token_engine_steps(comp):
            t_tok = times[min(es, len(times) - 1)]
            itl.append(t_tok - prev)
            prev = t_tok
    # the structural no-head-of-line claim, at step granularity (exact on
    # any host, unlike the wall-clock percentiles which are dispatch-noise
    # bound on a CPU toy config): tokens OTHER slots emitted between a
    # long request's admission and its first token. Prefill-then-pack is
    # an atomic admission — always 0; chunked admission keeps decoding.
    during = []
    longs = [c for c in eng.completions.values()
             if c.uid in submit_t and c.prompt_len == long_len]
    for lc in longs:
        n = sum(
            1
            for c in eng.completions.values()
            if c.uid != lc.uid and c.uid in submit_t
            for es in eng.token_engine_steps(c)
            if lc.admitted_engine_step < es < lc.first_token_step)
        during.append(n)
    pct = lambda xs, q: float(np.percentile(xs, q)) if xs else 0.0
    s = eng.stats
    recompiled = any(a != b for a, b in zip(eng.jit_cache_sizes().values(),
                                            warm_sizes.values()))
    return {
        "useful_tokens": s.tokens_out, "decode_steps": s.decode_steps,
        "engine_steps": s.engine_steps, "prefill_chunks": s.prefill_chunks,
        "admissions": s.admissions,
        "wall_s": times[-1] - t0,
        "tokens_per_s": s.tokens_out / max(times[-1] - t0, 1e-9),
        "tokens_per_step": s.tokens_out / max(s.decode_steps, 1),
        "occupancy": s.occupancy,
        "recompiled_after_warmup": recompiled,
        "jit_cache": eng.jit_cache_sizes(),
        "ttft_p50_s": pct(ttft, 50), "ttft_p99_s": pct(ttft, 99),
        "itl_p50_s": pct(itl, 50), "itl_p99_s": pct(itl, 99),
        "long_len": long_len,
        "decode_tokens_during_long_prefill":
            float(np.mean(during)) if during else 0.0,
    }


def _row(mode, layout, impl, r, *, lock=None, extra=None):
    """One machine-readable benchmark row (the --json payload unit)."""
    row = {"mode": mode, "layout": layout, "impl": impl,
           "tokens_per_s": r["tokens_per_s"],
           "tokens_per_step": r["tokens_per_step"],
           "decode_steps": r["decode_steps"],
           "useful_tokens": r["useful_tokens"],
           "wall_s": r["wall_s"]}
    if "occupancy" in r:
        row["occupancy"] = r["occupancy"]
    if "recompiled_after_warmup" in r:
        row["recompiled_after_warmup"] = r["recompiled_after_warmup"]
        row["jit_cache"] = r["jit_cache"]
    # split-rate + sampling/speculation fields (PR 8): tokens_per_s and
    # steps_per_s coincide per slot without speculation; a verify step
    # emits up to k tokens per slot, so spec rows report both
    for key in ("steps_per_s", "sampling", "spec_tokens", "draft",
                "spec_steps", "spec_drafted", "spec_accepted",
                "mean_accepted_len",
                # dispatch accounting + fused decode windows (PR 10)
                "engine_steps", "engine_steps_per_s", "dispatches",
                "steps_per_dispatch", "decode_window", "fused_windows",
                "fused_steps"):
        if key in r:
            row[key] = r[key]
    if lock is not None:
        row["speedup_vs_lockstep"] = r["tokens_per_s"] / lock["tokens_per_s"]
    if extra:
        row.update(extra)
    return row


def run(csv: bool = True, *, requests=24, max_batch=4, gen_min=2,
        gen_max=40, seed=0, reps=3, layout="default", layouts=None,
        attn_impl=None, json_path=None, prefill_chunk=None,
        arrival="batch", arrival_rate=0.5, tiered_hot_pages=None,
        spec_tokens=None, sampling=None, rebalance=False,
        decode_window=None):
    """Lockstep vs ragged at equal token budget, per layout (x impl).

    ``layouts`` is an iterable of core/layouts registry names (default:
    just the default layout; the deprecated single ``layout=`` alias is
    folded in). ``prefill_chunk=N`` adds, per layout, a chunked-prefill
    engine row (admission interleaved with decode, N tokens/step) next
    to the prefill-then-pack row, with a ``tokens_match_packed`` check.
    ``arrival="poisson"`` additionally runs the bursty-arrival LATENCY
    harness (``run_latency``) per layout — packed vs chunked p50/p99
    TTFT and inter-token latency rows. ``json_path`` writes the
    machine-readable row list (tok/s per layout x impl x admission mode,
    occupancy, recompile flags, latency percentiles) — the
    BENCH_serve.json artifact scripts/ci.sh smokes.

    ``spec_tokens=k`` adds, per layout, a speculative-decode engine row
    (self-drafted ngram prompt-lookup, one chunked verify forward per
    step) with a ``tokens_match_nonspec`` flag against the non-spec row
    — the coupled rejection sampler makes the trace EXACTLY the
    non-speculative one, greedy or stochastic — plus the dedicated
    ngram-friendly workload pair (constant-token prompts, widened share
    window so the selection-refresh boundary doesn't clamp acceptance)
    that carries the speculative >= non-spec tokens/s ratio gate.
    ``sampling=(temperature, top_p)`` adds stochastic rows: a sampled
    non-spec row per layout and (with ``spec_tokens``) a sampled
    speculative row token-matched against it.

    ``rebalance=True`` adds the rebalancing row pair: a CHURN workload
    (ragged prompts AND ragged budgets, so retirements leave the batch
    skewed) served twice — Engine(rebalance="off") vs "retire" — with a
    ``tokens_match_norebalance`` exact check, the migration counters,
    and ``load_imbalance_pre``/``load_imbalance_post`` (the cost-model
    bank imbalance at each rebalance check, before/after the applied
    plan — the strict-reduction gate in bench_bands.json). Both engines
    warm up on a replay of the same workload so the migrate jit
    compiles before the measured phase.

    ``decode_window=w`` adds the fused decode-window row trio on a
    widened share window (reduced() uses share_window=2, leaving one
    reuse step per window — too narrow for fusion to matter): its OWN
    lockstep baseline on the widened config, a per-step engine row
    (``decode_window=None``) and the fused row
    (``Engine(decode_window=w)``) — with a ``tokens_match_unfused``
    exact check, the dispatch counters, and ``speedup_vs_perstep`` (the
    fused >= per-step tokens/s ratio gate in bench_bands.json).
    """
    from repro.configs import get_arch, reduced
    from repro.core import layouts as layoutlib
    from repro.models import model as M

    names = [layoutlib.resolve_layout(n)
             for n in (layouts if layouts else [layout])]

    cfg = reduced(get_arch("smollm-360m"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    buckets = [24, 48]
    capacity = max(buckets) + gen_max + cfg.h2eal.page_size
    reqs = build_requests(cfg, n=requests, buckets=buckets,
                          gen_min=gen_min, gen_max=gen_max, seed=seed)

    # warm the lockstep jits (one group); measure best-of-reps (wall time
    # on a contended CPU is noisy; the step counts are deterministic)
    lockstep = make_lockstep_runner(cfg, params, capacity=capacity)
    lockstep(reqs[:max_batch], max_batch=max_batch, pad_to=max(buckets))
    lock = min((lockstep(reqs, max_batch=max_batch, pad_to=max(buckets))
                for _ in range(max(reps, 1))), key=lambda r: r["wall_s"])
    lock["tokens_per_step"] = (lock["useful_tokens"]
                               / max(lock["decode_steps"], 1))
    rows = [_row("lockstep", "default", "ref", lock)]
    out = {"lockstep": lock, "layouts": {}}
    if csv:
        print(f"serve_throughput,devices,{len(jax.devices())},"
              f"lockstep_tok_s,{lock['tokens_per_s']:.2f},steps,"
              f"{lock['decode_steps']},tok_per_step,"
              f"{lock['tokens_per_step']:.2f}")

    for name in names:
        admission = ("balanced" if layoutlib.get_layout(name).shards_pages
                     else "fifo")
        rag = run_engine(cfg, params, reqs, max_batch=max_batch,
                         capacity=capacity, buckets=buckets, reps=reps,
                         layout=name, admission=admission)
        ratio = rag["tokens_per_s"] / lock["tokens_per_s"]
        step_ratio = rag["tokens_per_step"] / lock["tokens_per_step"]
        rows.append(_row("ragged", name, "ref", rag, lock=lock))
        out["layouts"][name] = {"ragged": rag, "speedup": ratio,
                                "step_reduction": step_ratio}
        if csv:
            print(f"serve_throughput,layout,{name}")
            print(f"serve_throughput,ragged_tok_s,"
                  f"{rag['tokens_per_s']:.2f},steps,"
                  f"{rag['decode_steps']},tok_per_step,"
                  f"{rag['tokens_per_step']:.2f},occupancy,"
                  f"{rag['occupancy']:.2f}")
            print(f"serve_throughput,wall_speedup,{ratio:.2f},"
                  f"per_step_throughput_gain,{step_ratio:.2f}")
            print(f"serve_throughput,recompiled_after_warmup,"
                  f"{rag['recompiled_after_warmup']},jit_cache,"
                  f"\"{rag['jit_cache']}\"")
        if prefill_chunk:
            # chunked-prefill row: same requests/admission, the prompt KV
            # streams into the sharded slots chunk-by-chunk instead of
            # prefill-then-pack; tokens must match the packed row (off
            # argmax ties, EXPERIMENTS.md)
            chk = run_engine(cfg, params, reqs, max_batch=max_batch,
                             capacity=capacity, buckets=buckets, reps=reps,
                             layout=name, admission=admission,
                             prefill_chunk=prefill_chunk)
            match = chk["tokens"] == rag["tokens"]
            rows.append(_row("ragged", name, "ref", chk, lock=lock,
                             extra={"prefill_chunk": prefill_chunk,
                                    "tokens_match_packed": match}))
            out["layouts"][name]["chunked"] = chk
            out["layouts"][name]["chunked_tokens_match_packed"] = match
            if csv:
                print(f"serve_throughput,prefill_chunk,{prefill_chunk},"
                      f"tok_s,{chk['tokens_per_s']:.2f},"
                      f"tokens_match_packed,{match},"
                      f"recompiled_after_warmup,"
                      f"{chk['recompiled_after_warmup']}")
        samp = None
        if sampling:
            # stochastic non-spec row: same requests, per-request RNG
            # keys (seed, uid) — the reference trace the sampled
            # speculative row must reproduce exactly
            samp = run_engine(cfg, params, reqs, max_batch=max_batch,
                              capacity=capacity, buckets=buckets, reps=reps,
                              layout=name, admission=admission,
                              sampling=sampling)
            rows.append(_row("ragged", name, "ref", samp, lock=lock))
            out["layouts"][name]["sampled"] = samp
            if csv:
                print(f"serve_throughput,sampling,"
                      f"{sampling[0]},{sampling[1]},tok_s,"
                      f"{samp['tokens_per_s']:.2f},recompiled_after_warmup,"
                      f"{samp['recompiled_after_warmup']}")
        if spec_tokens:
            # speculative rows: the coupled rejection sampler emits the
            # EXACT non-speculative trace (greedy = temp-0 special case),
            # so both flags below are exact-match gates, not heuristics
            for lbl, smp, ref in ((("greedy"), None, rag),
                                  (("sampled"), sampling, samp)):
                if lbl == "sampled" and not sampling:
                    continue
                spec_r = run_engine(cfg, params, reqs, max_batch=max_batch,
                                    capacity=capacity, buckets=buckets,
                                    reps=reps, layout=name,
                                    admission=admission,
                                    spec_tokens=spec_tokens, sampling=smp)
                match = spec_r["tokens"] == ref["tokens"]
                rows.append(_row("ragged", name, "ref", spec_r, lock=lock,
                                 extra={"tokens_match_nonspec": match}))
                out["layouts"][name][f"spec_{lbl}"] = spec_r
                out["layouts"][name][f"spec_{lbl}_match"] = match
                if csv:
                    print(f"serve_throughput,spec_tokens,{spec_tokens},"
                          f"{lbl},tok_s,{spec_r['tokens_per_s']:.2f},"
                          f"steps_per_s,{spec_r['steps_per_s']:.2f},"
                          f"mean_accepted_len,"
                          f"{spec_r['mean_accepted_len']:.2f},"
                          f"tokens_match_nonspec,{match},"
                          f"recompiled_after_warmup,"
                          f"{spec_r['recompiled_after_warmup']}")
        if arrival == "poisson":
            for label, pc in (("packed", None), ("chunked", prefill_chunk)):
                if label == "chunked" and not prefill_chunk:
                    continue
                lat = run_latency(
                    cfg, params, requests=requests, max_batch=max_batch,
                    capacity=capacity, buckets=buckets, gen_min=gen_min,
                    gen_max=gen_max, seed=seed, layout=name,
                    admission=admission, prefill_chunk=pc,
                    arrival_rate=arrival_rate)
                rows.append(_row("poisson", name, "ref", lat, extra={
                    "prefill_chunk": pc or 0, "admission_mode": label,
                    "arrival_rate": arrival_rate,
                    "long_len": lat["long_len"],
                    "ttft_p50_s": lat["ttft_p50_s"],
                    "ttft_p99_s": lat["ttft_p99_s"],
                    "itl_p50_s": lat["itl_p50_s"],
                    "itl_p99_s": lat["itl_p99_s"],
                    "decode_tokens_during_long_prefill":
                        lat["decode_tokens_during_long_prefill"]}))
                out["layouts"][name][f"poisson_{label}"] = lat
                if csv:
                    print(f"serve_throughput,poisson,{label},layout,{name},"
                          f"ttft_p50_ms,{lat['ttft_p50_s']*1e3:.1f},"
                          f"ttft_p99_ms,{lat['ttft_p99_s']*1e3:.1f},"
                          f"itl_p50_ms,{lat['itl_p50_s']*1e3:.1f},"
                          f"itl_p99_ms,{lat['itl_p99_s']*1e3:.1f},"
                          f"decode_tok_during_long_prefill,"
                          f"{lat['decode_tokens_during_long_prefill']:.1f}")
        if attn_impl == "pallas":
            # ref-vs-pallas comparison row: same requests, same admission
            # trace, only the attention kernel impl differs
            # (EXPERIMENTS.md).
            pal = run_engine(cfg, params, reqs, max_batch=max_batch,
                             capacity=capacity, buckets=buckets, reps=reps,
                             layout=name, admission=admission,
                             attn_impl="pallas")
            match = pal["tokens"] == rag["tokens"]
            impl_ratio = pal["tokens_per_s"] / rag["tokens_per_s"]
            rows.append(_row("ragged", name, "pallas", pal, lock=lock,
                             extra={"tokens_match_ref": match}))
            if csv:
                print(f"serve_throughput,attn_impl,pallas,tok_s,"
                      f"{pal['tokens_per_s']:.2f},vs_ref,{impl_ratio:.2f},"
                      f"tokens_match_ref,{match},recompiled_after_warmup,"
                      f"{pal['recompiled_after_warmup']}")
            out["layouts"][name]["pallas"] = pal
            out["layouts"][name]["pallas_tokens_match_ref"] = match

    if tiered_hot_pages:
        # tiered hot/cold residency rows: a DEEPER workload (long
        # prompts, page table >= 2x oversubscribed vs the hot budget) so
        # the spill/prefetch machinery actually runs, served twice —
        # all-resident oracle vs Engine(hot_pages=N) — with a
        # token-exactness flag and the modeled far-bank traffic
        # (runtime.perfmodel byte counts through the hbsim NoC link)
        from repro.hbsim import sim as hbsim

        t_buckets = [128]
        t_gen = 12
        t_cap = 160
        t_reqs = build_requests(cfg, n=8, buckets=t_buckets,
                                gen_min=t_gen, gen_max=t_gen, seed=seed)
        res = run_engine(cfg, params, t_reqs, max_batch=2,
                         capacity=t_cap, buckets=t_buckets, reps=reps)
        tier = run_engine(cfg, params, t_reqs, max_batch=2,
                         capacity=t_cap, buckets=t_buckets, reps=reps,
                         hot_pages=tiered_hot_pages)
        match = tier["tokens"] == res["tokens"]
        p = cfg.h2eal.page_size
        slot_pages = -(-(max(t_buckets) + t_gen) // p)
        oversub = slot_pages / tiered_hot_pages
        modeled = hbsim.tiered_serving_overhead(
            cfg, fills=tier["tier_fills"], spills=tier["tier_spills"],
            prefetch=tier["tier_prefetch"],
            decode_steps=tier["decode_steps"])
        rows.append(_row("ragged", "default", "ref", res,
                         extra={"tier": "resident",
                                "prompt_len": max(t_buckets)}))
        rows.append(_row("ragged", "default", "ref", tier, extra={
            "tier": "tiered", "hot_pages": tiered_hot_pages,
            "oversubscription": oversub,
            "tokens_match_resident": match,
            "tier_hits": tier["tier_hits"],
            "tier_misses": tier["tier_misses"],
            "tier_spills": tier["tier_spills"],
            "tier_fills": tier["tier_fills"],
            "tier_prefetch": tier["tier_prefetch"],
            "tier_hit_rate": tier["tier_hit_rate"],
            "far_bank_modeled": modeled}))
        out["tiered"] = {"resident": res, "tiered": tier,
                         "tokens_match_resident": match,
                         "oversubscription": oversub,
                         "far_bank_modeled": modeled}
        if csv:
            print(f"serve_throughput,tiered,hot_pages,{tiered_hot_pages},"
                  f"oversubscription,{oversub:.2f},tok_s,"
                  f"{tier['tokens_per_s']:.2f},resident_tok_s,"
                  f"{res['tokens_per_s']:.2f},hit_rate,"
                  f"{tier['tier_hit_rate']:.3f},spills,"
                  f"{tier['tier_spills']},fills,{tier['tier_fills']},"
                  f"prefetch,{tier['tier_prefetch']},"
                  f"tokens_match_resident,{match}")

    if spec_tokens:
        # the throughput-gate workload: speculation only pays when the
        # draft is usually right AND acceptance may run several tokens
        # before a selection refresh, so this pair is constructed to sit
        # in that regime. Constant-token prompts + an init seed whose
        # greedy continuation locks into a period-1 cycle (PRNGKey(3);
        # seed 0's continuation breaks its runs every ~5 tokens, capping
        # prompt-lookup acceptance near 2) make the suffix-n-gram draft
        # usually right, and a share window widened to 2k keeps the
        # selection-refresh boundary from clamping max_emit below k.
        # Served twice — non-spec vs Engine(spec_tokens=k) — this pair
        # carries the `speculative >= non-spec tokens/s` ratio gate in
        # bench_bands.json; the per-layout rows above measure the
        # ngram-hostile random workload and are NOT ratio-gated.
        import dataclasses

        from repro.serving import Request

        s_cfg = dataclasses.replace(
            cfg, h2eal=dataclasses.replace(cfg.h2eal,
                                           share_window=2 * spec_tokens))
        s_params = M.init_params(cfg, jax.random.PRNGKey(3))
        s_gen = 48
        s_cap = max(buckets) + s_gen + cfg.h2eal.page_size
        s_reqs = [Request(uid=i,
                          prompt=np.full((buckets[i % 2],), 7, np.int32),
                          max_new=s_gen)
                  for i in range(8)]
        # batch/reps pinned (not the CLI smoke flags): max_batch=1 is
        # the latency-bound regime speculation targets — per-step fixed
        # dispatch cost amortizes over accepted tokens, whereas at
        # larger batches this host is compute-saturated and the k-query
        # verify forward costs its full flops (ratio ~0.9 at B=4,
        # ~1.3 at B=1 with the same 3.36 acceptance); reps >= 2 because
        # a 1-rep run is noise-bound on a contended CI host
        s_mb, s_reps = 1, max(reps, 2)
        base_n = run_engine(s_cfg, s_params, s_reqs, max_batch=s_mb,
                            capacity=s_cap, buckets=buckets, reps=s_reps)
        spec_n = run_engine(s_cfg, s_params, s_reqs, max_batch=s_mb,
                            capacity=s_cap, buckets=buckets, reps=s_reps,
                            spec_tokens=spec_tokens)
        match = spec_n["tokens"] == base_n["tokens"]
        ratio = spec_n["tokens_per_s"] / base_n["tokens_per_s"]
        rows.append(_row("ragged", "default", "ref", base_n,
                         extra={"workload": "ngram"}))
        rows.append(_row("ragged", "default", "ref", spec_n,
                         extra={"workload": "ngram",
                                "tokens_match_nonspec": match,
                                "speedup_vs_nonspec": ratio}))
        out["spec_ngram"] = {"nonspec": base_n, "spec": spec_n,
                             "tokens_match_nonspec": match,
                             "speedup_vs_nonspec": ratio}
        if csv:
            print(f"serve_throughput,spec_ngram,k,{spec_tokens},"
                  f"share_window,{s_cfg.h2eal.share_window},"
                  f"mean_accepted_len,{spec_n['mean_accepted_len']:.2f},"
                  f"tok_s,{spec_n['tokens_per_s']:.2f},nonspec_tok_s,"
                  f"{base_n['tokens_per_s']:.2f},speedup,{ratio:.2f},"
                  f"tokens_match_nonspec,{match}")

    if decode_window:
        # fused decode-window row trio (PR 10): the windows only pay
        # when a share window holds several reuse steps, so this pair
        # runs on a widened share window (reduced() uses 2 — a single
        # reuse step per window) with generation lengths spanning
        # several windows. The per-step row is BOTH the token-exactness
        # reference and the tokens/s denominator; the widened config
        # gets its own lockstep baseline so speedup_vs_lockstep stays
        # honest.
        import dataclasses

        f_w = 8
        f_cfg = dataclasses.replace(
            cfg, h2eal=dataclasses.replace(cfg.h2eal, share_window=f_w))
        # decode-heavy shape: short prompts (smallest bucket only) and
        # generations spanning 3-6 windows, so the dispatch savings the
        # fusion buys are measured against decode wall, not prefill
        f_buckets = [min(buckets)]
        f_gen_min, f_gen_max = 3 * f_w, 6 * f_w
        f_cap = max(f_buckets) + f_gen_max + cfg.h2eal.page_size
        f_reqs = build_requests(cfg, n=12, buckets=f_buckets,
                                gen_min=f_gen_min, gen_max=f_gen_max,
                                seed=seed)
        f_lockstep = make_lockstep_runner(f_cfg, params, capacity=f_cap)
        f_lockstep(f_reqs[:max_batch], max_batch=max_batch,
                   pad_to=max(f_buckets))
        f_lock = min((f_lockstep(f_reqs, max_batch=max_batch,
                                 pad_to=max(f_buckets))
                      for _ in range(max(reps, 1))),
                     key=lambda r: r["wall_s"])
        f_lock["tokens_per_step"] = (f_lock["useful_tokens"]
                                     / max(f_lock["decode_steps"], 1))
        # best-of-3 wall clocks: the ratio gate in bench_bands.json is
        # exact (not banded), and fused-vs-per-step differ by ~100 ms
        # on the toy config — single-rep scheduler noise could flip it
        f_reps = max(reps, 3)
        base_f = run_engine(f_cfg, params, f_reqs, max_batch=max_batch,
                            capacity=f_cap, buckets=f_buckets, reps=f_reps)
        fus = run_engine(f_cfg, params, f_reqs, max_batch=max_batch,
                         capacity=f_cap, buckets=f_buckets, reps=f_reps,
                         decode_window=decode_window)
        match = fus["tokens"] == base_f["tokens"]
        ratio = fus["tokens_per_s"] / base_f["tokens_per_s"]
        rows.append(_row("ragged", "default", "ref", base_f, lock=f_lock,
                         extra={"workload": "fusedwin",
                                "share_window": f_w}))
        rows.append(_row("ragged", "default", "ref", fus, lock=f_lock,
                         extra={"workload": "fusedwin",
                                "share_window": f_w,
                                "tokens_match_unfused": match,
                                "speedup_vs_perstep": ratio}))
        out["fused"] = {"perstep": base_f, "fused": fus,
                        "tokens_match_unfused": match,
                        "speedup_vs_perstep": ratio}
        if csv:
            print(f"serve_throughput,fused_window,{decode_window},"
                  f"share_window,{f_w},tok_s,{fus['tokens_per_s']:.2f},"
                  f"perstep_tok_s,{base_f['tokens_per_s']:.2f},"
                  f"speedup_vs_perstep,{ratio:.2f},dispatches,"
                  f"{fus['dispatches']},perstep_dispatches,"
                  f"{base_f['dispatches']},steps_per_dispatch,"
                  f"{fus['steps_per_dispatch']:.2f},fused_windows,"
                  f"{fus['fused_windows']},tokens_match_unfused,{match},"
                  f"recompiled_after_warmup,"
                  f"{fus['recompiled_after_warmup']}")

    if rebalance:
        # rebalancing row pair: the churn workload mixes short/long
        # prompts with short/long budgets at seed-determined positions,
        # so early retirements leave heavy slots clustered in one bank —
        # the drift the retire-triggered planner exists to undo. Served
        # twice (rebalance off vs retire) with identical requests: the
        # trace must match token-for-token (migration moves cache rows
        # verbatim; sampling keys are (seed, uid)-owned), and the mean
        # cost-model bank imbalance at the rebalance checks must drop
        # strictly (the bench_bands.json imbalance gate).
        from repro.hbsim import sim as hbsim

        rb_buckets = [8, 16, 24]
        rb_gen_max = 19
        rb_cap = max(rb_buckets) + rb_gen_max + cfg.h2eal.page_size
        rb_reqs = build_requests(cfg, n=12, buckets=rb_buckets,
                                 gen_min=3, gen_max=rb_gen_max, seed=seed)
        base_rb = run_engine(cfg, params, rb_reqs, max_batch=4,
                             capacity=rb_cap, buckets=rb_buckets,
                             reps=reps, warm_requests=rb_reqs)
        reb = run_engine(cfg, params, rb_reqs, max_batch=4,
                         capacity=rb_cap, buckets=rb_buckets, reps=reps,
                         rebalance="retire", warm_requests=rb_reqs)
        match = reb["tokens"] == base_rb["tokens"]
        modeled = hbsim.rebalance_overhead(
            cfg, migrations=reb["migrations"],
            migrated_tokens=reb["migrated_tokens"],
            decode_steps=reb["decode_steps"])
        rows.append(_row("ragged", "default", "ref", base_rb,
                         extra={"workload": "churn"}))
        rows.append(_row("ragged", "default", "ref", reb, extra={
            "workload": "churn+rb", "rebalance": "retire",
            "tokens_match_norebalance": match,
            "migrations": reb["migrations"],
            "rebalances": reb["rebalances"],
            "rebalance_checks": reb["rebalance_checks"],
            "load_imbalance_pre": reb["load_imbalance_pre"],
            "load_imbalance_post": reb["load_imbalance_post"],
            "rebalance_modeled": modeled}))
        out["rebalance"] = {"norebalance": base_rb, "rebalanced": reb,
                            "tokens_match_norebalance": match,
                            "rebalance_modeled": modeled}
        if csv:
            print(f"serve_throughput,rebalance,retire,migrations,"
                  f"{reb['migrations']},applied,{reb['rebalances']},"
                  f"imbalance_pre,{reb['load_imbalance_pre']:.3f},"
                  f"imbalance_post,{reb['load_imbalance_post']:.3f},"
                  f"tok_s,{reb['tokens_per_s']:.2f},norebalance_tok_s,"
                  f"{base_rb['tokens_per_s']:.2f},"
                  f"tokens_match_norebalance,{match},"
                  f"recompiled_after_warmup,"
                  f"{reb['recompiled_after_warmup']}")

    # back-compat single-layout view (deprecated alias, one release)
    first = out["layouts"][names[0]]
    out.update({"ragged": first["ragged"], "speedup": first["speedup"],
                "step_reduction": first["step_reduction"]})
    if "pallas" in first:
        out["pallas"] = first["pallas"]
        out["pallas_tokens_match_ref"] = first["pallas_tokens_match_ref"]

    if json_path:
        import json

        payload = {
            "benchmark": "serve_throughput",
            "devices": len(jax.devices()),
            "config": {"requests": requests, "max_batch": max_batch,
                       "gen_min": gen_min, "gen_max": gen_max,
                       "seed": seed, "reps": reps,
                       "prompt_buckets": buckets, "capacity": capacity},
            "rows": [{k: v for k, v in r.items() if k != "tokens"}
                     for r in rows],
        }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        if csv:
            print(f"serve_throughput,json,{json_path},rows,{len(rows)}")
    return out


if __name__ == "__main__":
    from repro.core.layouts import available_layouts
    from repro.runtime.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--gen-min", type=int, default=2)
    ap.add_argument("--gen-max", type=int, default=40)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--layout", default="default",
                    help="comma-separated engine serve-cache layouts "
                         f"(registry entries: {', '.join(available_layouts())}; "
                         "page-sharding layouts get balanced admission)")
    ap.add_argument("--attn-impl", choices=["ref", "pallas"], default="ref",
                    help="pallas = add the ref-vs-pallas comparison row "
                         "per layout (Pallas kernels; interpret mode "
                         "on CPU)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="add a chunked-prefill engine row per layout "
                         "(N prompt tokens per engine step, interleaved "
                         "with decode; 0 = prefill-then-pack only)")
    ap.add_argument("--arrival", choices=["batch", "poisson"],
                    default="batch",
                    help="poisson = bursty-arrival LATENCY rows (p50/p99 "
                         "TTFT + inter-token latency, packed vs chunked; "
                         "per-step device sync, not a throughput number)")
    ap.add_argument("--arrival-rate", type=float, default=0.5,
                    help="poisson arrivals per engine step")
    ap.add_argument("--tiered-hot-pages", type=int, default=0,
                    help="add the tiered-residency row pair: a deep-"
                         "prompt workload served all-resident and with "
                         "Engine(hot_pages=N) (spill/prefetch through "
                         "the host far store), with hit/miss/spill/"
                         "prefetch counters, a tokens_match_resident "
                         "flag, and the modeled far-bank traffic")
    ap.add_argument("--spec-tokens", type=int, default=0,
                    help="add speculative-decode rows per layout "
                         "(Engine(spec_tokens=k), ngram prompt-lookup "
                         "draft, tokens_match_nonspec exact check) plus "
                         "the ngram-friendly workload pair carrying the "
                         "spec >= non-spec tokens/s ratio gate; 0 = off")
    ap.add_argument("--sampling", default=None, metavar="TEMP,TOP_P",
                    help="add stochastic-sampling rows per layout "
                         "(per-request RNG keys; with --spec-tokens also "
                         "a sampled speculative row token-matched "
                         "against the sampled non-spec row)")
    ap.add_argument("--rebalance", action="store_true",
                    help="add the rebalancing row pair: a churn workload "
                         "served with Engine(rebalance='off') vs "
                         "'retire' — tokens_match_norebalance exact "
                         "check, migration counters, and the "
                         "load_imbalance_pre/post strict-reduction gate")
    ap.add_argument("--decode-window", type=int, default=0,
                    help="add the fused decode-window row trio on a "
                         "widened share window: own lockstep baseline, "
                         "per-step engine row, and Engine(decode_window"
                         "=w) — tokens_match_unfused exact check, "
                         "dispatch counters, speedup_vs_perstep ratio "
                         "gate; 0 = off")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the machine-readable row list (tok/s per "
                         "layout x impl x admission mode, occupancy, "
                         "recompile flags, latency percentiles) to PATH, "
                         "e.g. BENCH_serve.json")
    a = ap.parse_args()
    enable_compile_cache()
    samp = None
    if a.sampling:
        parts = [float(s) for s in a.sampling.split(",")]
        samp = (parts[0], parts[1] if len(parts) > 1 else 1.0)
    run(requests=a.requests, max_batch=a.max_batch, gen_min=a.gen_min,
        gen_max=a.gen_max, seed=a.seed, reps=a.reps,
        layouts=[s.strip() for s in a.layout.split(",") if s.strip()],
        attn_impl=None if a.attn_impl == "ref" else a.attn_impl,
        json_path=a.json, prefill_chunk=a.prefill_chunk or None,
        arrival=a.arrival, arrival_rate=a.arrival_rate,
        tiered_hot_pages=a.tiered_hot_pages or None,
        spec_tokens=a.spec_tokens or None, sampling=samp,
        rebalance=a.rebalance, decode_window=a.decode_window or None)
