"""One run of one cell: set-up, the measured window, the metrics, and
the check of what the window served against the plain reference.

``run()`` takes the devices it may use; bench/run.py looks for them and
refuses to run without the chips a cell asks for. The tests call
``run()`` on the CPU at a tiny size.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import time

import numpy as np

from harness import driver as drv_lib
from harness import spec as spec_lib
from harness import traffic as traffic_lib
from harness import work as work_lib

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


@dataclasses.dataclass
class Run:
    """What the metric readers (bench/metrics/<name>.py) read."""
    cell: spec_lib.Cell
    peaks: dict
    setup_s: float
    t_open: float
    t_close: float
    t_end: float
    polls: list              # driver.Poll, every poll of the run
    reqs: dict               # uid -> driver.Req
    stats_open: dict         # EngineStats counters at window open
    stats_end: dict          # ... at the end of the window
    trace: dict = None       # harness/xplane.reduce() of the traced polls
    traced_polls: list = None  # driver.Poll matching trace["polls"]

    @property
    def window_s(self) -> float:
        return self.t_end - self.t_open

    @property
    def window_polls(self) -> list:
        return [p for p in self.polls if self.t_open <= p.t0 < self.t_close]

    def delta(self, key: str) -> int:
        return self.stats_end[key] - self.stats_open[key]


class CompileWatch:
    """Counts compilations (and compile-cache loads) by host time."""

    def __init__(self, clock):
        import jax
        self.clock, self.events = clock, []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event in COMPILE_EVENTS:
            self.events.append((self.clock(), event, secs))

    def between(self, lo, hi):
        return [e for e in self.events if lo <= e[0] < hi]


def _log(*a):
    print(*a, file=sys.stderr, flush=True)


class _Phases:
    """Logs the host time each phase of a run took."""

    def __init__(self, clock, t0):
        self.clock, self.t = clock, t0

    def __call__(self, name):
        now = self.clock()
        _log(f"phase {name}: {now - self.t:.3f} s")
        self.t = now


def _params_fn(conf, ref, prog):
    import jax
    return jax.jit(lambda lo, hi: prog.params(conf, ref.make_weights(
        conf, lo, hi)))


def _warm(eng, cell, request_cls, vocab):
    """Compile every program the cell's traffic uses, on the engine that
    the window then drives: two requests that take two prompt chunks and
    both kinds of decode step, then an idle reset of the metrics."""
    chunk = cell.engine["prefill_chunk"]
    w = max(cell.config["h2eal"]["share_window"], 1)
    rng = np.random.default_rng(0)
    for i in range(2):
        eng.submit(request_cls(
            uid=10 ** 9 + i, max_new=w + 2,
            prompt=rng.integers(0, vocab, size=chunk + 1).astype(np.int32)))
    while eng.busy():
        eng.poll()
    eng.sync()
    eng.reset_metrics()


def _sample(cell, eng, drv, seed):
    """Requests to check, among those the run served tokens to, finished
    or still in flight: every one where the cell's ``check.requests`` is
    ``"all"``; else the longest, and more drawn from the seed."""
    eng.finalize()
    comps = dict(eng.completions)
    # the engine exposes finished completions only; in-flight ones
    # (their tokens materialised by finalize()) sit in its slot map
    comps.update({c.uid: c for c in eng._live.values()})
    cands = [c for u, c in comps.items() if u in drv.reqs and c.tokens]
    if not cands:
        return []
    cands.sort(key=lambda c: (-(c.prompt_len + len(c.tokens)), c.uid))
    k = cell.settings["check"]["requests"]
    if k == "all":
        return cands
    k = min(int(k), len(cands))
    rng = traffic_lib.seed_rng(seed)
    rest = rng.permutation(len(cands) - 1)[:k - 1] + 1
    return [cands[0]] + [cands[int(i)] for i in sorted(rest)]


def check(cell, seed, sample, prompts, ref, *, control=False):
    """Gaps of the served tokens below the reference's best logit."""
    conf = dict(cell.config, reference_length=cell.engine["capacity"])
    w = ref.weights(conf, seed)
    gaps, cgaps, flips, n = [], [], 0, 0
    for c in sample:
        out = ref.logit_gaps(conf, w, prompts[c.uid], np.asarray(c.tokens),
                             control=control)
        gaps.append(out["gap"])
        flips += out["flips"]
        n += len(c.tokens)
        if control:
            cgaps.append(out["control_gap"])
    res = {"tokens": n, "flip_share": flips / max(n, 1)}
    for key, gs in (("gap", gaps), ("control_gap", cgaps)):
        if gs:
            g = np.concatenate(gs)
            res[key + "_mean"] = float(g.mean())
            res[key + "_max"] = float(g.max())
            # read, not compared: the worst request's own mean, which a
            # fault confined to one request does not average away
            res[key + "_request_max"] = float(max(x.mean() for x in gs))
    return res


def decide(value: float, limit: float) -> bool:
    """The comparison that decides ``correct``, for the program's
    reading and for the control's alike."""
    return bool(np.isfinite(value)) and value <= limit


def run(cell, *, seed: int, seconds: float, trace: bool, devices, peaks,
        t_start: float, engine_hook=None, control: bool = False,
        keep_trace: str = None,
        clock=time.perf_counter) -> dict:
    """One run of ``cell`` on ``devices``; returns the result line."""
    import jax
    from repro.runtime.compile_cache import enable_compile_cache
    from repro.serving import Engine, Request

    enable_compile_cache()
    watch = CompileWatch(clock)
    conf = cell.config
    ref = spec_lib.load_module("reference", conf["reference"])
    prog = spec_lib.load_module("programs", conf["program"])
    arch = prog.arch_config(conf)
    phase = _Phases(clock, t_start)
    params = _params_fn(conf, ref, prog)(*ref.seed_words(seed))
    jax.block_until_ready(params)
    phase("weights")
    e = cell.engine
    eng = Engine(arch, params, max_batch=e["max_batch"],
                 capacity=e["capacity"], prompt_buckets=(e["prefill_chunk"],),
                 impl=e["impl"], prefill_chunk=e["prefill_chunk"])
    if engine_hook is not None:
        engine_hook(eng)
    phase("engine")
    arrivals = traffic_lib.generate(cell.traffic, seed=seed, seconds=seconds,
                                    vocab=conf["vocab_size"],
                                    capacity=e["capacity"])
    prompts = {a.uid: a.prompt for a in arrivals}
    closed = cell.traffic["kind"] == "closed"
    if not closed:
        _warm(eng, cell, Request, conf["vocab_size"])
        phase("warm-up")
    if trace:
        # the profiler's first start is slow: take it outside the window
        warm_dir = tempfile.mkdtemp(prefix="bench_warm_")
        jax.profiler.start_trace(warm_dir)
        jax.profiler.stop_trace()
        shutil.rmtree(warm_dir, ignore_errors=True)
        phase("profiler warm-up")
    drv = drv_lib.Driver(eng, clock)
    if closed:
        drv_lib.fill(drv, arrivals, Request)
        phase(f"prefill of {len(arrivals)} sessions "
              f"({len(drv.polls)} polls)")
        t_open = clock()
        arrivals_win = []
    else:
        t_open = clock() + float(cell.traffic["lead_s"])
        arrivals_win = arrivals
    t_close = t_open + seconds
    state = {"stats_open": None, "trace_dir": None, "trace_on": None,
             "trace_off": None}
    # a cell may trace only the first part of its window: stopping the
    # profiler takes about a second per second traced
    trace_s = float(cell.settings.get("trace_seconds", seconds))

    def stop_trace():
        state["trace_off"] = clock()
        jax.profiler.stop_trace()

    def tick(now):
        if state["stats_open"] is None and now >= t_open:
            state["stats_open"] = drv_lib.stats_now(eng)
        if trace and state["trace_on"] is None and now >= t_open:
            state["trace_dir"] = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # spans only, not every call
            jax.profiler.start_trace(state["trace_dir"],
                                     profiler_options=opts)
            state["trace_on"] = clock()
            _log(f"profiler start took {state['trace_on'] - now:.3f} s")
        if (state["trace_on"] is not None and state["trace_off"] is None
                and now >= state["trace_on"] + trace_s):
            stop_trace()

    drv_lib.run_open(drv, arrivals_win, t_open, t_close, Request,
                     on_tick=tick)
    t_end = max([t_close] + [p.t2 for p in drv.polls if p.t0 < t_close])
    if trace and state["trace_off"] is None:
        stop_trace()
    stats_end = drv_lib.stats_now(eng)
    mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
              for d in devices)
    run_rec = Run(cell=cell, peaks=peaks, setup_s=t_open - t_start,
                  t_open=t_open, t_close=t_close, t_end=t_end,
                  polls=drv.polls, reqs=drv.reqs,
                  stats_open=state["stats_open"], stats_end=stats_end)
    compiles = watch.between(t_open, t_end)
    _report(run_rec, compiles, arrivals, conf)
    sample = _sample(cell, eng, drv, seed)

    # free the program's state before the reference runs
    del eng, params, drv
    gc.collect()
    phase("window and free")

    chk = check(cell, seed, sample, prompts, ref, control=control)
    phase("reference check" + (" with control" if control else ""))
    limit = float(cell.settings["check"]["logit_gap_mean_limit"])
    value = chk.get("gap_mean", float("nan"))
    correct = bool(sample) and decide(value, limit)
    if control:
        chk["correct"] = bool(sample) and decide(
            chk.get("control_gap_mean", float("nan")), limit)

    breakdown = None
    if trace:
        from harness import xplane
        ev = xplane.load(xplane.find_xplane(state["trace_dir"]),
                         [d.id for d in devices])
        if keep_trace:
            xplane.dump(ev, keep_trace)
        shutil.rmtree(state["trace_dir"], ignore_errors=True)
        red = xplane.reduce(ev)
        phase("trace reduction")
        traced = [p for p in run_rec.polls
                  if state["trace_on"] <= p.t0 and p.t2 <= state["trace_off"]]
        if len(traced) != len(red["polls"]):
            _log(f"trace: {len(red['polls'])} polls in the trace, "
                 f"{len(traced)} recorded; matched in order")
        n = min(len(traced), len(red["polls"]))
        red["polls"] = red["polls"][:n]
        run_rec.trace, run_rec.traced_polls = red, traced[:n]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        val = spec_lib.load_module("metrics", m["name"]).read(run_rec)
        if val is not None:
            metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    if trace:
        device["busy_s"] = run_rec.trace["busy_s"]
        device["window_s"] = run_rec.trace["window_s"]
    attempted = (len(run_rec.reqs) if closed else len(_due(run_rec)))
    result = {"correct": correct, "attempted": attempted, "failed": 0,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control:
        result["control"] = chk
    result["check"] = {"logit_gap_mean": {"value": value, "limit": limit}}
    _log(f"check sample: {len(sample)} requests, {chk['tokens']} served "
         f"tokens, a share {chk['flip_share']!r} not the reference's argmax, "
         f"widest gap {chk.get('gap_max')!r}, worst request's mean gap "
         f"{chk.get('gap_request_max')!r}")
    if control:
        _log(f"control logit_gap_mean {chk.get('control_gap_mean')!r} "
             f"limit {limit!r} correct {chk['correct']}")
    _log(f"check logit_gap_mean {value!r} limit {limit!r}")
    return result


def _due(run: Run) -> list:
    """Requests due inside the window."""
    return [r for r in run.reqs.values() if run.t_open <= r.due < run.t_close]


def _report(run: Run, compiles, arrivals, conf):
    """Facts of the run that are not metrics, on stderr."""
    late = [r.submit - r.due for r in run.reqs.values()]
    if run.cell.traffic["kind"] == "open" and late:
        _log(f"generator lateness: max {max(late) * 1e3:.3f} ms, "
             f"mean {np.mean(late) * 1e3:.3f} ms over {len(late)} submits")
    _log(f"compiles inside the window: {len(compiles)}"
         + (f" {compiles}" if compiles else ""))
    due = _due(run)
    first = sum(1 for r in due if r.tokens and r.tokens[0] < run.t_end)
    done = sum(1 for r in run.reqs.values() if len(r.tokens) >= r.max_new)
    _log(f"requests: {len(run.reqs)} submitted, {len(due)} due in the "
         f"window, {first} of them with a first token, {done} finished, "
         f"{len(run.reqs) - done} unfinished")
    if due:
        from harness.readers import waits_ms
        from harness.stats import percentile
        w = waits_ms(run, "first")
        _log("ttft ms over " + str(len(w)) + " requests due in the window: "
             + ", ".join(f"p{q} {percentile(w, q):.1f}"
                         for q in (50, 75, 90, 95, 100)))
    ctxs = [c for p in run.window_polls for c in p.decode_ctx]
    if ctxs:
        att = [work_lib.attended(conf, c)[0] / c for c in ctxs]
        _log(f"attended share of the context per retrieval head: mean "
             f"{np.mean(att):.4f}, min {np.min(att):.4f} over "
             f"{len(ctxs)} decode tokens")
    _log(f"window: {run.window_s:.3f} s, {len(run.window_polls)} polls, "
         f"stats {dict((k, run.delta(k)) for k in run.stats_end)}")
