#!/usr/bin/env python3
"""One traced run of a cell, its trace read with bench/harness/scopes.py.

    python3 bench/scope_run.py --workload <cell> --seed <n> --seconds <s> \
        [--keep-trace <out.json> [--keep-polls <n>]]

The run is ``harness.runner.run`` with ``trace=True``, exactly as
``bench/run.py --trace 1`` makes it, except that the trace is loaded,
reduced and kept by bench/harness/scopes.py instead of
bench/harness/xplane.py: the breakdown's ``idle_gaps`` name the innermost
span over each idle gap, and ``--keep-trace`` keeps the engine spans and
op scopes of the first ``--keep-polls`` polls too, with each program's
``op_name`` of every instruction beside it
(``<out.json>.programs.json``). Prints the result line, then one JSON
line of the readings inside the program: the four scope readings
(``reuse_step_unscoped_ms``, ``reuse_step_attention_ms``,
``chunk_step_attention_ms``, ``idle_host_share``), device time per
``program/scope`` per traced reuse, select and chunk step, the top
``program/scope`` device times, and the traced polls' count and mean
``poll()`` host time. On a program without spans or scopes the readings
are null and the rest reads as usual.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))


def _per_step(run, kind, key):
    """Mean device ms per ``program/scope`` over the traced polls that
    ran a step of ``kind`` (``key`` its EngineStats counter)."""
    tot, n = {}, 0
    for p, ps in zip(run.traced_polls, run.trace["poll_scopes"]):
        if p.stats[key] > 0:
            n += 1
            for k, v in ps.items():
                tot[k] = tot.get(k, 0.0) + v
    return {k: 1e3 * v / n for k, v in sorted(tot.items())} if n else {}


def readings(run) -> dict:
    from harness import scopes
    from harness.readers import polls_of

    t = run.trace
    host_ms = [1e3 * (p.t1 - p.t0) for p in run.traced_polls]
    reuse = polls_of(run, "reuse")
    return {
        "reuse_step_unscoped_ms": scopes.reuse_step_unscoped_ms(run),
        "reuse_step_attention_ms": scopes.reuse_step_attention_ms(run),
        "chunk_step_attention_ms": scopes.chunk_step_attention_ms(run),
        "idle_host_share": scopes.idle_host_share(run),
        "traced_polls": len(run.traced_polls),
        "poll_host_ms_mean": sum(host_ms) / len(host_ms) if host_ms else None,
        # the decode program's time on the XLA Modules line, per traced
        # reuse step, against the sum of its scoped leaf ops
        "reuse_decode_module_ms": (1e3 * sum(d.get("decode", 0.0)
                                             for _, d in reuse) / len(reuse)
                                   if reuse else None),
        "per_step_ms": {kind: _per_step(run, kind, key) for kind, key in (
            ("reuse", "reuse_steps"), ("select", "select_steps"),
            ("chunk", "prefill_chunks"))},
        "device_scopes": t["device_scopes"],
        "idle_gaps": t["idle_gaps"],
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep-trace", default=None)
    ap.add_argument("--keep-polls", type=int, default=12)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    import harness
    from harness import runner, scopes, spec, xplane
    from harness.peaks import peaks_for
    from run import tpu_devices

    # the runner imports harness.xplane inside run(): hand it this
    # module's reader, and keep the run record it builds
    def load(path, devices):
        if args.keep_trace:
            with open(path, "rb") as f:
                programs = scopes.program_op_names(f.read())
            with open(args.keep_trace + ".programs.json", "w") as f:
                json.dump(programs, f)
        return scopes.load(path, devices)

    harness.xplane = types.SimpleNamespace(
        find_xplane=xplane.find_xplane, load=load, reduce=scopes.reduce,
        dump=lambda ev, path: scopes.dump(ev, path,
                                          max_polls=args.keep_polls))
    kept = []

    class Run(runner.Run):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            kept.append(self)

    runner.Run = Run
    cell = spec.load_cell(args.workload)
    devices = tpu_devices(cell.chips)
    result = runner.run(cell, seed=args.seed, seconds=args.seconds,
                        trace=True, devices=devices,
                        peaks=peaks_for(devices[0].device_kind),
                        t_start=T_START, keep_trace=args.keep_trace)
    run = kept[-1]
    # the runner matched the trace's polls to its own and cut both to
    # the shorter; cut the per-poll scopes alike
    run.trace["poll_scopes"] = run.trace["poll_scopes"][
        :len(run.trace["polls"])]
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    print(json.dumps({"scopes": readings(run)}), flush=True)


if __name__ == "__main__":
    main()
