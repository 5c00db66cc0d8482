#!/usr/bin/env python3
"""Find the highest rate an open-traffic cell sustains, once, on the chip.

    python3 bench/sweep.py --workload <cell> --seconds <s> --rates 0.5 1 2

For each rate, one whole run of the cell (bench/run.py's set-up, lead,
window and check) in this one process, with only the traffic's
``rate_per_s`` replaced. Prints one JSON line per rate with the run's
end-to-end metrics; standard error says, per run, how many requests due
in the window got a first token and how many were left unfinished (a
queue that grows through the window marks a rate above capacity). The
cell's traffic file then states a fixed rate below the knee.
"""
import argparse
import dataclasses
import json
import time

from run import ROOT, tpu_devices  # noqa: F401  (sets sys.path)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    import os
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    from harness import runner, spec
    from harness.peaks import peaks_for

    cell = spec.load_cell(args.workload)
    devices = tpu_devices(cell.chips)
    peaks = peaks_for(devices[0].device_kind)
    for rate in args.rates:
        at = dataclasses.replace(
            cell, traffic=dict(cell.traffic, rate_per_s=rate))
        res = runner.run(at, seed=args.seed, seconds=args.seconds,
                         trace=False, devices=devices, peaks=peaks,
                         t_start=time.perf_counter())
        print(json.dumps({"rate_per_s": rate, "correct": res["correct"],
                          "attempted": res["attempted"],
                          **{k: v["value"]
                             for k, v in res["metrics"].items()}}),
              flush=True)


if __name__ == "__main__":
    main()
