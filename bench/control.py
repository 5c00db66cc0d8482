#!/usr/bin/env python3
"""Readings that the check's limit is set from, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 \
        [--control-seeds 1 2] [--fault alter_token 4 --fault state_unchanged 5]

For each seed, one whole run of the cell (the same set-up, window and
sample as bench/run.py) in this one process, and the program's reading:
the mean gap of the served tokens below the reference's best logit. For
the control seeds also the control's reading: the reference computed
with every matmul operand in float8 e4m3, put in the program's place, at
the same positions of the same prompts and served tokens, read as the
gap of the token it puts first and passed through the comparison that
decides ``correct`` (``control_correct``). Each ``--fault <name> <seed>``
is one more run with that fault of ``harness/faults.py`` planted under
the timed path. One JSON line per run on standard output. The
benchmark's own runs never run the control or plant a fault.
"""
import argparse
import json
import sys
import time

from run import ROOT, tpu_devices  # noqa: F401  (sets sys.path)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault", nargs=2, action="append", default=[],
                    metavar=("NAME", "SEED"))
    args = ap.parse_args()
    import os
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    from harness import runner, spec
    from harness.faults import FAULTS
    from harness.peaks import peaks_for

    cell = spec.load_cell(args.workload)
    devices = tpu_devices(cell.chips)
    peaks = peaks_for(devices[0].device_kind)
    runs = [(None, s) for s in args.seeds]
    runs += [(name, int(s)) for name, s in args.fault]
    for fault, seed in runs:
        t0 = time.perf_counter()
        res = runner.run(cell, seed=seed, seconds=args.seconds, trace=False,
                         devices=devices, peaks=peaks, t_start=t0,
                         control=fault is None and seed in args.control_seeds,
                         engine_hook=FAULTS[fault] if fault else None)
        line = {"seed": seed, "fault": fault, "correct": res["correct"],
                "gap_mean": res["check"]["logit_gap_mean"]["value"],
                "limit": res["check"]["logit_gap_mean"]["limit"],
                "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                "memory_peak_bytes": res["device"]["memory_peak_bytes"],
                "seconds": time.perf_counter() - t0}
        if "control" in res:
            line["control"] = res["control"]
            line["control_correct"] = res["control"]["correct"]
        print(json.dumps(line), flush=True)
        sys.stderr.flush()


if __name__ == "__main__":
    main()
