#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Looks the cell up in BENCHMARK.json (bench/harness/spec.py), makes the
weights and the traffic from the seed, sets up the serving engine and
warms up the cell's own shapes, measures for ``--seconds``, checks what
the window served against the plain reference, and prints as the last
line of standard output one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics untraced, its
per-layer metrics with ``--trace 1``), ``device`` (and ``breakdown``
when traced), then ``check``: each compared number with its limit.
Everything else goes to standard error. Exits non-zero, with no result,
when JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the first polls of the trace as a "
                         "small JSON event record (for bench/tests)")
    return ap.parse_args(argv)


def tpu_devices(chips: int):
    """The first ``chips`` TPU devices; exits 3 when there are fewer."""
    import jax
    devs = [d for d in jax.devices() if d.platform == "tpu"]
    if len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s), JAX found "
              f"{[d.platform for d in jax.devices()]}", file=sys.stderr)
        sys.exit(3)
    return devs[:chips]


def main(argv=None):
    args = parse(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    from harness import runner, spec
    from harness.peaks import peaks_for

    cell = spec.load_cell(args.workload)
    devices = tpu_devices(cell.chips)
    peaks = peaks_for(devices[0].device_kind)
    result = runner.run(cell, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace), devices=devices, peaks=peaks,
                        t_start=T_START, keep_trace=args.keep_trace)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
