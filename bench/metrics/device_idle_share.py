"""Share of the traced window in which no operation ran on the device:
1 - union of the device's op intervals / window (harness/xplane.py)."""


def read(run):
    if run.trace is None:
        return None
    t = run.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
