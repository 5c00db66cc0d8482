"""Device time of the decode program in traced polls that ran a select
step, per such step (profiler trace, harness/xplane.py)."""
from harness.readers import DECODE, mean_ms


def read(run):
    return mean_ms(run, "select", DECODE)
