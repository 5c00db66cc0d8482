"""Device time of the prefill-chunk program in traced polls that fed a
prompt chunk, per chunk step (profiler trace, harness/xplane.py)."""
from harness.readers import CHUNK, mean_ms


def read(run):
    return mean_ms(run, "chunk", CHUNK)
