"""95th percentile of the gaps between consecutive delivered tokens of a
request, over every request, both tokens delivered inside the window."""
from harness.stats import percentile


def read(run):
    gaps = []
    for r in run.reqs.values():
        ts = [t for t in r.tokens if run.t_open <= t <= run.t_end]
        gaps.extend(b - a for a, b in zip(ts, ts[1:]))
    return percentile(gaps, 95) * 1e3 if gaps else None
