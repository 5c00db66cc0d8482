"""Median, over the requests due inside the window, of the time from a
request's scheduled arrival to its first delivered token. A request with
no token by the end of the window enters with its wait so far."""
from harness.readers import waits_ms
from harness.stats import percentile


def read(run):
    w = waits_ms(run, "first")
    return percentile(w, 50) if w else None
