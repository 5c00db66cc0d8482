"""95th percentile, over the requests due inside the window, of the time
from scheduled arrival to the end of the poll that admitted the request
into a slot; one not admitted by the end of the window enters with its
wait so far."""
from harness.readers import waits_ms
from harness.stats import percentile


def read(run):
    w = waits_ms(run, "admitted")
    return percentile(w, 95) if w else None
