"""Output tokens delivered inside the window per second of the window.
A token is delivered when the sync after the poll that made it returns."""


def read(run):
    n = sum(1 for r in run.reqs.values() for t in r.tokens
            if run.t_open <= t <= run.t_end)
    return n / run.window_s
