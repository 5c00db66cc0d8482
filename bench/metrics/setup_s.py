"""Process start to window open: weights, engine, compile or compile-cache
loads, warm-up, and the prefill or arrival lead the cell needs."""


def read(run):
    return run.setup_s
