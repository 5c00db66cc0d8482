"""Shared by the readers of bench/metrics: request waits, and device
time of one program in the traced polls of one kind."""

DECODE = "decode"          # runtime/serve.py make_ragged_decode_step
CHUNK = "chunk_step"       # runtime/serve.py make_prefill_chunk_step


def polls_of(run, kind: str):
    """(poll, device seconds per program) of the traced polls that ran a
    select step, a reuse step, or a prefill chunk."""
    key = {"select": "select_steps", "reuse": "reuse_steps",
           "chunk": "prefill_chunks"}[kind]
    return [(p, dev) for p, dev in zip(run.traced_polls, run.trace["polls"])
            if p.stats[key] > 0]


def mean_ms(run, kind: str, program: str):
    if run.trace is None:
        return None
    ts = [dev[program] for _, dev in polls_of(run, kind) if program in dev]
    return 1e3 * sum(ts) / len(ts) if ts else None


def waits_ms(run, until: str):
    """Host-clock wait of each request due inside the window, from its
    scheduled arrival to its first token (``until="first"``) or to the
    end of the poll that admitted it (``"admitted"``); a request that
    has not got there by the end of the window enters with its wait so
    far."""
    out = []
    for r in run.reqs.values():
        if run.t_open <= r.due < run.t_close:
            t = r.tokens[0] if until == "first" and r.tokens else (
                r.admitted if until == "admitted" else None)
            if t is None or t > run.t_end:
                t = run.t_end
            out.append((t - r.due) * 1e3)
    return out
