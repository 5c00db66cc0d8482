"""Drive ``repro.serving.Engine`` through its public loop, as a
streaming server would: submit what is due, ``poll()`` one engine step,
``sync()`` so that the host sees the tokens that step made, repeat.

Each poll is recorded with host-clock times and with what the engine's
public state says it did: how many select, reuse and prefill-chunk steps
(``EngineStats``), which slot was fed how many prompt tokens, which slot
decoded at what context, whose first token arrived (``Engine.batch``).
A token is delivered at the end of the ``sync()`` that follows the poll
that made it. Each poll and sync is wrapped in a profiler TraceAnnotation
(``bench.poll``, ``bench.sync``) so that a traced run can attribute
device time to them (harness/xplane.py).
"""
from __future__ import annotations

import dataclasses
import time

STAT_KEYS = ("engine_steps", "decode_steps", "select_steps", "reuse_steps",
             "prefill_chunks", "dispatches", "tokens_out", "admissions")


@dataclasses.dataclass
class Poll:
    t0: float            # poll() called
    t1: float            # poll() returned
    t2: float            # sync() returned: the step's tokens are delivered
    stats: dict          # EngineStats deltas of this poll
    decode_ctx: list     # context after append, per token decoded
    chunks: list         # (start, n) per slot fed prompt tokens
    first: int = 0       # prompts completed: first tokens delivered


@dataclasses.dataclass
class Req:
    uid: int
    due: float           # host clock
    prompt_len: int
    max_new: int
    submit: float = None
    admitted: float = None   # end of the poll that admitted it
    tokens: list = dataclasses.field(default_factory=list)  # delivery times


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def stats_now(eng) -> dict:
    """The EngineStats counters the benchmark reads."""
    return {k: getattr(eng.stats, k) for k in STAT_KEYS}


class Driver:
    def __init__(self, eng, clock=time.perf_counter):
        self.eng = eng
        self.clock = clock
        self.polls: list = []
        self.reqs: dict = {}
        b = eng.batch
        self._uid = b.uid.copy()
        self._len = b.lengths.copy()
        self._pref = b.prefilling.copy()

    def submit(self, arrival, due: float, request_cls):
        now = self.clock()
        with span("bench.submit"):
            self.eng.submit(request_cls(
                uid=arrival.uid, prompt=arrival.prompt,
                max_new=arrival.max_new, temperature=arrival.temperature))
        self.reqs[arrival.uid] = Req(uid=arrival.uid, due=due,
                                     prompt_len=len(arrival.prompt),
                                     max_new=arrival.max_new, submit=now)

    def poll(self):
        eng = self.eng
        before = stats_now(eng)
        t0 = self.clock()
        with span("bench.poll"):
            eng.poll()
        t1 = self.clock()
        with span("bench.sync"):
            eng.sync()
        t2 = self.clock()
        after = stats_now(eng)
        rec = Poll(t0=t0, t1=t1, t2=t2,
                   stats={k: after[k] - before[k] for k in STAT_KEYS},
                   decode_ctx=[], chunks=[])
        self._account(rec)
        self.polls.append(rec)
        return rec

    def _account(self, rec: Poll):
        """Who was fed, who decoded, whose first token arrived."""
        b = self.eng.batch
        uid, length, pref = b.uid, b.lengths, b.prefilling
        for s in range(len(uid)):
            u_before, u_after = int(self._uid[s]), int(uid[s])
            if u_after >= 0 and u_after != u_before:
                # admitted in this poll: the slot starts empty, prefilling
                u, l0, was_pref = u_after, 0, True
                self.reqs[u].admitted = rec.t2
            elif u_before >= 0:
                u, l0, was_pref = u_before, int(self._len[s]), \
                    bool(self._pref[s])
            else:
                continue
            n = int(length[s]) - l0
            if n <= 0:
                continue
            r = self.reqs[u]
            if was_pref:
                rec.chunks.append((l0, n))
                if not pref[s]:             # prompt done: first token
                    r.tokens.append(rec.t2)
                    rec.first += 1
            else:
                rec.decode_ctx.append(int(length[s]))
                r.tokens.append(rec.t2)
        self._uid = uid.copy()
        self._len = length.copy()
        self._pref = pref.copy()


def run_open(drv: Driver, arrivals, t_open: float, t_close: float,
             request_cls, on_tick=None):
    """Open loop: each arrival is submitted at t_open + due, whatever the
    engine is doing; the loop polls while there is work and sleeps until
    the next arrival when there is none. Returns when t_close passes.
    ``on_tick(now)`` runs between polls (window bookkeeping, profiler)."""
    eng = drv.eng
    i = 0
    while True:
        now = drv.clock()
        if on_tick is not None:
            on_tick(now)
        if now >= t_close:
            return
        while i < len(arrivals) and t_open + arrivals[i].due <= now:
            drv.submit(arrivals[i], t_open + arrivals[i].due, request_cls)
            i += 1
        if eng.busy():
            drv.poll()
            continue
        nxt = t_open + arrivals[i].due if i < len(arrivals) else t_close
        with span("bench.wait"):
            time.sleep(max(0.0, min(nxt, t_close) - now))


def fill(drv: Driver, arrivals, request_cls):
    """Closed set-up: submit every session, poll until each is decoding."""
    eng = drv.eng
    now = drv.clock()
    for a in arrivals:
        drv.submit(a, now, request_cls)
    b = eng.batch
    while eng.busy() and (b.prefilling.any() or b.ready.any() or any(
            r.admitted is None for r in drv.reqs.values())):
        drv.poll()
