"""Reduce a profiler trace to device busy time, device time per program
and per benchmark host span, and idle gaps by what the host was doing.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.
``load`` reads it with ``jax.profiler.ProfileData`` into plain event
lists (``Events``), which ``to_json``/``from_json`` keep as a small
recorded trace for the tests. Times are nanoseconds on the trace's
clock; device and host events share it.

* Device planes are ``/device:TPU:<i>``: their "XLA Ops" line holds one
  event per operation that ran, their "XLA Modules" line one event per
  program execution (named after the jitted function).
* Host spans are the ``bench.*`` TraceAnnotations that the benchmark's
  own driver puts around each ``Engine.poll()`` (``bench.poll``), each
  ``Engine.sync()`` (``bench.sync``), submissions (``bench.submit``) and
  waits for arrivals (``bench.wait``).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class Events:
    # per device index: ops and module executions, (name, start, dur)
    ops: dict
    modules: dict
    host: list           # bench.* spans: (name, start, dur)

    def to_json(self) -> dict:
        return {"ops": {str(k): v for k, v in self.ops.items()},
                "modules": {str(k): v for k, v in self.modules.items()},
                "host": self.host}

    @classmethod
    def from_json(cls, d: dict) -> "Events":
        tup = lambda xs: [tuple(x) for x in xs]
        return cls(ops={int(k): tup(v) for k, v in d["ops"].items()},
                   modules={int(k): tup(v) for k, v in d["modules"].items()},
                   host=tup(d["host"]))


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {paths}")
    return paths[0]


def load(path: str, devices) -> Events:
    import jax

    prof = jax.profiler.ProfileData.from_file(path)
    ops, modules, host, seen = {}, {}, [], []
    for plane in prof.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in devices:
            dev = int(m.group(1))
            lines = {ln.name: ln for ln in plane.lines}
            seen.append((plane.name, sorted(lines)))
            for name, dest in ((OPS_LINE, ops), (MODULES_LINE, modules)):
                if name not in lines:
                    raise RuntimeError(
                        f"{plane.name} has no {name!r} line: {sorted(lines)}")
                dest[dev] = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                             for ev in lines[name].events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith("bench."):
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)))
    if sorted(ops) != sorted(devices):
        raise RuntimeError(f"device planes for {sorted(devices)} not all "
                           f"found in the trace: {seen}")
    host.sort(key=lambda e: e[1])
    return Events(ops=ops, modules=modules, host=host)


def merge(intervals):
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def covered(merged, lo, hi) -> int:
    """Length of merged intervals inside [lo, hi)."""
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged)


def op_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    m = re.match(r"%?([^\s=]+)", name)
    return m.group(1) if m else name


def module_kind(name: str) -> str:
    """Program name without the jit_ prefix and any (id) suffix."""
    name = re.sub(r"\(.*\)$", "", name).strip()
    return name[4:] if name.startswith("jit_") else name


def _leaves(ops):
    """Ops that contain no other op: a ``while`` over the layers is
    listed with the ops of its body, which are what the time went to."""
    ops = sorted(ops, key=lambda e: (e[1], -e[2]))
    return [op for op, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] >= op[1] + op[2]]


def _polls(ev: Events):
    """(poll span, following sync span) pairs, in order."""
    out, pending = [], None
    for name, s, d in ev.host:
        if name == "bench.poll":
            pending = (s, s + d)
        elif name == "bench.sync" and pending is not None:
            out.append((pending[0], s + d))
            pending = None
    return out


def reduce(ev: Events, top: int = 10) -> dict:
    """Busy/idle over the traced window, device time per program inside
    each poll, the busiest operations, and idle time by host activity.

    The traced window runs from the first ``bench.poll`` span to the end
    of the last ``bench.sync`` span. Device numbers are averaged over the
    devices in the trace."""
    polls = _polls(ev)
    if not polls:
        raise RuntimeError("no bench.poll/bench.sync spans in the trace")
    lo, hi = polls[0][0], polls[-1][1]
    devs = sorted(ev.ops)
    busy, op_time, idle = 0, {}, {}
    per_poll = [dict() for _ in polls]
    host = sorted((s, s + d, n) for n, s, d in ev.host if s < hi and s + d > lo)
    host_starts = [h[0] for h in host]
    for dev in devs:
        merged = merge((s, s + d) for _, s, d in ev.ops[dev] if d > 0)
        busy += covered(merged, lo, hi)
        mods = sorted((s, s + d, module_kind(n))
                      for n, s, d in ev.modules[dev])
        starts = [m[0] for m in mods]
        for name, s, d in _leaves(ev.ops[dev]):
            if s >= lo and s + d <= hi:
                i = bisect.bisect_right(starts, s) - 1
                mod = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
                key = f"{mod}/{op_name(name)}"
                op_time[key] = op_time.get(key, 0) + d
        j = 0
        for i, (p0, p1) in enumerate(polls):
            while j < len(mods) and mods[j][1] <= p0:
                j += 1
            k = j
            while k < len(mods) and mods[k][0] < p1:
                s, e, kind = mods[k]
                t = max(0, min(e, p1) - max(s, p0))
                per_poll[i][kind] = per_poll[i].get(kind, 0) + t / len(devs)
                k += 1
        # idle gaps inside the window, charged to the host spans over them
        cur = lo
        for s, e in merged + [[hi, hi]]:
            s, e = max(s, lo), min(e, hi)
            if s > cur:
                _charge(idle, host, host_starts, cur, s, len(devs))
            cur = max(cur, e)
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": busy / len(devs) * ns,
        "polls": [{k: v * ns for k, v in p.items()} for p in per_poll],
        "device_ops": sorted(([n, t / len(devs) * ns]
                              for n, t in op_time.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(([n, t * ns] for n, t in idle.items()),
                            key=lambda x: -x[1])[:top],
    }


def _charge(idle: dict, host, starts, g0: int, g1: int, n_dev: int):
    """Charge the idle gap [g0, g1) to the host spans over it (spans do
    not overlap), and the rest to ``host.other``."""
    left = g1 - g0
    i = bisect.bisect_left(starts, g1) - 1
    while i >= 0 and host[i][1] > g0:
        s, e, name = host[i]
        t = max(0, min(e, g1) - max(s, g0))
        if t:
            idle[name] = idle.get(name, 0) + t / n_dev
            left -= t
        i -= 1
    if left > 0:
        idle["host.other"] = idle.get("host.other", 0) + left / n_dev


def dump(ev: Events, path: str, max_polls: int = 12):
    """Keep the first ``max_polls`` polls of a trace as a small recorded
    trace (JSON) for the tests."""
    polls = _polls(ev)[:max_polls]
    lo, hi = polls[0][0], polls[-1][1]
    keep = lambda xs: [x for x in xs if x[1] + x[2] > lo and x[1] < hi]
    short = lambda xs: [(op_name(n), s, d) for n, s, d in keep(xs)]
    small = Events(ops={k: short(v) for k, v in ev.ops.items()},
                   modules={k: keep(v) for k, v in ev.modules.items()},
                   host=keep(ev.host))
    with open(path, "w") as f:
        json.dump(small.to_json(), f)
