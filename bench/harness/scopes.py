"""Attribution inside the program, from a profiler trace: device time per
scope of the step programs, and idle time per innermost host span.

The program marks its own structure on the profiler's clock:

* device scopes: ``jax.named_scope`` around each sub-layer of the decode,
  select, prefill-chunk and sample programs (``SCOPES``). They live in
  the HLO ``op_name`` metadata only; the layer ``lax.scan`` itself is
  under none, so its slicing, stacking and copying of the per-layer serve
  state, and the copies XLA inserts, read as ``unscoped``;
* host spans: ``repro.serving.Engine`` opens one ``engine.poll`` step
  span per ``poll()`` with a child per phase (``engine.admit``,
  ``engine.schedule``, ``engine.chunk``, ``engine.decode``,
  ``engine.sample``, ``engine.account``), and ``engine.sync`` inside
  ``sync()``.

This module reads both and changes nothing of bench/harness/xplane.py:
``load`` returns what ``xplane.load`` returns (``ops``, ``modules`` and
the ``bench.*`` spans in ``host``, unchanged) plus ``engine``, the
``engine.*`` spans, and ``op_scopes``, the ``op_name`` of each op in
``ops``. The profiler's Python reader gives an XLA Ops event its
instruction and no metadata, so the ``op_name`` of each instruction is
read from the HLO protos that the profiler writes into the
``/host:metadata`` plane, one per program, with a small protobuf wire
reader (``_fields``).

``reduce`` returns ``xplane.reduce``'s dict, every key as there, plus
``poll_scopes`` (per traced poll, device seconds per ``program/scope``;
aligned with ``polls``, to be truncated with it), ``scopes`` (the
window's device seconds per ``program/scope``), ``device_scopes`` (the
top ones), ``idle_host_s`` (device idle while the host is inside
``engine.poll``), and ``idle_gaps`` charged to the innermost span over
each gap, ``engine.*`` spans included (as ``xplane.reduce`` has it when
the trace holds no ``engine.*`` span). Leaf ops only, as ``device_ops``.
"""
from __future__ import annotations

import bisect
import dataclasses
import json
import struct

from harness import xplane

SCOPES = ("embed", "qkv", "attn.append", "attn.select", "attn.gather",
          "attn.retrieval", "attn.streaming", "attn.prefill", "attn.out",
          "ffn", "head", "sample")
UNSCOPED = "unscoped"
METADATA_PLANE = "/host:metadata"
ENGINE = "engine."


@dataclasses.dataclass
class Trace(xplane.Events):
    engine: list = dataclasses.field(default_factory=list)
    # per device index: the op_name of each op of ``ops[dev]``
    op_scopes: dict = dataclasses.field(default_factory=dict)

    def to_json(self) -> dict:
        d = super().to_json()
        table = sorted({p for v in self.op_scopes.values() for p in v})
        idx = {p: i for i, p in enumerate(table)}
        d["engine"] = self.engine
        d["op_name_table"] = table
        d["op_scopes"] = {str(k): [idx[p] for p in v]
                          for k, v in self.op_scopes.items()}
        return d

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        ev = xplane.Events.from_json(d)
        table = d.get("op_name_table", [])
        return cls(ops=ev.ops, modules=ev.modules, host=ev.host,
                   engine=[tuple(x) for x in d.get("engine", [])],
                   op_scopes={int(k): [table[i] for i in v]
                              for k, v in d.get("op_scopes", {}).items()})


# --------------------------------------------------------------------------
# protobuf wire format, as much as the XSpace and HloProto messages need
# --------------------------------------------------------------------------


def _varint(b, i):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b):
    """(field number, value) of a serialized message: an int for
    varints, bytes for fixed-width and length-delimited fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _first(b, num):
    return next((v for f, v in _fields(b) if f == num), None)


def _stat_value(stat: bytes):
    """XStat: metadata_id = 1, then one of double (2), uint64 (3),
    int64 (4), str (5), bytes (6), ref (7)."""
    mid, val = None, None
    for f, v in _fields(stat):
        if f == 1:
            mid = v
        elif f == 2:
            val = struct.unpack("<d", v)[0]
        elif f in (3, 4, 7):
            val = v
        elif f in (5, 6):
            val = bytes(v)
    return mid, val


def hlo_op_names(hlo_proto: bytes) -> dict:
    """HloProto -> {instruction name: op_name}, over every computation
    (HloProto.hlo_module = 1; HloModuleProto.computations = 3;
    HloComputationProto.instructions = 2; HloInstructionProto.name = 1,
    .metadata = 7; OpMetadata.op_name = 2)."""
    out = {}
    module = _first(hlo_proto, 1)
    for f, comp in _fields(module or b""):
        if f != 3:
            continue
        for g, inst in _fields(comp):
            if g != 2:
                continue
            name, path = None, ""
            for h, v in _fields(inst):
                if h == 1:
                    name = bytes(v).decode()
                elif h == 7:
                    path = bytes(_first(v, 2) or b"").decode()
            out[name] = path
    return out


def program_op_names(data: bytes) -> dict:
    """{program name as on the XLA Modules line: {instruction: op_name}}
    from the HLO protos of the ``/host:metadata`` plane (XSpace.planes =
    1; XPlane.name = 2, .event_metadata = 4 (map id -> XEventMetadata),
    .stat_metadata = 5; XEventMetadata.name = 2, .stats = 5)."""
    out = {}
    for f, plane in _fields(memoryview(data)):
        if f != 1 or bytes(_first(plane, 2) or b"") != \
                METADATA_PLANE.encode():
            continue
        stat_names, metas = {}, []
        for g, v in _fields(plane):
            if g == 5:
                meta = _first(v, 2)
                stat_names[_first(meta, 1)] = bytes(
                    _first(meta, 2) or b"").decode()
            elif g == 4:
                metas.append(_first(v, 2))
        for meta in metas:
            name = bytes(_first(meta, 2) or b"").decode()
            for g, stat in _fields(meta):
                if g != 5:
                    continue
                mid, val = _stat_value(stat)
                if stat_names.get(mid) == "Hlo Proto" and val:
                    out[name] = hlo_op_names(memoryview(val))
    return out


# --------------------------------------------------------------------------
# loading and recording
# --------------------------------------------------------------------------


def load(path: str, devices) -> Trace:
    import jax

    ev = xplane.load(path, devices)
    engine = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(ENGINE):
                        engine.append((e.name, int(e.start_ns),
                                       int(e.duration_ns)))
    engine.sort(key=lambda e: e[1])
    with open(path, "rb") as f:
        names = program_op_names(f.read())
    op_scopes = {}
    if names:
        for dev, ops in ev.ops.items():
            mods = sorted((s, s + d, n) for n, s, d in ev.modules[dev])
            starts = [m[0] for m in mods]
            paths = []
            for name, s, _ in ops:
                i = bisect.bisect_right(starts, s) - 1
                prog = mods[i][2] if i >= 0 and s < mods[i][1] else None
                paths.append(names.get(prog, {}).get(xplane.op_name(name),
                                                     ""))
            op_scopes[dev] = paths
    return Trace(ops=ev.ops, modules=ev.modules, host=ev.host,
                 engine=engine, op_scopes=op_scopes)


def dump(ev: Trace, path: str, max_polls: int = 12):
    """Keep the first ``max_polls`` polls as a small recorded trace (JSON),
    as ``xplane.dump`` does, with the engine spans and op scopes."""
    polls = xplane._polls(ev)[:max_polls]
    lo, hi = polls[0][0], polls[-1][1]
    inside = lambda x: x[1] + x[2] > lo and x[1] < hi
    keep = lambda xs: [x for x in xs if inside(x)]
    ops, scopes = {}, {}
    for dev, xs in ev.ops.items():
        kept = [i for i, x in enumerate(xs) if inside(x)]
        ops[dev] = [(xplane.op_name(xs[i][0]), xs[i][1], xs[i][2])
                    for i in kept]
        if dev in ev.op_scopes:
            scopes[dev] = [ev.op_scopes[dev][i] for i in kept]
    small = Trace(ops=ops, modules={k: keep(v) for k, v in ev.modules.items()},
                  host=keep(ev.host), engine=keep(ev.engine),
                  op_scopes=scopes)
    with open(path, "w") as f:
        json.dump(small.to_json(), f)


# --------------------------------------------------------------------------
# reduction
# --------------------------------------------------------------------------


def scope_of(op_name: str) -> str:
    """The innermost path segment of ``op_name`` that is a scope of the
    program (segments match exactly), else ``unscoped``."""
    hit = [s for s in op_name.split("/") if s in SCOPES]
    return hit[-1] if hit else UNSCOPED


def _innermost(spans):
    """Disjoint (start, end, name) segments naming, at each instant, the
    innermost of the nested (name, start, dur) spans over it; a span
    that outlasts the one it started in is cut at that one's end."""
    out, stack, t = [], [], None

    def pop_until(x):
        nonlocal t
        while stack and stack[-1][0] <= x:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    for name, s, d in sorted(spans, key=lambda x: (x[1], -x[2])):
        e = s + d
        if t is not None:
            pop_until(s)
            if stack and s > t:
                out.append((t, s, stack[-1][1]))
        t = s if t is None else max(t, s)
        if stack:
            e = min(e, stack[-1][0])
        stack.append((e, name))
    if stack:
        pop_until(float("inf"))
    return out


def _idle_gaps(merged, lo, hi):
    cur, out = lo, []
    for s, e in merged + [[hi, hi]]:
        s, e = max(s, lo), min(e, hi)
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    return out


def reduce(ev: Trace, top: int = 10) -> dict:
    red = xplane.reduce(ev, top)
    polls = xplane._polls(ev)
    lo, hi = polls[0][0], polls[-1][1]
    p_starts = [p[0] for p in polls]
    devs = sorted(ev.ops)
    n_dev = len(devs)
    per_poll = [dict() for _ in polls]
    window = {}
    innermost = _innermost(
        [x for x in ev.host + ev.engine if x[1] < hi and x[1] + x[2] > lo])
    seg_starts = [s[0] for s in innermost]
    in_poll = xplane.merge((s, s + d) for n, s, d in ev.engine
                           if n == "engine.poll")
    idle, idle_host = {}, 0
    for dev in devs:
        scoped = ev.op_scopes.get(dev)
        if scoped:
            mods = sorted((s, s + d, xplane.module_kind(n))
                          for n, s, d in ev.modules[dev])
            m_starts = [m[0] for m in mods]
            ops = [(scope_of(p), s, d) for (_, s, d), p
                   in zip(ev.ops[dev], scoped)]
            for scope, s, d in xplane._leaves(ops):
                if not (s >= lo and s + d <= hi):
                    continue
                i = bisect.bisect_right(m_starts, s) - 1
                mod = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
                key = f"{mod}/{scope}"
                window[key] = window.get(key, 0) + d / n_dev
                j = bisect.bisect_right(p_starts, s) - 1
                if j >= 0 and s < polls[j][1]:
                    per_poll[j][key] = per_poll[j].get(key, 0) + d / n_dev
        merged = xplane.merge((s, s + d) for _, s, d in ev.ops[dev] if d > 0)
        for g0, g1 in _idle_gaps(merged, lo, hi):
            idle_host += xplane.covered(in_poll, g0, g1) / n_dev
            left = g1 - g0
            k = max(bisect.bisect_right(seg_starts, g0) - 1, 0)
            while k < len(innermost) and innermost[k][0] < g1:
                s, e, name = innermost[k]
                t = max(0, min(e, g1) - max(s, g0))
                if t:
                    idle[name] = idle.get(name, 0) + t / n_dev
                    left -= t
                k += 1
            if left > 0:
                idle["host.other"] = idle.get("host.other", 0) + left / n_dev
    ns = 1e-9
    red["poll_scopes"] = [{k: v * ns for k, v in p.items()}
                          for p in per_poll]
    red["scopes"] = {k: v * ns for k, v in window.items()}
    red["device_scopes"] = sorted(([k, v * ns] for k, v in window.items()),
                                  key=lambda x: -x[1])[:top]
    red["idle_host_s"] = idle_host * ns if ev.engine else None
    if ev.engine:
        red["idle_gaps"] = sorted(([n, t * ns] for n, t in idle.items()),
                                  key=lambda x: -x[1])[:top]
    return red


# --------------------------------------------------------------------------
# readings for per-layer metrics (bench/metrics/<name>.py would call these)
# --------------------------------------------------------------------------

_KIND = {"select": "select_steps", "reuse": "reuse_steps",
         "chunk": "prefill_chunks"}


def step_scope_ms(run, kind: str, program: str, pick) -> float:
    """Mean, over the traced polls of ``kind`` in which ``program`` ran,
    of the device ms of its leaf ops whose scope ``pick`` accepts; None
    without scopes in the trace."""
    t = run.trace
    if t is None or not t.get("scopes"):
        return None
    vals = []
    for p, dev, ps in zip(run.traced_polls, t["polls"], t["poll_scopes"]):
        if p.stats[_KIND[kind]] > 0 and program in dev:
            vals.append(sum(v for k, v in ps.items()
                            if k.split("/", 1)[0] == program
                            and pick(k.split("/", 1)[1])))
    return 1e3 * sum(vals) / len(vals) if vals else None


def reuse_step_unscoped_ms(run):
    """ms per traced reuse step of ``decode`` leaf ops under no scope."""
    return step_scope_ms(run, "reuse", "decode", lambda s: s == UNSCOPED)


def reuse_step_attention_ms(run):
    """ms per traced reuse step of ``decode`` leaf ops under ``attn.*``."""
    return step_scope_ms(run, "reuse", "decode",
                         lambda s: s.startswith("attn."))


def chunk_step_attention_ms(run):
    """ms per traced chunk step of ``chunk_step`` leaf ops under
    ``attn.*``."""
    return step_scope_ms(run, "chunk", "chunk_step",
                         lambda s: s.startswith("attn."))


def idle_host_share(run):
    """% of the traced window in which the device is idle while the host
    is inside ``engine.poll``."""
    t = run.trace
    if t is None or t.get("idle_host_s") is None:
        return None
    return 100.0 * t["idle_host_s"] / t["window_s"]
