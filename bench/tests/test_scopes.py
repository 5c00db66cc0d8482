"""Attribution inside the program (bench/harness/scopes.py): device time
per scope and idle time per innermost span, on a hand-made trace with
known answers, on the two recorded traces that carry no scopes (which
must read as bench/harness/xplane.py reads them), and on the HLO protos
of a CPU trace."""
import glob
import os
import types

import pytest

from harness import scopes, spec, xplane

MS = 1_000_000
G = "jit(decode)/while/body/closed_call/"


def _trace():
    ops = [("while.9", 1.5, 4.5, "jit(decode)/while"),
           ("fusion.1", 1.5, 1.0, G + "attn.gather/concatenate"),
           ("copy.2", 2.5, 1.5, ""),
           ("paged_attention.3", 4.0, 2.0,
            G + "attn.retrieval/jit(paged_attention)/paged_attention"),
           ("fusion.4", 6.0, 1.0, "jit(sample)/sample/argmax"),
           ("chunk_attention_paged.5", 11.0, 2.0,
            "jit(chunk_step)/while/body/closed_call/attn.prefill/x"),
           ("fusion.6", 13.0, 1.0, "jit(chunk_step)/while/body/ffn/dot")]
    mods = [("jit_decode(7)", 1.5, 4.5), ("jit_sample(9)", 6.0, 1.0),
            ("jit_chunk_step(8)", 11.0, 3.0)]
    host = [("bench.poll", 0, 4), ("bench.sync", 4, 6),
            ("bench.poll", 10, 3), ("bench.sync", 13, 3)]
    engine = [("engine.poll", 0.5, 3.0), ("engine.admit", 0.5, 0.5),
              ("engine.decode", 1.0, 1.0), ("engine.sample", 2.0, 1.0),
              ("engine.account", 3.0, 0.5), ("engine.sync", 4.5, 5.0),
              ("engine.poll", 10.5, 2.0), ("engine.chunk", 10.5, 1.5),
              ("engine.sync", 13.0, 2.5)]
    ns = lambda xs: [(n, int(s * MS), int(d * MS)) for n, s, d in xs]
    return scopes.Trace(
        ops={0: ns([o[:3] for o in ops])}, modules={0: ns(mods)},
        host=ns(host), engine=ns(engine),
        op_scopes={0: [o[3] for o in ops]})


def test_reduce_nested_known_trace():
    ev = _trace()
    red = scopes.reduce(ev)
    base = xplane.reduce(xplane.Events(ops=ev.ops, modules=ev.modules,
                                       host=ev.host))
    for k in ("window_s", "busy_s", "polls", "device_ops"):
        assert red[k] == base[k], k
    assert red["poll_scopes"][0] == pytest.approx({
        "decode/attn.gather": 1e-3, "decode/unscoped": 1.5e-3,
        "decode/attn.retrieval": 2e-3, "sample/sample": 1e-3})
    assert red["poll_scopes"][1] == pytest.approx({
        "chunk_step/attn.prefill": 2e-3, "chunk_step/ffn": 1e-3})
    # per scope, unscoped included, sums to the leaf time of each poll
    # (the while over the layers is not a leaf)
    for got, leaf in zip(red["poll_scopes"], (5.5e-3, 3e-3)):
        assert sum(got.values()) == pytest.approx(leaf)
    assert red["device_scopes"][0] == ["decode/attn.retrieval",
                                       pytest.approx(2e-3)]
    # idle [0,1.5) [7,11) [14,16), each instant charged to the innermost
    # span over it
    assert dict(red["idle_gaps"]) == pytest.approx({
        "engine.sync": 4e-3, "bench.poll": 1e-3, "bench.sync": 1e-3,
        "engine.admit": 0.5e-3, "engine.decode": 0.5e-3,
        "engine.chunk": 0.5e-3})
    assert sum(t for _, t in red["idle_gaps"]) == pytest.approx(
        red["window_s"] - red["busy_s"])
    # inside engine.poll: [0.5,1.5) and [10.5,11)
    assert red["idle_host_s"] == pytest.approx(1.5e-3)


def _run(red, kinds):
    keys = ("select_steps", "reuse_steps", "prefill_chunks")
    polls = [types.SimpleNamespace(stats={k: int(k == kind) for k in keys})
             for kind in kinds]
    return types.SimpleNamespace(trace=red, traced_polls=polls)


def test_readers_on_known_trace():
    red = scopes.reduce(_trace())
    run = _run(red, ("reuse_steps", "prefill_chunks"))
    assert scopes.reuse_step_unscoped_ms(run) == pytest.approx(1.5)
    assert scopes.reuse_step_attention_ms(run) == pytest.approx(3.0)
    assert scopes.chunk_step_attention_ms(run) == pytest.approx(2.0)
    assert scopes.idle_host_share(run) == pytest.approx(100 * 1.5 / 16)
    # a select poll is no reuse poll
    run = _run(red, ("select_steps", "prefill_chunks"))
    assert scopes.reuse_step_unscoped_ms(run) is None


def test_no_engine_spans_reads_as_xplane():
    ev = _trace()
    ev.engine = []
    red = scopes.reduce(ev)
    assert red["idle_gaps"] == xplane.reduce(ev)["idle_gaps"]
    assert red["idle_host_s"] is None


def test_json_round_trip(tmp_path):
    ev = _trace()
    p = tmp_path / "t.json"
    scopes.dump(ev, str(p))
    back = scopes.Trace.from_json(spec.load_json(p))
    assert scopes.reduce(back) == scopes.reduce(ev)


# every key of xplane.reduce on the traces recorded before the program
# carried spans or scopes, as they read then
PINNED = {
    "trace_chat": dict(
        window_s=0.967040046, busy_s=0.9614916240000001, polls=2,
        chunk_step=0.36228358400000005, decode=0.09098363300000001,
        top_op=["chunk_step/chunk_attention_paged.12",
                0.24156375300000002],
        idle_gaps=[["bench.sync", 0.003918024],
                   ["bench.poll", 0.001468258],
                   ["host.other", 0.00013203000000000002],
                   ["bench.submit", 3.011e-05]]),
    "trace_mistral": dict(
        window_s=0.33651643200000003, busy_s=0.328164287, polls=6,
        chunk_step=None, decode=0.05063502,
        top_op=["decode/bitcast_dynamic-update-slice_fusion.13",
                0.039233500000000004],
        idle_gaps=[["bench.sync", 0.005889923],
                   ["bench.poll", 0.0020256420000000002],
                   ["host.other", 0.00043658000000000005]]),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_old_recorded_trace_reads_as_before(name):
    d = spec.load_json(spec.BENCH / "tests" / "data" / f"{name}.json")
    old = xplane.reduce(xplane.Events.from_json(d))
    red = scopes.reduce(scopes.Trace.from_json(d))
    for k, v in old.items():
        assert red[k] == v, k
    pin = PINNED[name]
    assert red["window_s"] == pin["window_s"]
    assert red["busy_s"] == pin["busy_s"]
    assert len(red["polls"]) == pin["polls"]
    assert red["polls"][0].get("chunk_step") == pin["chunk_step"]
    assert red["polls"][0]["decode"] == pin["decode"]
    assert red["device_ops"][0] == pin["top_op"]
    assert red["idle_gaps"] == pin["idle_gaps"]
    assert red["scopes"] == {} and red["idle_host_s"] is None
    assert red["poll_scopes"] == [{}] * pin["polls"]


def test_op_names_from_hlo_protos(tmp_path):
    """The profiler writes each program's HLO into the trace; the wire
    reader finds the scope of its instructions there."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("qkv"):
            y = jnp.tanh(x) * 2
        return y + 1

    x = jnp.ones((8,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    with open(path, "rb") as fh:
        names = scopes.program_op_names(fh.read())
    prog = [k for k in names if k.startswith("jit_f(")]
    assert len(prog) == 1
    paths = names[prog[0]].values()
    assert any(scopes.scope_of(p) == "qkv" for p in paths)
    assert scopes.scope_of("jit(f)/qkvx/tanh") == scopes.UNSCOPED


def _kinds(poll_scopes):
    """Per-poll step counts (EngineStats deltas), read back from the
    scopes: a decode with ``attn.select`` is a select step, else a reuse
    step."""
    out = []
    for ps in poll_scopes:
        sel = "decode/attn.select" in ps
        dec = any(k.startswith("decode/") for k in ps)
        chunk = any(k.startswith("chunk_step/") for k in ps)
        out.append(types.SimpleNamespace(stats={
            "select_steps": int(sel), "reuse_steps": int(dec and not sel),
            "prefill_chunks": int(chunk)}))
    return out


# readings on the first polls of a traced chip run of each cell (TPU v5e,
# ``bench/scope_run.py --keep-trace``): reuse unscoped and attention ms,
# chunk attention ms, idle host share %
RECORDED = {
    "trace_chat_scoped": (26.991441, 62.604637, 188.0654285,
                          0.4453959411),
    "trace_mistral_scoped": (42.106032, 4.2183858, None, 1.2818396037),
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_scoped_trace(name):
    ev = scopes.Trace.from_json(
        spec.load_json(spec.BENCH / "tests" / "data" / f"{name}.json"))
    red = scopes.reduce(ev)
    polls = xplane._polls(ev)
    # per scope, unscoped included, sums to the leaf time of each poll
    leaves = xplane._leaves(ev.ops[0])
    for (p0, p1), ps in zip(polls, red["poll_scopes"]):
        leaf = sum(d for _, s, d in leaves if p0 <= s < p1) * 1e-9
        assert sum(ps.values()) == pytest.approx(leaf, rel=1e-9)
    # the decode program's leaf ops cover its time on the Modules line
    run = types.SimpleNamespace(trace=red,
                                traced_polls=_kinds(red["poll_scopes"]))
    for p, dev, ps in zip(run.traced_polls, red["polls"],
                          red["poll_scopes"]):
        if p.stats["reuse_steps"]:
            dec = sum(v for k, v in ps.items() if k.startswith("decode/"))
            assert dec == pytest.approx(dev["decode"], rel=0.01)
    names = [n for n, _ in red["idle_gaps"]]
    assert "engine.sync" in names and "engine.decode" in names
    assert sum(t for _, t in red["idle_gaps"]) == pytest.approx(
        red["window_s"] - red["busy_s"])
    got = (scopes.reuse_step_unscoped_ms(run),
           scopes.reuse_step_attention_ms(run),
           scopes.chunk_step_attention_ms(run), scopes.idle_host_share(run))
    for g, want in zip(got, RECORDED[name]):
        assert g == (None if want is None else pytest.approx(want))
