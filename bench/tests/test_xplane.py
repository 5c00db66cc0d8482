"""The trace reduction, on a hand-made trace with known answers and on
a small trace recorded on a TPU v5e (first polls of a traced run of
each cell, kept with ``bench/run.py --keep-trace``)."""
import pytest

from harness import spec, xplane


def _events():
    ms = 1_000_000
    ops = [("fusion.1", 0 * ms, 2 * ms), ("fusion.2", 1 * ms, 2 * ms),
           ("copy.3", 6 * ms, 1 * ms), ("fusion.1", 12 * ms, 3 * ms)]
    mods = [("jit_decode(7)", 0, 3 * ms), ("jit_sample(9)", 6 * ms, ms),
            ("jit_chunk_step(8)", 12 * ms, 3 * ms)]
    host = [("bench.poll", 0, 1 * ms), ("bench.sync", 1 * ms, 9 * ms),
            ("bench.poll", 10 * ms, 1 * ms), ("bench.sync", 11 * ms, 5 * ms),
            ("bench.wait", 16 * ms, 4 * ms)]
    return xplane.Events(ops={0: ops}, modules={0: mods}, host=host)


def test_reduce_known_trace():
    red = xplane.reduce(_events())
    assert red["window_s"] == pytest.approx(16e-3)
    # union of [0,3) [6,7) [12,15)
    assert red["busy_s"] == pytest.approx(7e-3)
    assert red["polls"][0] == pytest.approx({"decode": 3e-3,
                                             "sample": 1e-3})
    assert red["polls"][1] == pytest.approx({"chunk_step": 3e-3})
    assert red["device_ops"][0] == ["chunk_step/fusion.1", pytest.approx(3e-3)]
    idle = dict(red["idle_gaps"])
    # gaps [3,6) [7,10) in sync, [10,11) poll, [11,12) sync, [15,16) sync
    assert idle["bench.sync"] == pytest.approx(8e-3)
    assert idle["bench.poll"] == pytest.approx(1e-3)


def test_json_round_trip(tmp_path):
    ev = _events()
    p = tmp_path / "t.json"
    xplane.dump(ev, str(p))
    back = xplane.Events.from_json(spec.load_json(p))
    assert xplane.reduce(back) == xplane.reduce(ev)


@pytest.mark.parametrize("name", ["trace_chat", "trace_mistral"])
def test_recorded_tpu_trace(name):
    path = spec.BENCH / "tests" / "data" / f"{name}.json"
    red = xplane.reduce(xplane.Events.from_json(spec.load_json(path)))
    assert 0 < red["busy_s"] <= red["window_s"]
    kinds = set().union(*red["polls"])
    assert "decode" in kinds
    assert red["device_ops"] and red["idle_gaps"]
