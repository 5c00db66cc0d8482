"""A whole run on the CPU at a toy size: the result line, the metrics,
and the check that decides ``correct``, also with the timed path broken
underneath."""
import numpy as np
import pytest

import tiny
from harness.faults import FAULTS


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


@pytest.mark.parametrize("kind", ["closed", "open"])
def test_sound_run_is_correct(kind):
    res = tiny.run(tiny.cell(kind))
    assert res["correct"], res["check"]
    assert list(res)[-1] == "check"
    m = res["metrics"]
    assert m["output_tokens_per_s"]["value"] > 0
    assert m["itl_p95_ms"]["value"] > 0
    assert m["setup_s"]["value"] > 0
    assert ("ttft_p50_ms" in m) == (kind == "open")
    assert res["attempted"] > 0 and res["failed"] == 0


def test_pallas_path_is_correct():
    res = tiny.run(tiny.cell("closed", impl="pallas"))
    assert res["correct"], res["check"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("kind,requests", [("closed", 2), ("open", "all")])
def test_broken_path_is_not_correct(fault, kind, requests):
    """Open: every request served a token is compared, in flight or
    finished, so a fault confined to slot 0 is among them."""
    res = tiny.run(tiny.cell(kind, requests=requests),
                   engine_hook=FAULTS[fault])
    assert not res["correct"]
    assert res["check"]["logit_gap_mean"]["value"] > 10 * tiny.LIMIT


def test_control_fails_where_program_passes():
    """The control: the reference computed in fp8 in the program's place
    reads above the limit on the same prompts and tokens, and the
    comparison that decides ``correct`` says so."""
    res = tiny.run(tiny.cell("closed"), control=True)
    assert res["correct"]
    assert res["control"]["correct"] is False
    ctl = res["control"]["control_gap_mean"]
    assert ctl > 10 * tiny.LIMIT, ctl
    assert np.isfinite(res["control"]["gap_mean"])
