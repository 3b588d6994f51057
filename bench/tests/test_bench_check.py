"""The output check, driven through a whole run on the CPU at a tiny size
(the harness's look for a chip skipped): the served path passes, the
float8 control fails, and each fault of ``bench/faults.py`` planted
underneath fails."""
import time

import jax
import pytest

from bench import faults, harness

DATA = harness.BENCH / "tests" / "data"
CFG = harness.load_json(DATA / "tiny.sd1.json")
MIX = harness.load_json(DATA / "tiny_open.json")
BENCH = {"workloads": [{"name": "tiny", "config": "tiny.sd1",
                        "traffic": "tiny_open", "chips": 1}],
         "end_to_end": harness.benchmark()["end_to_end"], "per_layer": []}
LIMIT = CFG["check"]["widest_logit_gap"]


def _run(seed, **kw):
    return harness.run("tiny", seed, 3.0, False, jax.devices(), time.time(),
                       bench=BENCH, cfg=CFG, mx=MIX, **kw)


def test_served_path_passes_and_the_control_fails():
    out = _run(2 ** 35 + 17, control=True)
    assert out["correct"], out["checks"]
    assert out["checks"]["widest_logit_gap"]["value"] <= LIMIT
    assert not out["control"]["correct"]
    assert max(out["control"]["gaps"]) > LIMIT
    assert set(out["metrics"]) == {
        m["name"] for m in harness.metrics_for(BENCH, "tiny", "end_to_end")}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_broken_round_is_not_correct(fault, monkeypatch):
    monkeypatch.setattr(harness, "WARM_TIMEOUT_S", 5.0)
    out = _run(5, after_build=lambda s: faults.FAULTS[fault](s, CFG))
    assert not out["correct"], out["checks"]
