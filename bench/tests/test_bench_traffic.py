"""The benchmark's traffic generator: seeded, clipped, the same work on
every seed."""
import numpy as np
import pytest

from bench import harness, loadgen

OPEN = {"loop": "open", "rate_rps": 3.0, "batch": 4, "block_size": 16,
        "quantiles": 16,
        "prefill_chunk": 32,
        "prompt": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                   "min": 32, "max": 512},
        "output": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                   "min": 64, "max": 1024}}
CLOSED = dict(OPEN, loop="closed", clients=8, requests=64)


def _key(trace):
    return [(r.idx, r.due_s, r.max_new, r.prompt.tolist()) for r in trace]


@pytest.mark.parametrize("mix", [OPEN, CLOSED], ids=["open", "closed"])
def test_same_seed_same_trace(mix):
    a = loadgen.make_trace(mix, 2 ** 40 + 3, 30.0, 1000)
    b = loadgen.make_trace(mix, 2 ** 40 + 3, 30.0, 1000)
    c = loadgen.make_trace(mix, 2 ** 40 + 4, 30.0, 1000)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


@pytest.mark.parametrize("mix", [OPEN, CLOSED], ids=["open", "closed"])
def test_lengths_stay_in_their_clips(mix):
    trace = loadgen.make_trace(mix, 5, 60.0, 1000)
    P = [len(r.prompt) for r in trace]
    O = [r.max_new for r in trace]
    assert min(P) >= 32 and max(P) <= 512
    assert min(O) >= 64 and max(O) <= 1024
    assert all(0 <= t < 1000 for r in trace for t in r.prompt)


@pytest.mark.parametrize("mix", [OPEN, CLOSED], ids=["open", "closed"])
def test_every_seed_sends_the_same_work(mix):
    """The same schedule on every seed, other prompt tokens; the open
    loop's arrivals span exactly n / rate."""
    a = loadgen.make_trace(mix, 1, 30.0, 1000)
    b = loadgen.make_trace(mix, 2, 30.0, 1000)
    sched = lambda t: [(r.due_s, len(r.prompt), r.max_new) for r in t]  # noqa
    assert sched(a) == sched(b)
    if mix["loop"] == "open":
        assert len(a) == 90
        assert a[0].due_s == 0.0 and a[-1].due_s < 30.0
        gaps = np.diff([r.due_s for r in a] + [30.0])
        assert gaps.sum() == pytest.approx(30.0)


def test_closed_loop_blocks_hold_the_same_lengths():
    a = loadgen.make_trace(CLOSED, 1, 30.0, 1000)
    b = loadgen.make_trace(CLOSED, 2, 30.0, 1000)
    for k in range(0, 64, 16):
        blk = lambda t: sorted((len(r.prompt), r.max_new)  # noqa
                               for r in t[k:k + 16])
        assert blk(a) == blk(b)


def test_quantile_lengths_follow_the_distribution():
    spec = {"dist": "lognormal", "median": 100, "sigma": 0.5, "min": 1,
            "max": 10 ** 6}
    v = loadgen.quantile_lengths(spec, 1001)
    assert v[500] == 100
    assert list(v) == sorted(v)
    u = loadgen.quantile_lengths({"dist": "uniform", "min": 10, "max": 19},
                                 10)
    assert list(u) == list(range(10, 20))


def test_geometry_holds_the_worst_request():
    mx = harness.mix("shortchat")
    g = harness.geometry(mx)
    worst = mx["prompt"]["max"] + mx["output"]["max"] + 8 + 1
    assert g["max_blocks_per_row"] * g["block_size"] >= worst
    assert g["num_blocks"] - 1 >= mx["batch"] * g["max_blocks_per_row"]
