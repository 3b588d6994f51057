"""Idle time put down to the program's host spans, on a small recorded
profile: exact buckets, a straddling gap split, a capture's cut-off
parents restored, and the readers' share of the traced window."""
import os
from types import SimpleNamespace

import pytest

from bench import harness, host_spans, trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data")
US = 1000.0            # ns per microsecond


def _profile():
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "spans_trace.pbtxt")) as f:
        data = ProfileData.from_text_proto(f.read())
    return trace_reduce.load(data), host_spans.load(data)


def test_each_bucket_gets_the_idle_time_placed_in_it():
    devs, spans = _profile()
    # the loop thread's own event is no program span
    assert "host_work" not in {s[0] for s in spans}
    got = host_spans.split(devs, spans)
    # gaps (us): [8,10] under a round.sync whose step began before the
    # capture; [20,30] admit 2, prefill 3, round 5; [40,55] round 10,
    # harvest 5; [60,70] harvest 5, the step's own code 5; [80,95] that
    # code 10, fan-out 5; [100,105] a step.admit still open at the stop
    assert got == pytest.approx({"admit": 22e-6, "dispatch": 20e-6,
                                 "harvest": 10e-6, "frontend": 5e-6})


def test_the_buckets_add_up_to_the_idle_time_between_first_and_last_op():
    devs, spans = _profile()
    d = devs[0]
    first, last = trace_reduce.span_ns(d)
    idle = (last - first - trace_reduce.busy_ns(d)) * 1e-9
    assert idle == pytest.approx(57e-6)
    assert sum(host_spans.split(devs, spans).values()) == pytest.approx(idle)


def test_a_gap_that_straddles_two_spans_is_split():
    _, spans = _profile()
    edges, buckets = host_spans.segments(host_spans.restore_parents(spans))
    # [20, 30]: step.admit until 22, step.prefill to 25, step.round after
    assert host_spans.split_interval(edges, buckets, 20 * US, 30 * US) == \
        pytest.approx({"admit": 2 * US, "dispatch": 8 * US})
    # [80, 95]: server.step closes at 90, the fan-out runs after it
    assert host_spans.split_interval(edges, buckets, 80 * US, 95 * US) == \
        pytest.approx({"admit": 10 * US, "frontend": 5 * US})
    ops = [("a", 0.0, 20 * US), ("b", 30 * US, 31 * US)]
    assert host_spans.idle_by_bucket(ops, host_spans.restore_parents(
        spans)) == pytest.approx({"admit": 2 * US, "dispatch": 8 * US,
                                  "harvest": 0.0, "frontend": 0.0})


def test_cut_off_parents_are_restored_from_the_capture_edges():
    _, spans = _profile()
    restored = host_spans.restore_parents(spans)
    added = sorted(set(restored) - set(spans), key=lambda s: (s[1], s[0]))
    inf = float("inf")
    assert added == [("server.step", -inf, 12 * US),
                     ("step.round", -inf, 12 * US),
                     ("server.step", 96 * US, inf)]


def test_no_server_step_means_no_split():
    devs, spans = _profile()
    assert host_spans.split(devs, [s for s in spans
                                   if s[0] == "frontend.fanout"]) is None
    assert host_spans.split([], spans) is None


def test_round_sync_lags():
    devs, spans = _profile()
    # round.sync [0, 12] ends inside op [10, 20]: -8 us; [28, 50] after the
    # op [30, 40]: 10 us
    assert host_spans.sync_lags(devs[0], spans) == \
        pytest.approx([-8e-6, 10e-6])


def test_longest_gaps_carry_their_buckets():
    devs, spans = _profile()
    gaps = host_spans.longest_gaps(devs[0], spans, top=2)
    # [40, 55] and [80, 95] (us), 35 and 75 us after the first op at 5
    assert [g[:2] for g in gaps] == [pytest.approx([35e-6, 15e-6]),
                                     pytest.approx([75e-6, 15e-6])]
    assert gaps[0][2].startswith("fusion.1 f32[8] fusion")
    assert gaps[0][3] == pytest.approx({"dispatch": 10e-6, "harvest": 5e-6})
    assert gaps[1][3] == pytest.approx({"admit": 10e-6, "frontend": 5e-6})


@pytest.mark.parametrize("bucket", host_spans.BUCKETS)
def test_reader_shares_of_the_traced_window(bucket, tmp_path, monkeypatch):
    src = os.path.join(DATA, "spans_trace.pbtxt")
    devs, spans = _profile()
    monkeypatch.setattr(harness, "CACHE", tmp_path)
    monkeypatch.setattr(host_spans, "load", lambda path: spans)
    run = SimpleNamespace(window=SimpleNamespace(
        trace={"devices": devs, "summary": {"window_s": 200e-6}}))
    read = harness.reader(f"idle_{bucket}_share")
    assert read(run) is None                       # no profile written
    prof = tmp_path / "trace" / "plugins" / "profile" / "1"
    prof.mkdir(parents=True)
    with open(src) as f:
        (prof / "host.xplane.pb").write_text(f.read())
    want = {"admit": 22, "dispatch": 20, "harvest": 10, "frontend": 5}
    assert read(run) == pytest.approx(100.0 * want[bucket] * 1e-6 / 200e-6)
    assert read(SimpleNamespace(window=SimpleNamespace(trace=None))) is None
