"""Observability layer (repro.obs): span tracing, round events, drift.

Four contracts:
  * Tracer — spans nest, export to valid Chrome-trace JSON, record
    nothing when disabled (a profiler annotation only), and land on a
    captured jax.profiler trace, nested as the server step runs them;
  * RoundEventLog — alpha_hat() reproduces ServingMetrics.alpha_hat()
    exactly (same per-row EMA, unclamped) from typed RoundEvents;
  * DriftMonitor — flags an injected 2x verify slowdown, stays quiet when
    measurements match the cost model, and survives compile-priced rounds
    in its calibration window (unit ratchets down to the fastest verify);
  * traced serving — the paged server under an enabled tracer emits
    draft/verify/commit spans covering the serve wall time, produces the
    SAME tokens as the untraced fused round, and calibrates a drift
    monitor whose evidence re-enters the Planner (respec_from_drift).
"""
import glob
import json
import math
import os

import jax
import numpy as np
import pytest

from repro.api import DeploymentSpec, Planner, respec_from_drift
from repro.configs import registry
from repro.core import cost_model
from repro.models.model import build_model
from repro.obs import (NULL_TRACER, DriftConfig, DriftMonitor, RoundEvent,
                       RoundEventLog, Tracer)
from repro.obs.clock import ManualClock
from repro.serving import (PagedSpecServer, SchedulerConfig, ServeRequest,
                           ServingMetrics)

# ---------------------------------------------------------------------- tracer


def test_span_nesting_and_durations():
    clk = ManualClock()
    tr = Tracer(clock=clk)
    with tr.span("outer", phase="serve", role="host"):
        clk.advance(1.0)
        with tr.span("inner", phase="draft", role="drafter", round=3):
            clk.advance(0.25)
        clk.advance(0.5)
    spans = tr.spans()
    assert [s.name for s in spans] == ["inner", "outer"]  # close order
    inner, outer = spans
    assert inner.depth == 1 and outer.depth == 0
    assert inner.duration == pytest.approx(0.25)
    assert outer.duration == pytest.approx(1.75)
    assert inner.tags["round"] == 3
    assert tr.total(phase="draft") == pytest.approx(0.25)
    assert tr.count(role="host") == 1
    assert tr.phase_totals() == {"serve": pytest.approx(1.75),
                                 "draft": pytest.approx(0.25)}


def test_chrome_trace_export(tmp_path):
    clk = ManualClock()
    tr = Tracer(clock=clk)
    with tr.span("verify", phase="verify", role="target"):
        clk.advance(0.002)
    with tr.span("draft", phase="draft", role="drafter"):
        clk.advance(0.001)
    path = tmp_path / "trace.json"
    tr.export(str(path))
    doc = json.loads(path.read_text())
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {e["name"] for e in events} == {"verify", "draft"}
    assert {m["args"]["name"] for m in meta} == {"target", "drafter"}
    # roles map to distinct timeline rows; times are microseconds
    assert len({e["tid"] for e in events}) == 2
    v = next(e for e in events if e["name"] == "verify")
    assert v["ts"] == pytest.approx(0.0) and v["dur"] == pytest.approx(2000.0)
    assert v["cat"] == "verify"


def test_disabled_tracer_is_noop():
    clk = ManualClock()
    reads = []
    tr = Tracer(enabled=False, clock=lambda: reads.append(1) or clk())
    s1 = tr.span("a", phase="draft")
    s2 = tr.span("b", role="host", round=3)
    with s1:
        with s2:
            clk.advance(1.0)
    # only a profiler annotation: no clock read, nothing in the ring
    assert s1.duration == 0.0 and s2.duration == 0.0
    assert reads == []
    assert tr.spans() == [] and tr.count() == 0
    assert tr.phase_totals() == {}
    # the module singleton every default flows through
    assert not NULL_TRACER.enabled
    with NULL_TRACER.span("x"):
        pass
    assert NULL_TRACER.spans() == []


def test_ring_buffer_bounds_memory():
    clk = ManualClock()
    tr = Tracer(clock=clk, capacity=4)
    for i in range(10):
        with tr.span(f"s{i}"):
            clk.advance(1.0)
    spans = tr.spans()
    assert len(spans) == 4
    assert [s.name for s in spans] == ["s6", "s7", "s8", "s9"]


# ---------------------------------------------------------------- round events


def test_round_event_alpha_and_hist_parity():
    """RoundEventLog subsumes ServingMetrics' round counters: identical
    alpha EMA (unclamped, per live row) and acceptance histogram."""
    rng = np.random.default_rng(0)
    m = ServingMetrics(gamma_max=6, alpha_ema=0.9, now=ManualClock())
    log = RoundEventLog(alpha_ema=0.9)
    B = 4
    for k in range(40):
        gamma = int(rng.integers(0, 9))          # 0 = AR; up to 8 > gamma_max
        acc = (rng.integers(0, gamma + 1, B) if gamma > 0
               else np.zeros(B, np.int64))
        active = rng.random(B) < 0.8
        if not active.any():
            active[0] = True
        rids = [int(10 + b) if live else None
                for b, live in enumerate(active)]
        m.record_round(acc, gamma, active=active, rids=rids)
        live_acc = tuple(int(a) for a, l in zip(acc, active) if l)
        log.record(RoundEvent(round=k, gamma=gamma, n_active=len(live_acc),
                              accepted=live_acc,
                              emitted=sum(live_acc) + len(live_acc),
                              t_round=1e-3))
    assert m.alpha_hat() is not None
    assert log.alpha_hat() == pytest.approx(m.alpha_hat())
    np.testing.assert_array_equal(log.accept_hist(6), m.accept_hist)
    assert log.n_rounds == m.n_rounds
    assert log.n_spec_rounds == m.n_spec_rounds


def test_round_event_alpha_round_and_phase_means():
    log = RoundEventLog()
    for k in range(3):
        log.record(RoundEvent(round=k, gamma=4, n_active=2, accepted=(2, 4),
                              emitted=8, t_round=0.01, t_draft=0.004,
                              blocks_read=12, rids=(1, 2), t_wall=1000.0 + k))
    assert [ev.round for ev in log.events()] == [0, 1, 2]
    assert log.events()[0].alpha_round == pytest.approx(0.75)
    assert log.phase_means()["t_draft"] == pytest.approx(0.004)


# ----------------------------------------------------------------------- drift

_CFG = DriftConfig(ema=0.9, tol=0.2, warmup_rounds=0, calibration_rounds=2,
                   min_samples=3)
_UNIT = 0.01                               # clean t_target: 10 ms


def _clean_round(gamma=4, c=0.25):
    h = cost_model.DISPATCH_OVERHEAD_DEFAULT
    return dict(t_draft=gamma * c * _UNIT, t_verify=_UNIT, t_commit=1e-3,
                t_round=cost_model.round_time(gamma, c, h) * _UNIT)


def test_drift_flags_injected_verify_slowdown():
    mon = DriftMonitor(gamma=4, c=0.25, cfg=_CFG)
    for _ in range(_CFG.calibration_rounds):
        mon.observe(**_clean_round())
    assert mon.calibrated and mon.unit == pytest.approx(_UNIT)
    slow = _clean_round()
    slow["t_verify"] = 2 * _UNIT                  # inject the 2x slowdown
    slow["t_round"] += _UNIT
    for _ in range(4):
        mon.observe(**slow)
    rep = mon.report()
    assert rep["verify"]["flagged"]
    assert rep["verify"]["rel_err"] == pytest.approx(1.0, abs=0.05)
    assert not rep["draft"]["flagged"]            # the drafter is innocent
    msgs = mon.alerts()
    assert any("verify" in m for m in msgs)
    assert any("+100%" in m for m in msgs)


def test_drift_quiet_when_model_holds():
    mon = DriftMonitor(gamma=4, c=0.25, cfg=_CFG)
    for _ in range(10):
        mon.observe(**_clean_round())
    assert mon.calibrated
    assert mon.alerts() == []
    for comp, r in mon.report().items():
        assert not r["flagged"], comp
        assert abs(r["rel_err"]) < 0.05, comp


def test_drift_unit_survives_compile_priced_calibration():
    """The first rounds pay XLA compilation; the unit must come from the
    fastest (clean) sample, not the compile-inflated mean."""
    cfg = DriftConfig(ema=0.9, tol=0.2, warmup_rounds=1, calibration_rounds=3)
    mon = DriftMonitor(gamma=4, c=0.25, cfg=cfg)
    mon.observe(t_verify=50 * _UNIT, t_draft=50 * _UNIT)   # warmup: dropped
    mon.observe(t_verify=20 * _UNIT, t_draft=_UNIT)        # recompile round
    mon.observe(t_verify=_UNIT, t_draft=_UNIT)
    mon.observe(t_verify=_UNIT, t_draft=_UNIT)
    assert mon.calibrated and mon.unit == pytest.approx(_UNIT)
    # a later, even faster verify refines the unit downward...
    mon.observe(t_verify=0.8 * _UNIT)
    assert mon.unit == pytest.approx(0.8 * _UNIT)
    # ...but a slowdown never raises it (it must show as drift instead)
    mon.observe(t_verify=3 * _UNIT)
    assert mon.unit == pytest.approx(0.8 * _UNIT)


def test_drift_evidence_feeds_replanning():
    mon = DriftMonitor(gamma=4, c=0.25, cfg=_CFG)
    spec = DeploymentSpec(batch_size=1, prompt_lens=(8,), max_new=16,
                          alpha=0.8, cost_coefficient=0.25,
                          adaptive_gamma=False)
    assert respec_from_drift(spec, None) is spec
    assert respec_from_drift(spec, mon) is spec          # no evidence yet
    # measured reality: drafting costs 2x the planned c
    for _ in range(6):
        mon.observe(t_draft=4 * 0.5 * _UNIT, t_verify=_UNIT,
                    t_round=(4 * 0.5 + 1.05) * _UNIT)
    ev = mon.evidence()
    assert ev["c"] == pytest.approx(0.5, rel=0.05)
    spec2 = respec_from_drift(spec, mon, alpha=0.7)
    assert spec2.cost_coefficient is None                # planner re-derives
    assert spec2.t_draft == pytest.approx(ev["t_draft"])
    assert spec2.t_target == pytest.approx(ev["t_target"])
    assert spec2.alpha == pytest.approx(0.7)
    plan = Planner(spec2).plan()
    assert plan.cost_coefficient == pytest.approx(0.5, rel=0.05)


# --------------------------------------------------------------- metrics fixes


def test_metrics_count_actual_tokens_not_budget():
    clk = ManualClock(100.0)
    m = ServingMetrics(gamma_max=4, now=clk)
    m.submit(0, prompt_len=5, max_new=10)
    m.start(0)
    clk.advance(2.0)
    rec = m.complete(0, n_generated=4)       # EOS'd early: 4 of 10 produced
    assert rec.n_generated == 4
    assert rec.decode_tps == pytest.approx(2.0)
    assert m.total_generated == 4
    assert m.summary()["aggregate_tokens_per_s"] == pytest.approx(2.0)


def test_metrics_no_inf_at_zero_wall():
    m = ServingMetrics(now=ManualClock(5.0))     # time never advances
    m.submit(0, prompt_len=3, max_new=8)
    m.start(0)
    rec = m.complete(0, n_generated=8)
    assert math.isnan(rec.decode_tps)            # 0-second decode: undefined
    s = m.summary()
    assert s["aggregate_tokens_per_s"] is None   # not inf
    assert s["total_generated_tokens"] == 8


# ------------------------------------------------------- traced serving (e2e)

RAGGED = [(5, 8), (9, 12), (6, 4), (13, 10), (7, 6), (4, 9), (11, 5)]


def _pair(arch):
    cfg_t = registry.smoke_config(arch)
    cfg_d = cfg_t.replace(num_layers=max(1, cfg_t.num_layers - 1),
                          name="draft")
    mt, md = build_model(cfg_t), build_model(cfg_d)
    return (mt, md, mt.init(jax.random.PRNGKey(0)),
            md.init(jax.random.PRNGKey(7)), cfg_t)


def _wave(cfg, seed):
    return [ServeRequest(i, np.random.default_rng(seed + i)
                         .integers(0, cfg.vocab_size, P), new)
            for i, (P, new) in enumerate(RAGGED)]


def test_traced_paged_serving_end_to_end(tmp_path):
    """The acceptance bar: a traced paged run exports valid Chrome-trace
    JSON whose phase spans cover the serve wall time (within 10% after
    warmup), emits per-round events, calibrates the drift monitor — and
    generates EXACTLY the tokens the untraced fused round generates."""
    mt, md, pt, pd, cfg = _pair("llama3.2-1b")
    scfg = SchedulerConfig(max_batch=3, block_size=4, num_blocks=64,
                           max_blocks_per_row=12, gamma_max=6,
                           prefill_buckets=(8, 16))
    tracer = Tracer()
    warm = PagedSpecServer(mt, md, pt, pd, scfg, tracer=tracer)
    for r in _wave(cfg, 0):
        warm.submit(r)
    warm.run()                                   # pays XLA compilation
    tracer.clear()

    traced = PagedSpecServer(mt, md, pt, pd, scfg, tracer=tracer)
    for r in _wave(cfg, 100):
        traced.submit(r)
    done = traced.run()
    assert sorted(r.rid for r in done) == list(range(len(RAGGED)))

    # token identity: tracing phase-splits the round but must not change it
    untraced = PagedSpecServer(mt, md, pt, pd, scfg)
    for r in _wave(cfg, 100):
        untraced.submit(r)
    ref = {r.rid: np.asarray(r.tokens) for r in untraced.run()}
    for r in done:
        np.testing.assert_array_equal(np.asarray(r.tokens), ref[r.rid])

    # span coverage: leaf phases account for the serve wall time
    totals = tracer.phase_totals()
    leaf = sum(v for k, v in totals.items() if k != "serve")
    serve = tracer.total(name="serve")
    assert serve > 0
    assert 0.9 * serve <= leaf <= 1.02 * serve
    for phase in ("draft", "verify", "commit", "prefill"):
        assert tracer.count(phase=phase) > 0, phase

    # export is loadable Chrome-trace JSON with the three round phases
    path = tmp_path / "trace.json"
    tracer.export(str(path))
    doc = json.loads(path.read_text())
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert {"draft", "verify", "commit", "serve"} <= names

    # per-round events carry phase times and agree with the metrics EMA
    events = traced.events.events()
    assert len(events) == traced.total_rounds
    spec_evs = [e for e in events if e.gamma > 0]
    assert spec_evs and all(e.t_draft is not None and e.t_verify is not None
                            for e in spec_evs)
    assert traced.events.alpha_hat() == pytest.approx(
        traced.metrics.alpha_hat())

    # drift monitor calibrated off the run and produced planner evidence
    assert traced.drift is not None and traced.drift.calibrated
    ev = traced.drift.evidence()
    assert ev is not None and 0 < ev["c"] < 2.0
    assert traced.events.n_rounds == traced.metrics.n_rounds


# ----------------------------------------------- spans on the profiler's clock

STEP_SPANS = ("server.step", "step.admit", "step.prefill", "step.tables",
              "step.round", "round.dispatch", "round.sync", "step.harvest",
              "harvest.pull")


def _profile_spans(log_dir):
    """(name, start_ns, end_ns, stats) of the server step's spans on the
    host plane of the newest profile under ``log_dir``."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                     dict(ev.stats))
                    for ev in line.events if ev.name in STEP_SPANS]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_server_step_spans_land_in_the_profile(tmp_path):
    """With the tracer OFF, a profiled run still shows every step's host
    work as nested spans on the host plane, with the round tags equal to
    the RoundEvent ids, and serves the unprofiled tokens."""
    mt, md, pt, pd, cfg = _pair("llama3.2-1b")
    scfg = SchedulerConfig(max_batch=2, block_size=4, num_blocks=32,
                           max_blocks_per_row=8, gamma_max=4,
                           prefill_chunk=4)
    wave = _wave(cfg, 5)[:3]

    def serve():
        srv = PagedSpecServer(mt, md, pt, pd, scfg, gamma=2)
        srv.collect_streams = True          # the streaming pull runs too
        for r in wave:
            srv.submit(ServeRequest(r.rid, r.prompt, r.max_new))
        return srv, {r.rid: np.asarray(r.tokens) for r in srv.run()}

    _, ref = serve()                        # pays compilation
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        srv, got = serve()
    finally:
        jax.profiler.stop_trace()
    assert got.keys() == ref.keys()
    for rid in ref:
        np.testing.assert_array_equal(got[rid], ref[rid])

    spans = _profile_spans(str(tmp_path))
    steps = [s for s in spans if s[0] == "server.step"]
    assert [s[3]["step"] for s in steps] == list(range(srv.total_steps))
    rounds = [s for s in spans if s[0] == "step.round"]
    assert [s[3]["round"] for s in rounds] == \
        [ev.round for ev in srv.events.events()]
    assert all(s[3]["gamma"] == 2 for s in rounds)
    for r in rounds:
        step = next(s for s in steps if _inside(r, s))
        kids = {c[0] for c in spans if c is not step and _inside(c, step)}
        assert {"step.admit", "step.prefill", "step.tables", "step.round",
                "round.dispatch", "round.sync", "step.harvest"} <= kids
        assert sum(c[0] in ("round.dispatch", "round.sync")
                   and _inside(c, r) for c in spans) == 2
    harvests = [s for s in spans if s[0] == "step.harvest"]
    pulls = [s for s in spans if s[0] == "harvest.pull"]
    assert pulls and all(any(_inside(p, h) for h in harvests)
                         for p in pulls)
    # nothing else opens inside the dispatch or the sync
    for leaf in (s for s in spans if s[0] in ("round.dispatch",
                                               "round.sync")):
        assert not any(c is not leaf and _inside(c, leaf) for c in spans)


def test_prefill_chunk_span_times_t_prefill():
    """An enabled tracer's ``prefill_chunk`` spans and the round events'
    ``t_prefill`` time one interval: per step that ran a round, the
    chunk spans inside its ``server.step`` sum to its event's t_prefill."""
    mt, md, pt, pd, cfg = _pair("llama3.2-1b")
    scfg = SchedulerConfig(max_batch=3, block_size=4, num_blocks=64,
                           max_blocks_per_row=12, gamma_max=6,
                           prefill_chunk=4)
    tracer = Tracer()
    srv = PagedSpecServer(mt, md, pt, pd, scfg, tracer=tracer)
    for r in _wave(cfg, 11):
        srv.submit(r)
    srv.run()
    spans = tracer.spans()

    def inside(c, p):
        return p.t0 <= c.t0 and c.t1 <= p.t1

    chunk_t, chunk_n = {}, {}
    for step in (s for s in spans if s.name == "server.step"):
        rnd = [s for s in spans if s.name == "step.round" and inside(s, step)]
        if not rnd:
            continue                   # no round, so no event to compare
        chunks = [s for s in spans
                  if s.name == "prefill_chunk" and inside(s, step)]
        chunk_t[rnd[0].tags["round"]] = sum(c.duration for c in chunks)
        chunk_n[rnd[0].tags["round"]] = len(chunks)
    events = srv.events.events()
    assert sorted(chunk_t) == [ev.round for ev in events]
    assert [chunk_n[ev.round] for ev in events] == \
        [ev.prefill_chunks for ev in events]
    t_prefill = sum(ev.t_prefill or 0.0 for ev in events)
    assert t_prefill > 0
    assert sum(chunk_t.values()) == pytest.approx(t_prefill, rel=0.05)
