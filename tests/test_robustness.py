"""Robustness suite (docs/DESIGN.md §9): preemptive overcommit, graceful
degradation, and the seeded fault-injection chaos layer.

The invariants every scenario must uphold, no matter what the fault plan
does to the pool, the drafter, or the clock:

  * ZERO LEAKED BLOCKS — after the queue drains (and seized blocks are
    returned) the allocator audit balances: free == num_blocks - 1.
  * BYTE-IDENTICAL OUTPUT — completed requests match their standalone greedy
    AR continuation, whether or not they were preempted, degraded to AR
    mid-batch, or raced a fault. Preemption-by-eviction recomputes the
    committed prefix, so greedy decode resumes exactly.
  * EVERY REQUEST TERMINAL — completed + cancelled + expired + failed +
    rejected accounts for every submission; nothing wedges in the queue or
    a slot.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core.engine import autoregressive_generate
from repro.models.model import build_model
from repro.obs.clock import ManualClock
from repro.serving import (FaultPlan, PagedSpecServer, RoundWatchdog,
                           SchedulerConfig, ServeRequest)


@pytest.fixture(scope="module")
def pair():
    cfg_t = registry.smoke_config("llama3.2-1b")
    cfg_d = cfg_t.replace(num_layers=max(1, cfg_t.num_layers - 1),
                          name="draft")
    mt, md = build_model(cfg_t), build_model(cfg_d)
    return (mt, md, mt.init(jax.random.PRNGKey(0)),
            md.init(jax.random.PRNGKey(7)), cfg_t)


RAGGED = [(5, 12), (7, 10), (6, 11), (8, 9), (5, 12)]


def _requests(cfg, shapes=RAGGED, seed=0):
    """Fresh ServeRequest objects every call — the server mutates them
    (tokens, resume_tokens, preemptions), so runs must never share them."""
    rng = np.random.default_rng(seed)
    return [ServeRequest(i, rng.integers(0, cfg.vocab_size, P), new)
            for i, (P, new) in enumerate(shapes)]


def _overcommit_cfg(**kw):
    return SchedulerConfig(**{
        "max_batch": 3, "block_size": 4, "num_blocks": 16,
        "max_blocks_per_row": 8, "gamma_max": 4,
        "prefill_buckets": (8, 16, 32), "overcommit": 2.0, **kw})


def _assert_pool_whole(srv):
    """The zero-leak acceptance invariant, via the allocator's own census."""
    srv.alloc.release_seized()
    if srv.prefix_pool is not None:
        srv.prefix_pool.flush()
    assert srv.alloc.audit() == {
        "free": srv.scfg.num_blocks - 1, "live": 0, "cached": 0, "seized": 0}


def _assert_matches_ar(mt, pt, done):
    for r in done:
        ref = autoregressive_generate(
            mt, pt, jnp.asarray(np.asarray(r.prompt)[None]), r.max_new)
        np.testing.assert_array_equal(r.tokens, np.asarray(ref[0]))


def _assert_all_terminal(srv, n_submitted):
    s = srv.metrics.summary()
    terminal = (s["requests_completed"] + s["requests_cancelled"]
                + s["requests_expired"] + s["requests_failed"]
                + s["requests_rejected"])
    assert terminal == n_submitted
    assert not srv.metrics.requests          # no open record left behind
    assert not srv.sched.queue and all(r is None for r in srv._slots)


# ----------------------------------------------------------- overcommit
def test_overcommit_preempts_and_resumes_byte_identical(pair):
    """A pool too small for three worst cases + overcommit admission: rows
    must grow into each other, victims must be evicted mid-flight, and every
    completed request must STILL equal its standalone greedy continuation —
    the recompute half of preemption-by-eviction is exact."""
    mt, md, pt, pd, cfg = pair
    scfg = _overcommit_cfg()
    # worst case per request = P + new + gamma_max + 1 = 22 tokens = 6 blocks;
    # 3 resident worst cases need 18 > 15 allocatable -> preemption must fire
    srv = PagedSpecServer(mt, md, pt, pd, scfg)
    for r in _requests(cfg):
        srv.submit(r)
    done = srv.run()
    assert sorted(r.rid for r in done) == list(range(len(RAGGED)))
    assert srv.metrics.n_preemptions > 0
    assert srv.metrics.recompute_tokens > 0
    # at least one COMPLETED request lived through an eviction
    assert any(r.preemptions > 0 for r in srv.metrics.completed)
    _assert_matches_ar(mt, pt, done)
    _assert_all_terminal(srv, len(RAGGED))
    _assert_pool_whole(srv)


def test_overcommit_off_never_preempts(pair):
    """overcommit == 1.0 reserves the worst case: the same traffic on the
    same pool must serialize admissions instead of ever evicting."""
    mt, md, pt, pd, cfg = pair
    srv = PagedSpecServer(mt, md, pt, pd, _overcommit_cfg(overcommit=1.0))
    for r in _requests(cfg):
        srv.submit(r)
    done = srv.run()
    assert sorted(r.rid for r in done) == list(range(len(RAGGED)))
    assert srv.metrics.n_preemptions == 0
    _assert_pool_whole(srv)


def test_validate_rejects_unresumable_under_overcommit(pair):
    """Under overcommit the committed prefix can reach prompt+max_new-1 and
    must be re-prefillable: a request whose resume prefix exceeds the largest
    bucket is rejected at submit, not stranded by its first eviction."""
    mt, md, pt, pd, cfg = pair
    scfg = _overcommit_cfg(prefill_buckets=(8, 16), num_blocks=32)
    srv = PagedSpecServer(mt, md, pt, pd, scfg)
    with pytest.raises(ValueError, match="overcommit"):
        srv.submit(ServeRequest(0, np.zeros(8, np.int32), 12))  # 8+12-1 > 16
    assert srv.metrics.rejected and srv.metrics.rejected[0][0] == 0


# ---------------------------------------------------------------- chaos
def test_seeded_chaos_run_keeps_all_invariants(pair):
    """The headline chaos test: a seeded schedule of virtual delays, drafter
    failures, and transient pool seizures runs against the overcommitted
    server. Every request must finish, byte-identical to the fault-free run
    of the same traffic, with the pool whole afterward."""
    mt, md, pt, pd, cfg = pair
    scfg = _overcommit_cfg(max_batch=2, num_blocks=24, overcommit=1.5)

    def run(faults=None):
        srv = PagedSpecServer(mt, md, pt, pd, scfg, faults=faults)
        for r in _requests(cfg, seed=4):
            srv.submit(r)
        srv.run()
        return srv

    clean = run()
    plan = FaultPlan.seeded(5, horizon=256, p_delay=0.2, delay_s=0.05,
                            p_drafter=0.15, p_seize=0.2, max_seize=3)
    assert not plan.empty
    chaos = run(plan)

    # the schedule actually intersected the run (keyed by step index)
    fault_steps = (set(plan.delay_rounds) | set(plan.drafter_fail_rounds)
                   | set(plan.pool_deltas))
    assert any(s < chaos.total_steps for s in fault_steps)

    _assert_all_terminal(chaos, len(RAGGED))
    assert chaos.metrics.summary()["requests_completed"] == len(RAGGED)
    _assert_pool_whole(chaos)

    # byte-identity: faults may reorder/preempt/degrade, never change tokens
    ref = {r.rid: r.tokens for r in clean.done}
    for r in chaos.done:
        np.testing.assert_array_equal(r.tokens, ref[r.rid])
    _assert_matches_ar(mt, pt, chaos.done)


def test_drafter_fault_degrades_batch_to_ar(pair):
    """An injected drafter exception mid-batch must degrade that batch to AR
    (one-way spec->AR) with the reason recorded — and the outputs must not
    change."""
    mt, md, pt, pd, cfg = pair
    scfg = SchedulerConfig(max_batch=2, block_size=4, num_blocks=64,
                           max_blocks_per_row=12, gamma_max=4,
                           prefill_buckets=(8, 16))
    plan = FaultPlan(drafter_fail_rounds=frozenset({1}))
    srv = PagedSpecServer(mt, md, pt, pd, scfg, gamma=2, faults=plan)
    for r in _requests(cfg, shapes=[(6, 10), (9, 12)], seed=1):
        srv.submit(r)
    done = srv.run()
    reasons = [why for _, why in srv.metrics.degradations]
    assert any("injected drafter failure" in why for why in reasons)
    assert srv.metrics.n_rounds > srv.metrics.n_spec_rounds  # AR rounds ran
    _assert_matches_ar(mt, pt, done)
    _assert_pool_whole(srv)


def test_non_injected_round_error_propagates(pair):
    """Only the injected DrafterFault degrades a batch: any other error in
    the speculative round (a kernel that fails to lower, a dead donated
    buffer) leaves ``step`` instead of passing for a slow AR batch."""
    mt, md, pt, pd, cfg = pair
    scfg = SchedulerConfig(max_batch=2, block_size=4, num_blocks=64,
                           max_blocks_per_row=12, gamma_max=4,
                           prefill_buckets=(8, 16))
    srv = PagedSpecServer(mt, md, pt, pd, scfg, gamma=2)

    def broken_round(*_):
        raise RuntimeError("Mosaic lowering failed")

    srv._engine(2)._round_jit = broken_round
    for r in _requests(cfg, shapes=[(6, 10)], seed=1):
        srv.submit(r)
    with pytest.raises(RuntimeError, match="Mosaic lowering failed"):
        while srv.step() is not None:
            pass
    assert not srv.metrics.degradations and srv.metrics.n_rounds == 0


def test_watchdog_trips_on_straggling_rounds(pair):
    """Virtual fault delays inflate t_round past the watchdog threshold: the
    batch must degrade to AR with a 'watchdog' reason, and outputs stay
    exact. No real sleeping — the delays are injected into telemetry."""
    mt, md, pt, pd, cfg = pair
    scfg = SchedulerConfig(max_batch=1, block_size=4, num_blocks=64,
                           max_blocks_per_row=12, gamma_max=4,
                           prefill_buckets=(8, 16))
    plan = FaultPlan(delay_rounds={4: 30.0, 5: 30.0, 6: 30.0})
    srv = PagedSpecServer(mt, md, pt, pd, scfg, gamma=2, faults=plan,
                          watchdog=RoundWatchdog(slow_factor=3.0, patience=2,
                                                 min_rounds=2))
    for r in _requests(cfg, shapes=[(6, 24)], seed=2):
        srv.submit(r)
    done = srv.run()
    assert any("watchdog" in why for _, why in srv.metrics.degradations)
    assert srv.metrics.n_rounds > srv.metrics.n_spec_rounds
    _assert_matches_ar(mt, pt, done)
    _assert_pool_whole(srv)


def test_corrupt_output_fails_request_cleanly(pair):
    """The output guard: a poisoned (out-of-vocab) committed token must fail
    that request terminally with the reason recorded — never silently return
    garbage — while its neighbours complete exactly."""
    mt, md, pt, pd, cfg = pair
    scfg = SchedulerConfig(max_batch=2, block_size=4, num_blocks=64,
                           max_blocks_per_row=12, gamma_max=4,
                           prefill_buckets=(8, 16))
    plan = FaultPlan(corrupt_rounds=frozenset({1, 2}))
    srv = PagedSpecServer(mt, md, pt, pd, scfg, gamma=2, faults=plan)
    reqs = _requests(cfg, shapes=[(6, 12), (9, 12), (5, 10)], seed=3)
    for r in reqs:
        srv.submit(r)
    done = srv.run()
    assert len(srv.metrics.failed) >= 1
    for rec in srv.metrics.failed:
        assert "corrupt token id" in rec.failed
    _assert_all_terminal(srv, len(reqs))
    assert len(done) == len(reqs) - len(srv.metrics.failed)
    _assert_matches_ar(mt, pt, done)     # survivors unaffected
    _assert_pool_whole(srv)


# --------------------------------------------------------------- expiry
def test_doomed_queued_request_expires_at_admission(pair):
    """A queued request whose deadline already passed is expired — terminal,
    zero blocks spent, goodput-counted as a miss — instead of head-blocking
    live work behind an unmeetable SLO."""
    mt, md, pt, pd, cfg = pair
    scfg = SchedulerConfig(max_batch=1, block_size=4, num_blocks=64,
                           max_blocks_per_row=12, gamma_max=4,
                           prefill_buckets=(8, 16))
    srv = PagedSpecServer(mt, md, pt, pd, scfg, now=ManualClock(1000.0))
    rng = np.random.default_rng(6)
    doomed = ServeRequest(0, rng.integers(0, cfg.vocab_size, 6), 8,
                          deadline=10.0)          # already past
    live = ServeRequest(1, rng.integers(0, cfg.vocab_size, 7), 6)
    srv.submit(doomed)
    srv.submit(live)
    done = srv.run()
    assert [r.rid for r in done] == [1]
    assert [r.rid for r in srv.metrics.expired] == [0]
    assert srv.metrics.expired[0].n_generated == 0
    assert srv.metrics.summary()["deadline_met"] == {0: False}
    _assert_all_terminal(srv, 2)
    _assert_pool_whole(srv)


# ------------------------------------------------------- AR stats (api)
def test_engine_backend_ar_stats_count_actual_tokens(pair):
    """EngineBackend._generate_ar must report what actually came back, not
    the max_new budget: one committed token per AR round, so rounds and
    tokens_generated both equal the emitted count."""
    from repro.api.backends import EngineBackend
    from repro.api.plan import ExecutionPlan, GammaSchedule

    mt, md, pt, pd, cfg = pair
    plan = ExecutionPlan(gamma=GammaSchedule(gamma=0), max_new=6)
    be = EngineBackend(mt, md, pt, pd, plan)
    prompt = np.random.default_rng(8).integers(0, cfg.vocab_size, (1, 5))
    toks, stats = be.generate(jnp.asarray(prompt, jnp.int32))
    n_new = int(toks.shape[1]) - prompt.shape[1]
    assert n_new > 0
    assert stats["tokens_generated"] == n_new
    assert stats["rounds"] == n_new
    assert stats["speculative"] is False
