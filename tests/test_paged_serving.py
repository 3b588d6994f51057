"""Paged serving subsystem: ragged continuous batching must be exact (every
request matches its own greedy AR continuation), the scheduler's admission/
refill must respect the block pool, and the gamma/AR decision must follow
the paper's cost model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache.paged_kv import NULL_BLOCK, BlockAllocator
from repro.configs import registry
from repro.core import cost_model
from repro.core.engine import autoregressive_generate
from repro.models.model import build_model
from repro.serving import (PagedSpecServer, Scheduler, SchedulerConfig,
                           ServeRequest, ServingMetrics)


def _pair(arch):
    cfg_t = registry.smoke_config(arch)
    if cfg_t.family == "vlm":
        cfg_t = cfg_t.replace(num_vision_tokens=0)
    cfg_d = cfg_t.replace(num_layers=max(1, cfg_t.num_layers - 1), name="draft")
    mt, md = build_model(cfg_t), build_model(cfg_d)
    return mt, md, mt.init(jax.random.PRNGKey(0)), md.init(jax.random.PRNGKey(7)), cfg_t


RAGGED = [(5, 8), (9, 12), (6, 4), (13, 10), (7, 6), (4, 9), (11, 5)]


@pytest.mark.parametrize("arch", ["llama3.2-1b", "mixtral-8x7b"])
def test_ragged_requests_match_own_greedy(arch):
    """THE acceptance invariant: mixed prompt lengths and per-request
    max_new, every completed request == its standalone AR continuation."""
    mt, md, pt, pd, cfg = _pair(arch)
    rng = np.random.default_rng(0)
    reqs = [ServeRequest(i, rng.integers(0, cfg.vocab_size, P), new)
            for i, (P, new) in enumerate(RAGGED)]
    scfg = SchedulerConfig(max_batch=3, block_size=4, num_blocks=64,
                           max_blocks_per_row=12, gamma_max=6,
                           prefill_buckets=(8, 16))
    srv = PagedSpecServer(mt, md, pt, pd, scfg)
    for r in reqs:
        srv.submit(r)
    done = srv.run()
    assert sorted(r.rid for r in done) == list(range(len(reqs)))
    # default c/alpha prior favor speculation at batch formation (the online
    # re-decision may later retune or downgrade on measured alpha)
    assert srv.metrics.n_spec_rounds > 0
    for r in done:
        ref = autoregressive_generate(
            mt, pt, jnp.asarray(np.asarray(r.prompt)[None]), r.max_new)
        np.testing.assert_array_equal(r.tokens, np.asarray(ref[0]))
    # all blocks returned to the pool (only the null block is off-limits)
    assert srv.alloc.num_free == scfg.num_blocks - 1
    s = srv.metrics.summary()
    assert s["requests_completed"] == len(reqs)
    assert s["total_generated_tokens"] == sum(n for _, n in RAGGED)
    assert s["alpha_hat"] is not None


def test_kv_traffic_charges_pool_block_bytes():
    """``kv_traffic`` charges each block read at one block's bytes of every
    layer of its pool stack, K and V: [L, NB, BS, Kv*D] pools."""
    mt, md, pt, pd, cfg = _pair("llama3.2-1b")
    rng = np.random.default_rng(1)
    scfg = SchedulerConfig(max_batch=2, block_size=4, num_blocks=32,
                           max_blocks_per_row=8, gamma_max=4,
                           prefill_buckets=(8, 16))
    srv = PagedSpecServer(mt, md, pt, pd, scfg)
    for i, (P, new) in enumerate(RAGGED[:3]):
        srv.submit(ServeRequest(i, rng.integers(0, cfg.vocab_size, P), new))
    srv.run()
    kv = srv.kv_traffic()

    def block_bytes(c):
        return 2 * c.num_layers * 4 * c.num_kv_heads * c.head_dim \
            * jnp.dtype(c.act_dtype).itemsize
    assert srv.kv_blocks_read_t > 0
    assert kv["read_bytes"] == (srv.kv_blocks_read_t * block_bytes(mt.cfg)
                                + srv.kv_blocks_read_d * block_bytes(md.cfg))


def test_ar_fallback_when_cost_model_says_no():
    """c >= alpha makes speculation infeasible (paper §II-B): the scheduler
    must choose gamma*=0 and the server must serve exact AR anyway."""
    mt, md, pt, pd, cfg = _pair("llama3.2-1b")
    rng = np.random.default_rng(1)
    reqs = [ServeRequest(i, rng.integers(0, cfg.vocab_size, P), new)
            for i, (P, new) in enumerate([(5, 6), (9, 4), (7, 8)])]
    scfg = SchedulerConfig(max_batch=2, block_size=4, num_blocks=64,
                           max_blocks_per_row=12, prefill_buckets=(8, 16))
    srv = PagedSpecServer(mt, md, pt, pd, scfg, cost_coefficient=1.5)
    for r in reqs:
        srv.submit(r)
    done = srv.run()
    assert srv.gamma == 0
    for r in done:
        ref = autoregressive_generate(
            mt, pt, jnp.asarray(np.asarray(r.prompt)[None]), r.max_new)
        np.testing.assert_array_equal(r.tokens, np.asarray(ref[0]))


def test_online_downgrade_to_ar_on_low_measured_alpha():
    """Telemetry must influence gamma WITHIN a run: a heavily noised drafter
    drives measured alpha below c, so the server starts speculative (prior
    alpha 0.8 > c) and downgrades to AR mid-run — outputs stay exact."""
    mt, md, pt, pd, cfg = _pair("llama3.2-1b")
    pd = jax.tree.map(
        lambda w: w + 0.5 * jax.random.normal(
            jax.random.PRNGKey(3), w.shape, jnp.float32).astype(w.dtype)
        if w.ndim >= 2 else w, pd)
    rng = np.random.default_rng(3)
    reqs = [ServeRequest(i, rng.integers(0, cfg.vocab_size, P), new)
            for i, (P, new) in enumerate([(6, 10), (9, 12)])]
    scfg = SchedulerConfig(max_batch=2, block_size=4, num_blocks=64,
                           max_blocks_per_row=12, gamma_max=4,
                           prefill_buckets=(8, 16), alpha_prior=0.8,
                           cost_coefficient=0.5)
    srv = PagedSpecServer(mt, md, pt, pd, scfg)
    for r in reqs:
        srv.submit(r)
    done = srv.run()
    assert srv.gamma == 0                     # downgraded once alpha measured
    assert srv.metrics.n_spec_rounds >= 1     # but it DID start speculative
    assert srv.metrics.n_rounds > srv.metrics.n_spec_rounds
    for r in done:
        ref = autoregressive_generate(
            mt, pt, jnp.asarray(np.asarray(r.prompt)[None]), r.max_new)
        np.testing.assert_array_equal(r.tokens, np.asarray(ref[0]))


def test_submit_rejects_requests_larger_than_pool():
    mt, md, pt, pd, cfg = _pair("llama3.2-1b")
    scfg = SchedulerConfig(max_batch=1, block_size=4, num_blocks=8,
                           max_blocks_per_row=8, gamma_max=4,
                           prefill_buckets=(8, 16))
    srv = PagedSpecServer(mt, md, pt, pd, scfg)
    # per-row capacity is 32 tokens but only 7 allocatable blocks (28 tokens):
    # demand 10+14+5=29 must fail loudly at submit, not strand in the queue
    with pytest.raises(ValueError, match="pool"):
        srv.submit(ServeRequest(0, np.zeros(10, np.int32), 14))
    # a prompt longer than the largest prefill bucket must also fail at
    # submit, not mid-flight inside the prefill after blocks were reserved
    big = SchedulerConfig(max_batch=1, block_size=8, num_blocks=64,
                          max_blocks_per_row=8, gamma_max=4,
                          prefill_buckets=(8, 16))
    srv2 = PagedSpecServer(mt, md, pt, pd, big)
    with pytest.raises(ValueError, match="bucket"):
        srv2.submit(ServeRequest(1, np.zeros(20, np.int32), 4))


def test_slot_refill_recycles_rows_and_blocks():
    mt, md, pt, pd, cfg = _pair("llama3.2-1b")
    rng = np.random.default_rng(2)
    R = 7
    reqs = [ServeRequest(i, rng.integers(0, cfg.vocab_size,
                                         int(rng.integers(4, 12))),
                         int(rng.integers(3, 9))) for i in range(R)]
    scfg = SchedulerConfig(max_batch=2, block_size=4, num_blocks=32,
                           max_blocks_per_row=10, gamma_max=4,
                           prefill_buckets=(4, 8, 16))
    srv = PagedSpecServer(mt, md, pt, pd, scfg)
    for r in reqs:
        srv.submit(r)
    done = srv.run()
    assert sorted(r.rid for r in done) == list(range(R))
    assert srv.total_rounds > R // 2          # B=2 slots must have recycled
    assert srv.alloc.num_free == scfg.num_blocks - 1


# --------------------------------------------------------------- scheduler
def _sched(**kw):
    cfg = SchedulerConfig(**{"max_batch": 2, "block_size": 4, "num_blocks": 8,
                             "max_blocks_per_row": 6, "gamma_max": 4,
                             "prefill_buckets": (8, 16), **kw})
    return Scheduler(cfg, BlockAllocator(cfg.num_blocks, cfg.block_size,
                                         cfg.max_blocks_per_row,
                                         cfg.max_batch)), cfg


def test_scheduler_admission_respects_pool():
    sched, cfg = _sched()
    # demand = P + max_new + gamma_max + 1 = 6+5+5 = 16 tokens = 4 blocks
    sched.submit(ServeRequest(0, np.zeros(6, np.int32), 5))
    sched.submit(ServeRequest(1, np.zeros(6, np.int32), 5))
    assert sched.try_admit(0) is not None     # 4 of 7 free blocks used
    assert sched.try_admit(1) is None         # 3 left < 4 needed: head blocks
    sched.alloc.free_row(0)
    assert sched.try_admit(1) is not None     # released blocks readmit
    # a request that can never fit per-row is rejected at submit time
    with pytest.raises(ValueError):
        sched.submit(ServeRequest(2, np.zeros(30, np.int32), 20))


def test_scheduler_gamma_decision_follows_cost_model():
    sched, cfg = _sched()
    # feasible: gamma* must equal the cost model's argmax, not just "some" g
    g, s = sched.choose_gamma(alpha=0.8, c=0.2)
    assert (g, s) == cost_model.optimal_gamma(0.8, 0.2, cfg.gamma_max)
    assert g > 0 and s > 1.0
    # infeasible (c >= alpha): fall back to AR
    g0, s0 = sched.choose_gamma(alpha=0.5, c=0.9)
    assert (g0, s0) == (0, 1.0)
    # telemetry feeds the decision: a measured low alpha flips it to AR
    sched.metrics.record_round(np.array([0, 0]), gamma=4)
    g1, _ = sched.choose_gamma(c=0.9)
    assert g1 == 0


def test_scheduler_bucketing_pads_exactly():
    sched, _ = _sched()
    assert sched.bucket(5) == 8 and sched.bucket(8) == 8
    assert sched.bucket(9) == 16
    with pytest.raises(ValueError):
        sched.bucket(17)
    padded = sched.pad_to_bucket(np.arange(1, 6, dtype=np.int32))
    assert padded.shape == (8,)
    assert (padded[:5] == np.arange(1, 6)).all() and (padded[5:] == 0).all()


def test_allocator_version_gates_table_pushes():
    """The device block table is only re-pushed when the host table actually
    changed: allocator.version bumps on allocation/release, not on no-ops."""
    alloc = BlockAllocator(num_blocks=16, block_size=4, max_blocks_per_row=8,
                           batch=2)
    v0 = alloc.version
    assert alloc.ensure(0, 8)            # allocates 2 blocks -> mutation
    assert alloc.version == v0 + 1
    assert alloc.ensure(0, 8)            # already covered -> no mutation
    assert alloc.ensure(0, 5)            # shrink request never shrinks
    assert alloc.version == v0 + 1
    assert alloc.free_tail(0, 8) == 0    # nothing beyond 2 blocks -> no-op
    assert alloc.version == v0 + 1
    assert alloc.free_row(0) == 2        # releases blocks -> mutation
    assert alloc.version == v0 + 2


def test_metrics_alpha_and_histogram():
    m = ServingMetrics(gamma_max=4)
    assert m.alpha_hat() is None
    m.record_round(np.array([4, 2]), gamma=4, active=np.array([True, True]),
                   rids=[7, 8])
    assert m.accept_hist[4] == 1 and m.accept_hist[2] == 1
    assert 0.0 < m.alpha_hat() <= 1.0
    m.record_round(np.array([1, 3]), gamma=4, active=np.array([False, True]),
                   rids=[7, 8])
    assert m.accept_hist[1] == 0              # inactive row not recorded
    assert m.row_hists[8][3] == 1
