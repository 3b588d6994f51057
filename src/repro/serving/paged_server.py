"""Paged continuous-batching speculative server.

Successor to launch/continuous.py's ContinuousSpecServer: the uniform
``(prompt_len, max_new)`` constraint is gone. Every request carries its own
prompt length and decode budget; KV lives in a shared block pool
(cache/paged_kv.py) so memory scales with resident tokens, and the
Scheduler (serving/scheduler.py) drives admission, length-bucketed prefill,
slot refill into the live block tables, and the cost-model gamma/AR
decision per admitted batch.

Execution model: one jitted round (speculative — BatchedSpecEngine.round —
or plain AR when the cost model says speculation does not pay) advances the
whole batch; between rounds the host harvests finished rows, frees their
blocks, and refills slots by running a bucketed one-row prefill directly
into the shared pools. Target and drafter consume identical token positions,
so one allocator/block-table drives both models' pools.

Invariant (tested): every completed request's tokens equal that prompt's
standalone greedy AR continuation, regardless of its neighbours' lengths.
"""
from __future__ import annotations

from collections import deque
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache.paged_kv import NULL_BLOCK, BlockAllocator
from repro.cache.prefix_pool import PrefixPool
from repro.core.batched_engine import (KV_FAMILIES, BatchedEngineConfig,
                                       BatchedSpecEngine, RowState)
from repro.core.rounds import TracedRound
from repro.obs import clock
from repro.obs.drift import DriftMonitor
from repro.obs.events import RoundEvent, RoundEventLog
from repro.obs.trace import NULL_TRACER
from repro.serving.faults import NO_FAULTS, DrafterFault, FaultPlan
from repro.serving.metrics import ServingMetrics
from repro.serving.scheduler import Scheduler, SchedulerConfig, ServeRequest
from repro.serving.watchdog import RoundWatchdog


class PagedSpecServer:
    def __init__(self, target, drafter, params_t, params_d,
                 scfg: Optional[SchedulerConfig] = None, *,
                 gamma: Optional[int] = None,
                 alpha: Optional[float] = None,
                 cost_coefficient: Optional[float] = None,
                 placement=None, tracer=None,
                 faults: Optional[FaultPlan] = None,
                 watchdog: Optional[RoundWatchdog] = None,
                 now=clock.wall):
        """``gamma``/``alpha``/``cost_coefficient`` override the scheduler's
        cost-model decision (None = decide online from telemetry).
        ``placement`` (api/placement.py) pins each model's params and block
        pool onto its own submesh and runs speculative rounds placed; AR
        rounds run target-only on the target submesh.

        An ENABLED ``tracer`` (repro.obs) switches speculative rounds onto
        the phase-split TracedRound (draft/verify/commit spans + per-phase
        times in the round events and the drift monitor); disabled (the
        default) keeps the fused donated round — tracing costs nothing
        when off.

        ``faults`` (serving/faults.py) injects a deterministic failure
        schedule — delays, drafter exceptions, pool seizure, output
        corruption — keyed by step index; the NO_FAULTS default costs a few
        dict lookups per round. ``watchdog`` (serving/watchdog.py) guards
        against straggling speculative rounds by degrading the batch to AR;
        ``now`` is the metrics clock (injectable for deterministic deadline
        and expiry tests)."""
        assert target.family in KV_FAMILIES and drafter.family in KV_FAMILIES, \
            "paged speculative serving needs KV-cache families"
        self.target, self.drafter = target, drafter
        self.placement = (placement if placement is not None
                          and placement.heterogeneous else None)
        if self.placement is not None:
            params_t = self.placement.target.put_params(target, params_t)
            params_d = self.placement.drafter.put_params(drafter, params_d)
        self.params_t, self.params_d = params_t, params_d
        self.scfg = scfg or SchedulerConfig()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.faults = faults if faults is not None else NO_FAULTS
        self.watchdog = watchdog if watchdog is not None else RoundWatchdog()
        self.metrics = ServingMetrics(gamma_max=self.scfg.gamma_max, now=now)
        self.events = RoundEventLog(alpha_ema=self.metrics.alpha_ema)
        self.drift: Optional[DriftMonitor] = None  # built at first spec round
        self.alloc = BlockAllocator(self.scfg.num_blocks, self.scfg.block_size,
                                    self.scfg.max_blocks_per_row,
                                    self.scfg.max_batch)
        self.sched = Scheduler(self.scfg, self.alloc, self.metrics)
        self._gamma_override = gamma
        self._alpha_override = alpha
        self._c_override = cost_coefficient

        self.B = self.scfg.max_batch
        self.T = self.scfg.max_tokens_per_row + self.scfg.gamma_max + 2
        self._slots: List[Optional[ServeRequest]] = [None] * self.B
        self._target_len = np.zeros(self.B, np.int64)
        # chunked-prefill state (docs/DESIGN.md §4/§10). ``_chunk`` is None
        # on the legacy bucketed all-at-once path; otherwise prefills run as
        # fixed-[1, C] chunk programs interleaved with decode rounds.
        # Mid-prefill rows are tracked host-side (``_masked``) and their rows
        # of the PUSHED device tables are nulled so stale-index speculative
        # writes for those (inactive) rows land in the null block — never in
        # their real blocks, and never in SHARED cached prefix blocks.
        self._chunk = self.scfg.effective_chunk if self.scfg.chunked else None
        self.prefix_pool = (PrefixPool(self.alloc) if self.scfg.prefix_cache
                            else None)
        self._prefill_pos = np.zeros(self.B, np.int64)  # next suffix position
        self._prefill_hit = np.zeros(self.B, np.int64)  # tokens from cache
        self._prefill_chunks = np.zeros(self.B, np.int64)
        self._masked: set = set()          # rows mid-prefill (inactive)
        self._table_masked: frozenset = frozenset()  # masked set last pushed
        self._chunk_jit = None
        # per-step prefill spans for the RoundEvent / drift monitor
        self._round_prefill_tokens = 0
        self._round_prefill_chunks = 0
        self._round_prefill_t = 0.0
        self._aborted_pending: List[int] = []  # mid-prefill evictions
                                               # awaiting "preempted" fanout
        self._state: Optional[RowState] = None
        self._lengths: Optional[np.ndarray] = None  # host mirror of .length
        self._batch_formed = False   # gamma decided for the current batch
        self._pending_cancels: Deque[int] = deque()  # rids to cancel (thread-
                                                     # safe handoff; processed
                                                     # at the next step)
        # per-round committed-token harvest for streaming front ends; off by
        # default so the synchronous run() hot path never pulls the token
        # buffer from device (AsyncSpecServer flips it on)
        self.collect_streams = False
        self._engines: Dict[int, BatchedSpecEngine] = {}
        self._prefill_jit = None
        self._ar_jit = None
        self._table_version = -1    # last allocator.version pushed to device
        self.gamma = None           # decided at batch formation
        self._degraded = False      # watchdog/fault AR pin (one-way until
                                    # the batch drains and re-forms)
        self._vocab = int(target.cfg.vocab_size)  # output-guard bound
        self._failed_pending: List[int] = []  # failed rids awaiting fanout
        self.done: List[ServeRequest] = []
        self.total_rounds = 0
        self.total_steps = 0        # step() calls incl. stalled/idle steps —
                                    # the fault-plan index (advances even when
                                    # no round runs, so seized blocks keyed to
                                    # a later step always come back)
        # paged-attention read accounting (see kv_traffic()): per-round KV
        # gathers, live-bounded vs worst-case row capacity, kept separately
        # for the target (verify / AR read) and the drafter (gamma
        # single-token draft reads per speculative round; none under AR)
        self.kv_blocks_read_t = 0
        self.kv_blocks_read_d = 0
        self.kv_blocks_capacity_t = 0
        self.kv_blocks_capacity_d = 0

    # ------------------------------------------------------------- plumbing
    def submit(self, req: ServeRequest):
        self.sched.submit(req)

    def inject_faults(self, plan: FaultPlan):
        """Swap the fault schedule in (chaos CLIs/benches; safe before the
        first step)."""
        self.faults = plan

    def _engine(self, gamma: int) -> BatchedSpecEngine:
        if gamma not in self._engines:
            eng = BatchedSpecEngine(self.target, self.drafter,
                                    BatchedEngineConfig(gamma=gamma),
                                    placement=self.placement,
                                    tracer=self.tracer)
            if eng._round_jit is None:
                # donate the round state: block pools update in place instead
                # of being copied every round (host snapshots pre-call); the
                # placed round manages its own per-submesh residency instead
                eng._round_jit = jax.jit(
                    lambda pt, pd, s: eng.round(pt, pd, s),
                    donate_argnums=(2,))
            self._engines[gamma] = eng
        return self._engines[gamma]

    def _empty_state(self) -> RowState:
        from repro.cache.ops import PAGED
        B = self.B
        geom = dict(num_blocks=self.scfg.num_blocks,
                    block_size=self.scfg.block_size,
                    max_blocks_per_row=self.scfg.max_blocks_per_row)
        tcache = PAGED.init(self.target, B, **geom)
        dcache = PAGED.init(self.drafter, B, **geom)
        st = RowState(tokens=jnp.zeros((B, self.T), jnp.int32),
                      length=jnp.ones((B,), jnp.int32),  # length-1 >= 0
                      dcache=dcache, tcache=tcache,
                      active=jnp.zeros((B,), bool),
                      n_rounds=jnp.zeros((), jnp.int32),
                      n_accepted=jnp.zeros((B,), jnp.int32),
                      n_drafted=jnp.zeros((), jnp.int32))
        if self.placement is not None:
            from repro.core.rounds import place_state
            st = place_state(st, self.placement, self.target, self.drafter)
        return st

    def _sync_tables(self, state: RowState) -> RowState:
        """Push the host block table to the device — only when it actually
        changed since the last push (allocator.version plus the mid-prefill
        mask gate the transfer; admission/release/chunk-completion bump
        them, idle rounds do not). Mid-prefill rows are pushed as NULL:
        decode rounds keep issuing speculative writes for every row at its
        (stale) device index, and for a row whose prefill is still in
        flight those writes must land in the null block, not in its real
        blocks (chunk programs carry the TRUE row table in their own
        views). Two separate device arrays: tcache/dcache must not share
        one buffer or the donated round state would donate it twice."""
        masked = frozenset(self._masked)
        if (self._table_version == self.alloc.version
                and self._table_masked == masked):
            return state
        self._table_version = self.alloc.version
        self._table_masked = masked
        host = self.alloc.table
        if masked:
            host = host.copy()
            host[sorted(masked)] = NULL_BLOCK
        # two INDEPENDENT uploads on purpose: a single host array pinned onto
        # both roles can alias one device buffer on shared devices
        # (device_put reuses resident shards), and the speculative round
        # DONATES the drafter cache — a shared buffer would be deleted out
        # from under the target's table
        t_table = jnp.asarray(host)
        d_table = jnp.asarray(host)
        if self.placement is not None:
            t_table = self.placement.to_target(t_table)
            d_table = self.placement.to_drafter(d_table)
        return state._replace(
            tcache={**state.tcache, "block_table": t_table},
            dcache={**state.dcache, "block_table": d_table})

    # -------------------------------------------------------------- prefill
    def _prefill_into(self, state: RowState, row: int, req: ServeRequest):
        """Length-bucketed one-row prefill written straight into the shared
        pools, then rolled back to the true prompt length (exact: the padded
        tail is causally invisible to the real tokens and masked afterward).
        The caller must have synced the block tables (``_refill`` does); the
        row views below slice the already-pushed device tables instead of
        re-uploading. The pool views are donated: prefill writes the shared
        pools in place rather than copying them per admitted request.

        A PREEMPTED request prefills its ``effective_prompt`` — the committed
        prefix (prompt + generated tokens) snapshotted at eviction — and then
        decodes from where it left off: greedy decode over the identical
        prefix continues byte-identically (the recompute half of
        preemption-by-eviction; docs/DESIGN.md §9).

        Returns ``(state, ok)``: ``ok`` is False when the target produced
        non-finite prefill logits — the caller must fail the request cleanly
        instead of decoding from a poisoned cache."""
        prompt = np.asarray(req.effective_prompt, np.int32)
        padded = self.sched.pad_to_bucket(prompt)
        P = req.resume_len
        if self._prefill_jit is None:
            if self.placement is None:
                def prefill(pt, pd, prompt, tc, dc):
                    logits, tc, _ = self.target.apply(pt, prompt[:, :-1], tc)
                    _, dc, _ = self.drafter.apply(pd, prompt[:, :-1], dc)
                    return tc, dc, jnp.isfinite(logits).all()
                self._prefill_jit = jax.jit(prefill, donate_argnums=(3, 4))
            else:
                # placed: each role's prefill is its own program on its own
                # submesh (one jit cannot span two meshes)
                def t_fn(pt, prompt, tc):
                    logits, tc, _ = self.target.apply(pt, prompt[:, :-1], tc)
                    return tc, jnp.isfinite(logits).all()
                pm = self.placement
                t_jit = pm.target.jit(t_fn, donate_argnums=(2,))
                d_jit = pm.drafter.jit(
                    lambda pd, prompt, dc:
                        self.drafter.apply(pd, prompt[:, :-1], dc)[1],
                    donate_argnums=(2,))

                def prefill(pt, pd, prompt, tc, dc):
                    tc, ok = t_jit(pt, pm.to_target(prompt), tc)
                    return tc, d_jit(pd, pm.to_drafter(prompt), dc), ok
                self._prefill_jit = prefill
        t_table = state.tcache["block_table"]
        d_table = state.dcache["block_table"]

        def row_slice(table):
            # with B == 1 the identity slice short-circuits to the SAME
            # buffer; a donated view would delete the full table the merged
            # cache keeps, so force a distinct buffer in that case
            v = table[row:row + 1]
            return jnp.copy(v) if v is table else v

        tc_view = {**state.tcache, "block_table": row_slice(t_table),
                   "index": jnp.zeros((1,), jnp.int32)}
        dc_view = {**state.dcache, "block_table": row_slice(d_table),
                   "index": jnp.zeros((1,), jnp.int32)}
        with self.tracer.span("prefill", phase="prefill", role="target",
                              rid=req.rid, prompt_len=P):
            tc, dc, ok = self._prefill_jit(self.params_t, self.params_d,
                                           jnp.asarray(padded[None]), tc_view,
                                           dc_view)
            if self.tracer.enabled:
                jax.block_until_ready((tc["index"], dc["index"]))
        # merge: pools carry the new rows; index rolls back to P-1 (bucket
        # padding beyond it is masked); tables re-broadcast to the full batch.
        # The merge happens even on a failed (non-finite) prefill — the views
        # were donated, so the old pools are gone; the caller frees the row
        # and its blocks are rewritten before they can become visible.
        tcache = {**tc, "block_table": t_table,
                  "index": state.tcache["index"].at[row].set(P - 1)}
        dcache = {**dc, "block_table": d_table,
                  "index": state.dcache["index"].at[row].set(P - 1)}
        tokens = state.tokens.at[row].set(0).at[row, :P].set(
            jnp.asarray(prompt, jnp.int32))
        # target_len counts from the ORIGINAL prompt: a resumed request only
        # owes the remainder of its decode budget
        self._target_len[row] = req.prompt_len + req.max_new
        state = state._replace(tokens=tokens,
                               length=state.length.at[row].set(P),
                               active=state.active.at[row].set(True),
                               tcache=tcache, dcache=dcache)
        return state, bool(jax.device_get(ok))

    # ------------------------------------------------- chunked prefill path
    def _chunk_fn(self):
        """Fixed-shape [1, C] chunk program, compiled ONCE (vs once per
        bucket on the legacy path): writes KV for C suffix tokens starting
        at the view's index and returns the finite-logits guard."""
        if self._chunk_jit is None:
            if self.placement is None:
                def chunk(pt, pd, toks, tc, dc):
                    logits, tc, _ = self.target.apply(pt, toks, tc)
                    _, dc, _ = self.drafter.apply(pd, toks, dc)
                    return tc, dc, jnp.isfinite(logits).all()
                self._chunk_jit = jax.jit(chunk, donate_argnums=(3, 4))
            else:
                def t_fn(pt, toks, tc):
                    logits, tc, _ = self.target.apply(pt, toks, tc)
                    return tc, jnp.isfinite(logits).all()
                pm = self.placement
                t_jit = pm.target.jit(t_fn, donate_argnums=(2,))
                d_jit = pm.drafter.jit(
                    lambda pd, toks, dc: self.drafter.apply(pd, toks, dc)[1],
                    donate_argnums=(2,))

                def chunk(pt, pd, toks, tc, dc):
                    tc, ok = t_jit(pt, pm.to_target(toks), tc)
                    return tc, d_jit(pd, pm.to_drafter(toks), dc), ok
                self._chunk_jit = chunk
        return self._chunk_jit

    def _begin_prefill(self, state: RowState, b: int,
                       req: ServeRequest) -> RowState:
        """Admit ``req`` into row ``b`` on the chunked path: look up the
        prefix cache, attach any cached block chain (the row then prefills
        only its unique suffix), stage the prompt tokens, and mark the row
        mid-prefill (masked + inactive) until ``_advance_prefills`` finishes
        the suffix. The attach rebuild cannot fail: admission's grant is
        returned to the free list first and cached blocks consume none."""
        prompt = np.asarray(req.effective_prompt, np.int32)
        P = req.resume_len
        hit_blocks: List[int] = []
        if self.prefix_pool is not None and P > 1:
            # cap at (P-1)//BS blocks: the row's first decode write lands at
            # position P-1, which must NEVER fall inside a shared block
            cap = min((P - 1) // self.scfg.block_size,
                      self.scfg.max_blocks_per_row)
            hit_blocks = self.prefix_pool.lookup(prompt, cap)
            if hit_blocks:
                admit = self.sched.admit_tokens(req)
                self.alloc.free_row(b)
                self.alloc.attach(b, hit_blocks)
                ok = self.alloc.ensure(b, admit)
                assert ok, "re-grow after cached-prefix attach cannot fail"
        start = len(hit_blocks) * self.scfg.block_size
        self._prefill_pos[b] = start
        self._prefill_hit[b] = start
        self._prefill_chunks[b] = 0
        self._target_len[b] = req.prompt_len + req.max_new
        self._masked.add(b)
        tokens = state.tokens.at[b].set(0).at[b, :P].set(
            jnp.asarray(prompt, jnp.int32))
        # reset the device length: the slot's previous occupant left its
        # FINAL length behind, which must not read as instant completion
        return state._replace(tokens=tokens,
                              length=state.length.at[b].set(1),
                              active=state.active.at[b].set(False))

    def _run_chunk(self, state: RowState, b: int, req: ServeRequest):
        """One chunk program for mid-prefill row ``b``: write KV for suffix
        positions [pos, min(pos+C, P-1)). The views carry the TRUE row table
        (the batch-wide device copy has this row masked to NULL) and the
        chunk-base index; final-chunk padding past P-1 is overwritten by the
        first decode rounds before it can become causally visible — the
        same argument as the legacy bucket padding. Returns ``(state, ok)``
        with ok=None when the pool is dry (caller aborts the prefill)."""
        prompt = np.asarray(req.effective_prompt, np.int32)
        P, C = req.resume_len, self._chunk
        s = int(self._prefill_pos[b])
        e = min(s + C, P - 1)
        if not self.sched.grow(b, e):
            return state, None
        padded = np.zeros(C, np.int32)
        padded[:e - s] = prompt[s:e]
        # the span and RoundEvent.t_prefill time one interval: uploads, the
        # chunk program and its ``ok`` sync
        with self.tracer.span("prefill_chunk", phase="prefill", role="target",
                              rid=req.rid, start=s, end=e):
            t0 = self.tracer.clock()
            # fresh per-chunk uploads of the one-row table — never a slice
            # of the donated batch-wide device table (see _prefill_into's
            # aliasing note); two independent uploads for the two donated
            # views
            t_row = jnp.asarray(self.alloc.table[b:b + 1])
            d_row = jnp.asarray(self.alloc.table[b:b + 1])
            if self.placement is not None:
                t_row = self.placement.to_target(t_row)
                d_row = self.placement.to_drafter(d_row)
            tc_view = {**state.tcache, "block_table": t_row,
                       "index": jnp.full((1,), s, jnp.int32)}
            dc_view = {**state.dcache, "block_table": d_row,
                       "index": jnp.full((1,), s, jnp.int32)}
            tc, dc, ok = self._chunk_fn()(self.params_t, self.params_d,
                                          jnp.asarray(padded[None]),
                                          tc_view, dc_view)
            ok = bool(jax.device_get(ok))
            self._round_prefill_t += self.tracer.clock() - t0
        # merge: pools carry the new KV; the batch tables/indices are kept
        # (this row's merged index is set once, at completion)
        state = state._replace(
            tcache={**tc, "block_table": state.tcache["block_table"],
                    "index": state.tcache["index"]},
            dcache={**dc, "block_table": state.dcache["block_table"],
                    "index": state.dcache["index"]})
        self._prefill_pos[b] = e
        self._prefill_chunks[b] += 1
        self._round_prefill_tokens += e - s
        self._round_prefill_chunks += 1
        return state, ok

    def _complete_prefill(self, state: RowState, b: int,
                          req: ServeRequest) -> RowState:
        """Suffix done: register the fully-written prefix blocks for future
        sharers, unmask the row, set its committed length/index, activate.
        Registered blocks sit strictly below position P-1, so this row (and
        every attacher) only ever writes PAST them — they are immutable
        from here on (the prefix pool's safety invariant)."""
        P = req.resume_len
        if self.prefix_pool is not None and P > 1:
            F = min((P - 1) // self.scfg.block_size,
                    self.scfg.max_blocks_per_row)
            if F > 0:
                prompt = np.asarray(req.effective_prompt, np.int32)
                self.prefix_pool.insert(
                    prompt[:F * self.scfg.block_size],
                    [int(x) for x in self.alloc.table[b, :F]])
        self._masked.discard(b)
        self.metrics.prefill(req.rid,
                             max(P - 1 - int(self._prefill_hit[b]), 0),
                             hit_tokens=int(self._prefill_hit[b]),
                             chunks=int(self._prefill_chunks[b]))
        self._lengths[b] = P
        return state._replace(
            length=state.length.at[b].set(P),
            active=state.active.at[b].set(True),
            tcache={**state.tcache,
                    "index": state.tcache["index"].at[b].set(P - 1)},
            dcache={**state.dcache,
                    "index": state.dcache["index"].at[b].set(P - 1)})

    def _abort_prefill(self, state: RowState, b: int,
                       req: ServeRequest) -> RowState:
        """Mid-prefill eviction (pool ran dry): free the row's blocks and
        re-queue. Re-admission restarts the prefill — cheap when the prefix
        cache still holds the chain (the eviction freed only this row's
        table references, not the pool's pins)."""
        self.alloc.free_row(b)
        self._masked.discard(b)
        self._slots[b] = None
        self.sched.requeue(req)
        self._aborted_pending.append(req.rid)
        return state._replace(active=state.active.at[b].set(False))

    def _advance_prefills(self, state: RowState) -> RowState:
        """Advance mid-prefill rows by at most ONE chunk program per step —
        the interleave policy: bounded prefill work per decode round keeps
        running rows' TPOT bounded, while a newly admitted prompt still
        reaches its first token in ceil(suffix/C) steps. Fully-cached rows
        (empty suffix) and rows whose chunk just finished the suffix
        activate THIS step."""
        if self._chunk is None or not self._masked:
            return state
        budget = 1
        for b in sorted(self._masked):
            req = self._slots[b]
            P = req.resume_len
            if int(self._prefill_pos[b]) >= P - 1:
                state = self._complete_prefill(state, b, req)
                continue
            if budget <= 0:
                continue
            budget -= 1
            state, ok = self._run_chunk(state, b, req)
            if ok is None:
                state = self._abort_prefill(state, b, req)
                continue
            if not ok:
                # non-finite target logits: fail cleanly, as on the legacy
                # path — never decode from a poisoned cache
                self.alloc.free_row(b)
                self._masked.discard(b)
                self.metrics.fail(req.rid, "non-finite prefill logits",
                                  n_generated=req.resume_len - req.prompt_len)
                self._failed_pending.append(req.rid)
                self._slots[b] = None
                continue
            if int(self._prefill_pos[b]) >= P - 1:
                state = self._complete_prefill(state, b, req)
        return state

    # ------------------------------------------------------------- AR round
    def _ar_round(self, state: RowState) -> RowState:
        """gamma* = 0 fallback: one committed token per active row per round,
        target model only (the cost model said drafting does not pay).
        The round is the shared core's ``ar_round`` (core/rounds.py)."""
        if self._ar_jit is None:
            from repro.core import rounds
            jit = (jax.jit if self.placement is None
                   else self.placement.target.jit)
            self._ar_jit = jit(
                lambda pt, st: rounds.ar_round(self.target, pt, st),
                donate_argnums=(1,))
        if self.placement is not None:
            # the drafter cache lives on its own submesh; AR rounds are
            # target-only, so detach it, run placed, reattach untouched
            out = self._ar_jit(self.params_t, state._replace(dcache=None))
            return out._replace(dcache=state.dcache)
        return self._ar_jit(self.params_t, state)

    # -------------------------------------------------------------- serving
    def _refill(self, state: RowState,
                lengths: Optional[np.ndarray] = None) -> RowState:
        for b in range(self.B):
            if self._slots[b] is not None:
                continue
            req = self.sched.try_admit(b)
            if req is None:
                break                       # FCFS head-blocking
            if self._chunk is not None:
                # chunked path: stage the row mid-prefill; the suffix runs
                # as interleaved chunk programs (_advance_prefills)
                state = self._begin_prefill(state, b, req)
                if lengths is not None:
                    lengths[b] = 1          # mirrors the reset device length
                self._slots[b] = req
                continue
            state = self._sync_tables(state)
            state, ok = self._prefill_into(state, b, req)
            if not ok:
                # non-finite target logits: fail the request cleanly (with
                # the reason in metrics) instead of decoding garbage from a
                # poisoned cache; the row's blocks go straight back
                self.alloc.free_row(b)
                self.metrics.fail(req.rid, "non-finite prefill logits",
                                  n_generated=req.resume_len - req.prompt_len)
                self._failed_pending.append(req.rid)
                state = state._replace(active=state.active.at[b].set(False))
                continue
            self.metrics.prefill(req.rid, max(req.resume_len - 1, 0))
            if lengths is not None:
                # keep the host mirror current; a resumed request starts at
                # its committed prefix, not its original prompt
                lengths[b] = req.resume_len
            self._slots[b] = req
        return state

    def _harvest(self, state: RowState, lengths: np.ndarray) -> RowState:
        """``lengths`` is the round's single host snapshot of state.length
        (run() pulls it once; refill updates it in place for new rows).
        Completing rows pass the output guard before release: a committed
        token outside the vocabulary means the decode was poisoned (corrupt
        logits / injected fault) — fail the request with the reason recorded
        instead of returning garbage."""
        for b in range(self.B):
            req = self._slots[b]
            if (req is None or b in self._masked
                    or lengths[b] < self._target_len[b]):
                continue
            toks = np.asarray(state.tokens[b, :self._target_len[b]])
            gen = toks[req.prompt_len:]
            if ((gen < 0) | (gen >= self._vocab)).any():
                self._fail_row(b, req, int(self._target_len[b]))
                state = state._replace(active=state.active.at[b].set(False))
                continue
            req.tokens = toks
            self.sched.release(b, req)
            self.done.append(req)
            self._slots[b] = None
            state = state._replace(active=state.active.at[b].set(False))
        return self._sync_tables(self._refill(state, lengths))

    # ----------------------------------------------------------- preemption
    def _fail_row(self, b: int, req: ServeRequest, cur: int):
        """Terminal-failure teardown for an in-flight row: blocks freed,
        reason recorded, rid queued for stream fanout. The caller clears the
        row's active flag on whichever state object it holds."""
        self.alloc.free_row(b)
        self.metrics.fail(req.rid,
                          f"corrupt token id outside [0, {self._vocab})",
                          n_generated=max(cur - req.prompt_len, 0))
        self._failed_pending.append(req.rid)
        self._slots[b] = None

    def _choose_victim(self, prefer_not: int) -> Optional[int]:
        """Victim policy: among occupied rows, LATEST deadline first (a
        best-effort None deadline sorts latest of all — most slack), ties
        broken by fewest committed tokens (cheapest recompute). The live EDF
        head — the occupied row with the earliest deadline — is protected
        whenever any other candidate exists, mirroring admission's
        no-starvation rule; likewise the row whose growth triggered the
        eviction (``prefer_not``) is evicted only as the last resort
        (self-preemption, which still terminates: re-admission's reservation
        floor guarantees a block of committed progress per cycle)."""
        occupied = [b for b in range(self.B) if self._slots[b] is not None]
        if not occupied:
            return None

        def dl(b):
            d = self._slots[b].deadline
            return float("inf") if d is None else d

        cands = list(occupied)
        if len(cands) > 1:
            head = min(occupied, key=lambda b: (dl(b), b))
            cands = [b for b in cands if b != head]
        if prefer_not in cands and len(cands) > 1:
            cands = [b for b in cands if b != prefer_not]
        return max(cands, key=lambda b: (dl(b),
                                         -int(min(self._lengths[b],
                                                  self._target_len[b])), -b))

    def _preempt_row(self, b: int, state: RowState) -> RowState:
        """Evict row ``b``: snapshot its committed prefix (prompt + generated
        tokens — never unverified speculation; ``_lengths`` is the committed
        length), free ALL its KV blocks, and re-queue the request. On
        re-admission the prefix is prefilled again and greedy decode resumes
        byte-identically (chaos-suite checked)."""
        req = self._slots[b]
        if b in self._masked:
            # mid-prefill victim: nothing committed beyond the resume prefix
            # it is already re-prefilling — no new snapshot to take
            return self._abort_prefill(state, b, req)
        cur = int(min(self._lengths[b], self._target_len[b]))
        req.resume_tokens = np.asarray(jax.device_get(
            state.tokens[b, :cur])).astype(np.int32)
        req.preemptions += 1
        self.alloc.free_row(b)
        self._slots[b] = None
        self.sched.requeue(req)
        return state._replace(active=state.active.at[b].set(False))

    def _ensure_capacity(self, state: RowState):
        """Overcommit enforcement, run between the gamma decision and the
        round dispatch: every live row must own blocks for its committed
        prefix plus this round's speculative writes (gamma + 1 unverified
        tokens past the committed index). When the pool runs dry, evict
        victims until the row fits. Under worst-case reservation
        (overcommit == 1.0) the admission grant already covers every round,
        so ``grow`` returns immediately and nothing is ever preempted.
        Returns ``(state, preempted_rids)``."""
        preempted: List[int] = []
        for b in range(self.B):
            if self._slots[b] is None or b in self._masked:
                continue   # mid-prefill rows grow chunk by chunk instead
            needed = (int(min(self._lengths[b], self._target_len[b]))
                      + self.gamma + 1)
            while self._slots[b] is not None and not self.sched.grow(b, needed):
                victim = self._choose_victim(prefer_not=b)
                if victim is None:
                    break
                preempted.append(self._slots[victim].rid)
                state = self._preempt_row(victim, state)
                if victim == b:
                    break               # the growing row evicted itself
        return state, preempted

    def _account_round(self, prev_len: np.ndarray):
        """Per-round paged-attention read bound (matches the block-scan read
        path): with live = batch-max committed length, a speculative round
        reads ceil((live+i)/BS) blocks/row for draft step i (gamma drafter
        gathers) plus ceil((live+gamma)/BS) for the target verify; an AR
        round reads ceil(live/BS) on the target only — vs max_blocks_per_row
        per gather under the old full-pool read. Feeds kv_traffic(). Like the
        engine bound, only occupied rows count.

        Returns ``(blocks_read, blocks_written)`` for this round (the write
        side is a span estimate: distinct blocks covering the up-to-gamma+1
        unverified target writes plus gamma drafter writes per occupied
        row) — the RoundEvent's traffic fields."""
        occupied = np.array([s is not None for s in self._slots])
        n_occ = int(occupied.sum())
        live = int(prev_len[occupied].max()) if occupied.any() else 1
        bs, mb = self.scfg.block_size, self.scfg.max_blocks_per_row

        def blocks(tokens):
            return min(-(-tokens // bs), mb)

        def write_span(n_new):
            # distinct blocks covering token positions [live, live + n_new)
            return 0 if n_new <= 0 else (live + n_new - 1) // bs - live // bs + 1

        if self.gamma > 0:
            t_blocks, d_gathers = blocks(live + self.gamma), self.gamma
            d_blocks = sum(blocks(live + i) for i in range(self.gamma))
            written = (write_span(self.gamma + 1)
                       + write_span(self.gamma)) * n_occ
        else:
            t_blocks, d_gathers, d_blocks = blocks(live), 0, 0
            written = write_span(1) * n_occ
        self.kv_blocks_read_t += t_blocks * self.B
        self.kv_blocks_read_d += d_blocks * self.B
        self.kv_blocks_capacity_t += mb * self.B
        self.kv_blocks_capacity_d += d_gathers * mb * self.B
        return (t_blocks + d_blocks) * self.B, written

    def kv_traffic(self) -> Dict[str, float]:
        """KV bytes gathered by per-round attention reads, live-block-bounded
        (actual) vs worst-case capacity (the old gathered-view read path).
        Target and drafter gathers are charged against their own pool sizes."""
        def per_block(cache):
            total = 0
            for leaf in jax.tree_util.tree_leaves(cache or {}):
                if getattr(leaf, "ndim", 0) == 4:  # [L, NB, BS, Kv*D] pools
                    L, _, BS, row = leaf.shape
                    total += L * BS * row * jnp.dtype(leaf.dtype).itemsize
            return total

        pt = per_block(self._state.tcache) if self._state is not None else 0
        pd = per_block(self._state.dcache) if self._state is not None else 0
        return {"read_blocks": self.kv_blocks_read_t + self.kv_blocks_read_d,
                "capacity_blocks": (self.kv_blocks_capacity_t
                                    + self.kv_blocks_capacity_d),
                "read_bytes": (self.kv_blocks_read_t * pt
                               + self.kv_blocks_read_d * pd),
                "capacity_bytes": (self.kv_blocks_capacity_t * pt
                                   + self.kv_blocks_capacity_d * pd)}

    def _measured_c(self) -> Optional[float]:
        """Drift-measured cost coefficient, once the monitor has evidence —
        the re-planning loop: the scheduler's next gamma decision uses the
        MEASURED t_draft/t_target instead of the configured prior."""
        if self._c_override is not None or self.drift is None:
            return None
        ev = self.drift.evidence()
        return ev["c"] if ev else None

    def cancel(self, rid: int):
        """Request cancellation of ``rid`` (queued or mid-generation). The
        actual teardown happens at the start of the next ``step()`` — queued
        requests leave the scheduler queue, in-flight rows are released with
        their partial tokens and their KV blocks returned to the pool, so the
        freed row can be re-admitted to a queued request in the same step.
        Thread-safe (a deque handoff): an async front end calls this from the
        event loop while the stepper thread runs a round."""
        self._pending_cancels.append(rid)

    def _process_cancels(self) -> List[int]:
        cancelled: List[int] = []
        while self._pending_cancels:
            rid = self._pending_cancels.popleft()
            if self.sched.cancel(rid):          # still queued: just dequeue
                cancelled.append(rid)
                continue
            for b, req in enumerate(self._slots):
                if req is None or req.rid != rid:
                    continue
                if b in self._masked:
                    # cancelled mid-prefill: nothing decoded; the committed
                    # prefix is just what re-admission would have prefilled
                    req.tokens = np.asarray(req.effective_prompt, np.int32)
                    self._masked.discard(b)
                    cur = req.prompt_len
                else:
                    cur = int(min(self._lengths[b], self._target_len[b]))
                    req.tokens = np.asarray(jax.device_get(
                        self._state.tokens[b, :cur]))
                self.alloc.free_row(b)          # KV blocks back to the pool
                self.metrics.cancel(rid, cur - req.prompt_len)
                self._slots[b] = None
                self._state = self._state._replace(
                    active=self._state.active.at[b].set(False))
                cancelled.append(rid)
                break
        return cancelled

    def run(self):
        """Drain the queue; returns completed requests (submission order is
        not guaranteed — rows finish by their own lengths)."""
        with self.tracer.span("serve", phase="serve"):
            while self.step() is not None:
                pass
            return self.done

    def _batch_drained(self):
        """The current batch is over: the next admission re-forms it (and
        re-decides gamma — safe, because no live row carries stale drafter
        KV). Degradation and the watchdog recover WITH the batch: both are
        scoped to one batch's spec->AR rule."""
        self._batch_formed = False
        self._degraded = False
        self.watchdog.reset()

    def _drain_failed(self) -> List[int]:
        out, self._failed_pending = self._failed_pending, []
        return out

    def _drain_aborted(self, seen: List[int]) -> List[int]:
        """Mid-prefill evictions since the last step, minus rids already in
        ``seen`` (capacity-driven aborts land in both bookkeeping paths)."""
        out, self._aborted_pending = self._aborted_pending, []
        return [r for r in out if r not in seen]

    def step(self) -> Optional[Dict]:
        """ONE serving round: apply scheduled faults, process cancellations,
        admit/refill (expiring doomed queue heads), decide gamma, enforce
        block capacity (preempting victims under overcommit), run one jitted
        round, record telemetry, harvest finished rows. Returns None when
        idle (no live rows, nothing queued, no terminal events to deliver);
        otherwise a step-info dict for streaming front ends:

            streams   — {rid: np.ndarray} tokens committed THIS round per
                        live request (only when ``collect_streams`` is set;
                        the sync path never pulls the token buffer)
            finished  — rids completed and released this step
            cancelled — rids cancelled this step
            expired   — rids expired at admission (deadline already passed)
            failed    — rids failed terminally (reason in metrics)
            preempted — rids evicted + re-queued this step (NOT terminal)
            round     — the RoundEvent.round id of this round (stream events
                        join the obs layer through it); None for a
                        notification-only step where no round ran
            queue_depth / n_live — scheduler pressure while the round ran

        ``run()`` is exactly ``while step() is not None`` — the synchronous
        and async serving paths share this one round loop, which is what
        keeps their token streams byte-identical.

        The step's host work runs under tracer spans (profiler annotations
        even when the tracer is off): ``server.step`` around it all, then
        ``step.admit``, ``step.prefill``, ``step.tables``, ``step.round``
        (``round.dispatch``, ``round.sync``; the same interval as
        ``RoundEvent.t_round``) and ``step.harvest`` (``harvest.pull``).
        """
        with self.tracer.span("server.step", step=self.total_steps):
            return self._step()

    def _step(self) -> Optional[Dict]:
        if self._state is None:
            self._state = self._empty_state()
            self._lengths = np.array(self._state.length)
        step_idx = self.total_steps
        self.total_steps += 1
        tr = self.tracer
        with tr.span("step.admit"):
            delta = self.faults.pool_delta(step_idx)
            if delta > 0:
                self.alloc.seize(delta)
            elif delta < 0:
                self.alloc.release_seized(-delta)
            cancelled = self._process_cancels()
            self._round_prefill_tokens = 0
            self._round_prefill_chunks = 0
            self._round_prefill_t = 0.0
            self._state = self._refill(self._state, self._lengths)
            expired = self.sched.drain_expired()
        # interleaved chunked prefill: one chunk program per step, BEFORE the
        # decode round, so a row whose suffix completes decodes this step
        with tr.span("step.prefill"):
            self._state = self._advance_prefills(self._state)
        with tr.span("step.tables"):
            self._state = self._sync_tables(self._state)
        if not any(r is not None for r in self._slots):
            self._batch_drained()
            failed = self._drain_failed()
            if cancelled or expired or failed or self.sched.has_work():
                # nothing live, but terminal events need delivery, or queued
                # work is stalled on transient (seized) pressure — emit a
                # notification-only step so front ends see the events and
                # the loop outlives the squeeze
                return {"streams": {}, "finished": [], "cancelled": cancelled,
                        "expired": expired, "failed": failed,
                        "preempted": self._drain_aborted([]),
                        "round": None, "queue_depth": len(self.sched.queue),
                        "n_live": 0}
            return None
        if all(b in self._masked for b in range(self.B)
               if self._slots[b] is not None):
            # every occupied row is still mid-prefill: no decode round to
            # run — deliver events and keep stepping (the next steps keep
            # advancing chunks until a row activates)
            return {"streams": {}, "finished": [], "cancelled": cancelled,
                    "expired": expired, "failed": self._drain_failed(),
                    "preempted": self._drain_aborted([]), "round": None,
                    "queue_depth": len(self.sched.queue), "n_live": 0}

        # gamma/AR decision (paper Eq. 1, telemetry alpha): decided at batch
        # formation, then re-decided online while speculative. Spec->spec
        # retunes are safe (both caches are maintained every speculative
        # round) and spec->AR downgrades when measured alpha makes Eq. 1
        # infeasible; AR->spec is one-way OFF within a batch because the
        # drafter KV is not written during AR rounds (it resynchronizes at
        # the next batch formation, when no stale row is live).
        if self._gamma_override is not None:
            self.gamma = self._gamma_override
        elif not self._batch_formed or self.gamma > 0:
            self.gamma, _ = self.sched.choose_gamma(
                self._alpha_override, self._c_override or self._measured_c())
        self._batch_formed = True
        if self._degraded:
            # degradation wins over a pinned gamma: a tripped watchdog or a
            # failed drafter keeps the batch on AR until it drains
            self.gamma = 0

        # overcommit: grow every live row to this round's block demand,
        # evicting victims when the pool is dry; tables changed -> re-sync
        with tr.span("step.tables"):
            self._state, preempted = self._ensure_capacity(self._state)
            preempted += self._drain_aborted(preempted)
            self._state = self._sync_tables(self._state)
        if not any(r is not None for r in self._slots):
            # extreme pressure evicted the whole batch; deliver and retry
            self._batch_drained()
            return {"streams": {}, "finished": [], "cancelled": cancelled,
                    "expired": expired, "failed": self._drain_failed(),
                    "preempted": preempted, "round": None,
                    "queue_depth": len(self.sched.queue), "n_live": 0}

        queue_depth = len(self.sched.queue)
        prev_len = self._lengths
        with tr.span("step.round", round=self.total_rounds, gamma=self.gamma):
            t0 = tr.clock()
            with tr.span("round.dispatch"):
                phase_t = self._dispatch_round(step_idx)
            # account AFTER execution so a degraded round is charged as the
            # AR round that actually ran, not the spec round that died
            blocks_read, blocks_written = self._account_round(prev_len)
            self.total_rounds += 1
            # ONE host sync per round: lengths + active in a single pull;
            # the harvest/refill below reuse the same snapshot
            with tr.span("round.sync"):
                lengths, active = map(np.array, jax.device_get(
                    (self._state.length, self._state.active)))
            fault_delay = self.faults.round_delay(step_idx)
            t_round = tr.clock() - t0 + fault_delay  # dispatch -> sync
                                   # (+ injected virtual straggle, if any)
        if self.gamma > 0 and self.watchdog.observe(t_round):
            self.metrics.degrade(self.total_rounds,
                                 "watchdog: straggling speculative rounds")
            self._degraded = True  # takes effect next round
        self._lengths = lengths
        if self.faults.corrupts(step_idx):
            self._corrupt_one_row(lengths)
        with tr.span("step.harvest"):
            emitted = lengths - prev_len
            rids = [r.rid if r is not None else None for r in self._slots]
            self.metrics.record_round(np.maximum(emitted - 1, 0), self.gamma,
                                      active, rids)
            streams = self._harvest_streams(prev_len, lengths)
            ev_lengths = lengths.copy()   # _harvest's refill mutates
                                          # `lengths` in place for newly
                                          # admitted rows; the event must see
                                          # THIS round's commit
            done_before = len(self.done)
            self._state = self._harvest(self._state, lengths)
            expired += self.sched.drain_expired()  # harvest-refill expiries
            failed = self._drain_failed()
            self._record_event(prev_len, ev_lengths, active, rids, t_round,
                               phase_t, blocks_read, blocks_written,
                               queue_depth, n_preempted=len(preempted),
                               n_expired=len(expired), n_failed=len(failed),
                               fault_delay=fault_delay)
        return {"streams": streams,
                "finished": [r.rid for r in self.done[done_before:]],
                "cancelled": cancelled,
                "expired": expired,
                "failed": failed,
                "preempted": preempted,
                "round": self.total_rounds - 1,
                "queue_depth": queue_depth,
                "n_live": int(np.sum(active))}

    def _dispatch_round(self, step_idx: int) -> dict:
        """Dispatch this step's round onto ``self._state``: the jitted
        speculative round, or the AR round (gamma 0, or a drafter failure
        that degrades the batch). Returns the traced round's phase times
        ({} off the traced path)."""
        if self.gamma > 0:
            eng = self._engine(self.gamma)
            try:
                # the injected drafter failure raises BEFORE dispatch (device
                # state intact, nothing donated), so the batch can degrade
                # to AR. Any other error in the round propagates: it may
                # have consumed the donated state, and hiding it would let
                # a broken program pass for a slow one.
                if self.faults.drafter_fails(step_idx):
                    raise DrafterFault(
                        f"injected drafter failure at step {step_idx}")
                if isinstance(eng._round_jit, TracedRound):
                    self._state = eng._round_jit(
                        self.params_t, self.params_d, self._state,
                        round=self.total_rounds, gamma=self.gamma)
                    return eng._round_jit.last_phase_times
                self._state = eng._round_jit(self.params_t, self.params_d,
                                             self._state)
                return {}
            except DrafterFault as e:
                # degrade the batch to AR (one-way until it drains) instead
                # of wedging the server
                self.metrics.degrade(self.total_rounds,
                                     f"spec round failed: {e}")
                self._degraded = True
                self.gamma = 0
                with self.tracer.span("ar_round", phase="verify",
                                      role="target", round=self.total_rounds):
                    self._state = self._ar_round(self._state)
                return {}
        with self.tracer.span("ar_round", phase="verify",
                              role="target", round=self.total_rounds):
            self._state = self._ar_round(self._state)
            if self.tracer.enabled:
                jax.block_until_ready(self._state.length)
        return {}

    def _corrupt_one_row(self, lengths):
        """Fault injection: poison the newest committed token of the first
        emitting row to an out-of-vocab id — the output guard must fail that
        request cleanly instead of streaming the garbage."""
        for b, req in enumerate(self._slots):
            if req is None:
                continue
            cur = int(min(lengths[b], self._target_len[b]))
            if cur > req.prompt_len:
                self._state = self._state._replace(
                    tokens=self._state.tokens.at[b, cur - 1].set(self._vocab))
                return

    def _harvest_streams(self, prev_len, lengths) -> Dict[int, np.ndarray]:
        """Newly committed tokens per live request this round (committed ==
        final: verify already accepted them, so streaming is exact). TTFT is
        stamped here for every path; the token pull itself happens only when
        a streaming front end asked for it. Streamed tokens pass the output
        guard first — a poisoned token FAILS the request instead of reaching
        a client (the sync path's guard lives in ``_harvest``)."""
        streams: Dict[int, np.ndarray] = {}
        tok_host = None
        for b, req in enumerate(self._slots):
            if req is None or b in self._masked:
                continue        # mid-prefill: nothing committed yet
            cur = int(min(lengths[b], self._target_len[b]))
            if cur > req.prompt_len:
                self.metrics.first_token(req.rid)   # idempotent
            if not self.collect_streams or cur <= int(prev_len[b]):
                continue
            if tok_host is None:   # one bulk pull for all emitting rows
                with self.tracer.span("harvest.pull"):
                    tok_host = np.asarray(jax.device_get(self._state.tokens))
            new = tok_host[b, int(prev_len[b]):cur].copy()
            if ((new < 0) | (new >= self._vocab)).any():
                self._fail_row(b, req, cur)
                self._state = self._state._replace(
                    active=self._state.active.at[b].set(False))
                continue
            streams[req.rid] = new
        return streams

    def _record_event(self, prev_len, lengths, active, rids, t_round,
                      phase_t, blocks_read, blocks_written, queue_depth=0,
                      n_preempted=0, n_expired=0, n_failed=0,
                      fault_delay=0.0):
        """One RoundEvent per round (always, traced or not) + a drift
        observation per speculative round (phase times when traced)."""
        emitted = lengths - prev_len
        accepted = tuple(int(max(e - 1, 0))
                         for e, a in zip(emitted, active) if a)
        live_rids = tuple(r for r, a in zip(rids, active)
                          if a and r is not None)
        self.events.record(RoundEvent(
            round=self.total_rounds - 1, gamma=self.gamma,
            n_active=int(np.sum(active)), accepted=accepted,
            emitted=int(emitted[active].sum()) if active.any() else 0,
            t_round=t_round,
            t_draft=phase_t.get("draft"), t_verify=phase_t.get("verify"),
            t_commit=phase_t.get("commit"),
            blocks_read=blocks_read, blocks_written=blocks_written,
            rids=live_rids, t_wall=clock.wall(), queue_depth=queue_depth,
            n_preempted=n_preempted, n_expired=n_expired, n_failed=n_failed,
            degraded=self._degraded, fault_delay=fault_delay,
            prefill_tokens=self._round_prefill_tokens,
            prefill_chunks=self._round_prefill_chunks,
            t_prefill=(self._round_prefill_t
                       if self._round_prefill_chunks else None),
            prefix_hit_rate=self.metrics.prefix_hit_rate()))
        if self.gamma > 0:
            if self.drift is None:
                c = (self._c_override if self._c_override is not None
                     else self.scfg.cost_coefficient)
                self.drift = DriftMonitor(self.gamma, c)
            self.drift.observe(t_round=t_round,
                               t_draft=phase_t.get("draft"),
                               t_verify=phase_t.get("verify"),
                               t_commit=phase_t.get("commit"),
                               t_prefill=(self._round_prefill_t
                                          if self._round_prefill_chunks
                                          else None),
                               gamma=self.gamma)
