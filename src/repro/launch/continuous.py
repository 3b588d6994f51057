"""Continuous-batching speculative server (beyond-paper serving layer).

Per-row speculation (core/batched_engine.py) lets rows advance independently,
but a fixed batch still waits for its slowest member. This server closes the
loop: when a row finishes, its slot is immediately REFILLED from the request
queue — one-row prefill, scatter into the live batch caches — so the batch
stays full and the 3.1x committed-tokens/round advantage becomes wall-clock
throughput (vLLM-style continuous batching, driven by the speculative round).

Constraints: KV-cache families; uniform (prompt_len, max_new) per server
instance (fixed XLA shapes); greedy acceptance. The paged successor
(repro.serving.PagedSpecServer) removes the uniform-shape constraint via
block-pool KV storage — prefer it for ragged traffic; this server remains
the minimal fixed-shape reference (see docs/DESIGN.md §4).

``python -m repro.launch.continuous --arch <id> --smoke`` drives it through
the ``repro.api.Session`` facade on a uniform synthetic stream; constructing
ContinuousSpecServer directly is deprecated (migration: docs/API.md).
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Any, Deque, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rounds
from repro.core.batched_engine import (BatchedEngineConfig, BatchedSpecEngine,
                                       RowState)
from repro.obs import clock
from repro.obs.trace import NULL_TRACER


@dataclass
class StreamRequest:
    rid: int
    prompt: np.ndarray
    tokens: Optional[np.ndarray] = None
    rounds_in_flight: int = 0


class ContinuousSpecServer:
    def __init__(self, target, drafter, params_t, params_d, *,
                 batch: int = 4, prompt_len: int = 12, max_new: int = 24,
                 gamma: int = 4, engine: Optional[BatchedSpecEngine] = None,
                 placement=None, tracer=None):
        """``engine`` lets callers share one (jit-cached) engine across
        server instances; it must have been built with the same gamma.
        ``placement`` (api/placement.py) runs the rounds placed — per-role
        submeshes with the drafter cache resident on the drafter mesh; slot
        refills pin the one-row prefill onto the right submesh before the
        scatter."""
        assert engine is None or engine.ecfg.gamma == gamma
        if engine is not None and placement is not None \
                and placement.heterogeneous:
            ep = engine.placement
            if ep is None or (ep.drafter.devices, ep.target.devices) != \
                    (placement.drafter.devices, placement.target.devices):
                raise ValueError(
                    "shared engine was built without this placement — build "
                    "it with BatchedSpecEngine(..., placement=...) or drop one")
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.engine = engine or BatchedSpecEngine(
            target, drafter, BatchedEngineConfig(gamma=gamma),
            placement=placement, tracer=self.tracer)
        self.placement = self.engine.placement
        if self.placement is not None:
            params_t = self.placement.target.put_params(target, params_t)
            params_d = self.placement.drafter.put_params(drafter, params_d)
        self.params_t, self.params_d = params_t, params_d
        self.B, self.P, self.max_new, self.gamma = batch, prompt_len, max_new, gamma
        self.max_len = prompt_len + max_new + gamma + 2
        self.queue: Deque[StreamRequest] = deque()
        self.done: List[StreamRequest] = []
        self._slots: List[Optional[StreamRequest]] = [None] * batch
        self._state: Optional[RowState] = None
        self._prefill_jit = None
        self._insert_jit = None
        self.n_accepted_total = 0     # accepted draft tokens across rounds
        self.n_drafted_total = 0      # drafted tokens across rounds

    # ------------------------------------------------------------ plumbing
    def _prefill_one(self, prompt):
        """B=1 prefill -> (buf_row [T], dcache1, tcache1) with per-row index.
        Placed serving runs each role's prefill as its own program on its
        own submesh (one jit cannot span two meshes)."""
        if self._prefill_jit is None:
            eng = self.engine
            slack = self.gamma + 2

            def prefill_t(pt, prompt):
                buf = jnp.zeros((1, self.max_len), jnp.int32)
                buf = jax.lax.dynamic_update_slice(buf, prompt, (0, 0))
                tc = eng.target.init_cache(1, eng.target.cache_len(self.max_len),
                                           spec_slack=slack)
                _, tc, _ = eng.target.apply(pt, prompt[:, :-1], tc)
                return buf, tc

            def prefill_d(pd, prompt):
                dc = eng.drafter.init_cache(1, eng.drafter.cache_len(self.max_len),
                                            spec_slack=slack)
                _, dc, _ = eng.drafter.apply(pd, prompt[:, :-1], dc)
                return dc

            if self.placement is None:
                def prefill(pt, pd, prompt):
                    buf, tc = prefill_t(pt, prompt)
                    return buf, prefill_d(pd, prompt), tc
                self._prefill_jit = jax.jit(prefill)
            else:
                t_jit, d_jit = jax.jit(prefill_t), jax.jit(prefill_d)
                pm = self.placement

                def prefill(pt, pd, prompt):
                    buf, tc = t_jit(pt, pm.to_target(prompt))
                    return buf, d_jit(pd, pm.to_drafter(prompt)), tc
                self._prefill_jit = prefill
        with self.tracer.span("prefill", phase="prefill", role="target"):
            out = self._prefill_jit(self.params_t, self.params_d,
                                    jnp.asarray(prompt[None], jnp.int32))
            if self.tracer.enabled:
                jax.block_until_ready(out)
        return out

    def _insert_row(self, state: RowState, b: int, buf1, dc1, tc1):
        """Scatter a one-row prefill into live batch state at slot b.
        Structural rule: KV caches are [L, B, ...] -> batch axis 1; per-row
        index vectors are [B] -> axis 0. Placed serving pins the one-row
        pieces onto their role submeshes first so the scatters stay
        colocated with the live state."""
        if self.placement is not None:
            buf1 = self.placement.to_target(buf1)
            tc1 = self.placement.to_target(tc1)
            dc1 = self.placement.to_drafter(dc1)
        def put_cache(batched, one):
            if batched.ndim >= 2 and one.ndim == batched.ndim \
                    and one.shape[1] == 1 and batched.shape[0] == one.shape[0]:
                return batched.at[:, b].set(one[:, 0])
            if batched.ndim == 1 and one.ndim == 0:
                return batched.at[b].set(one)
            if batched.ndim == 1 and one.ndim == 1 and one.shape[0] == 1:
                return batched.at[b].set(one[0])
            return batched

        new_tc = jax.tree.map(put_cache, state.tcache,
                              {**tc1, "index": jnp.full((1,), self.P - 1, jnp.int32)})
        new_dc = jax.tree.map(put_cache, state.dcache,
                              {**dc1, "index": jnp.full((1,), self.P - 1, jnp.int32)})
        tokens = state.tokens.at[b].set(buf1[0])
        length = state.length.at[b].set(self.P)
        active = state.active.at[b].set(True)
        return state._replace(tokens=tokens, length=length, active=active,
                              tcache=new_tc, dcache=new_dc)

    # -------------------------------------------------------------- serving
    def submit(self, req: StreamRequest):
        assert len(req.prompt) == self.P
        self.queue.append(req)

    def _bootstrap(self):
        first = [self.queue.popleft() for _ in range(min(self.B, len(self.queue)))]
        prompts = np.stack([r.prompt for r in first])
        while len(first) < self.B:          # pad with copies of the last
            first.append(StreamRequest(-1, first[-1].prompt))
            prompts = np.vstack([prompts, first[-1].prompt[None]])
        eng = self.engine
        B, P = self.B, self.P
        buf = jnp.zeros((B, self.max_len), jnp.int32)
        buf = jax.lax.dynamic_update_slice(
            buf, jnp.asarray(prompts, jnp.int32), (0, 0))
        slack = self.gamma + 2
        tc = eng.target.init_cache(B, eng.target.cache_len(self.max_len), spec_slack=slack)
        dc = eng.drafter.init_cache(B, eng.drafter.cache_len(self.max_len), spec_slack=slack)
        _, tc, _ = eng.target.apply(self.params_t, jnp.asarray(prompts[:, :-1]), tc)
        _, dc, _ = eng.drafter.apply(self.params_d, jnp.asarray(prompts[:, :-1]), dc)
        tc = {**tc, "index": jnp.full((B,), P - 1, jnp.int32)}
        dc = {**dc, "index": jnp.full((B,), P - 1, jnp.int32)}
        st = RowState(tokens=buf, length=jnp.full((B,), P, jnp.int32),
                      dcache=dc, tcache=tc,
                      active=jnp.ones((B,), bool),
                      n_rounds=jnp.zeros((), jnp.int32),
                      n_accepted=jnp.zeros((B,), jnp.int32),
                      n_drafted=jnp.zeros((), jnp.int32))
        if self.placement is not None:
            st = rounds.place_state(st, self.placement, eng.target,
                                    eng.drafter)
        self._state = st
        self._slots = first

    def run(self):
        """Drain the queue; returns completed requests. Rounds touch the WHOLE
        batch; finished rows are emitted and hot-swapped without a barrier."""
        if self._state is None:
            self._bootstrap()
        eng = self.engine
        if eng._round_jit is None:
            eng._round_jit = jax.jit(lambda pt, pd, s: eng.round(pt, pd, s))
        target_len = self.P + self.max_new
        n_rounds = 0
        traced = isinstance(eng._round_jit, rounds.TracedRound)
        while any(r is not None and r.rid >= 0 for r in self._slots):
            prev_len = np.asarray(self._state.length)
            prev_active = np.asarray(self._state.active)
            if traced:
                rids = tuple(r.rid for r in self._slots
                             if r is not None and r.rid >= 0)
                self._state = eng._round_jit(self.params_t, self.params_d,
                                             self._state, round=n_rounds,
                                             rids=rids)
            else:
                self._state = eng._round_jit(self.params_t, self.params_d,
                                             self._state)
            n_rounds += 1
            lengths = np.asarray(self._state.length)
            # acceptance telemetry: each active row emits n_accepted+1 tokens
            emitted = (lengths - prev_len)[prev_active]
            self.n_accepted_total += int(np.maximum(emitted - 1, 0).sum())
            self.n_drafted_total += int(prev_active.sum()) * self.gamma
            for b in range(self.B):
                req = self._slots[b]
                if req is None or req.rid < 0:
                    continue
                req.rounds_in_flight += 1
                if lengths[b] >= target_len:
                    req.tokens = np.asarray(self._state.tokens[b, :target_len])
                    self.done.append(req)
                    if self.queue:
                        nxt = self.queue.popleft()
                        buf1, dc1, tc1 = self._prefill_one(nxt.prompt)
                        self._state = self._insert_row(self._state, b, buf1, dc1, tc1)
                        self._slots[b] = nxt
                    else:
                        # freeze the slot: no more commits, no buffer overflow
                        self._state = self._state._replace(
                            active=self._state.active.at[b].set(False))
                        self._slots[b] = StreamRequest(-1, req.prompt)
        self.total_rounds = n_rounds
        return self.done


def main():
    import argparse

    from repro.api import DeploymentSpec, Planner, Session
    from repro.launch import cli_args

    ap = argparse.ArgumentParser()
    cli_args.add_model_args(ap)
    cli_args.add_traffic_args(ap)
    cli_args.add_spec_args(ap)
    cli_args.add_trace_args(ap)
    ap.add_argument("--batch", type=int, default=4,
                    help="live slots in the continuous batch")
    args = ap.parse_args()

    mt, md, pt, pd, cfg_t = cli_args.build_pair(args.arch, args.smoke)
    spec = DeploymentSpec(batch_size=args.batch,
                          prompt_lens=(args.prompt_len,),
                          max_new=args.max_new, streaming=True,
                          alpha=args.alpha,
                          cost_coefficient=args.cost_coefficient,
                          adaptive_gamma=False)
    plan = Planner(spec).plan()
    if args.gamma is not None:          # --gamma trumps the planner
        import dataclasses as _dc
        plan = _dc.replace(plan,
                           gamma=_dc.replace(plan.gamma, gamma=args.gamma))
    gamma = plan.gamma.gamma
    plan = cli_args.apply_placement_arg(plan, args.placement)
    sess = Session(mt, md, pt, pd, plan, max_batch=args.batch,
                   tracer=cli_args.make_tracer(args))
    if args.placement:
        print(sess.placement.describe())

    rng = np.random.default_rng(0)
    reqs = [sess.request(rng.integers(0, cfg_t.vocab_size, args.prompt_len),
                         args.max_new, rid=i) for i in range(args.requests)]
    t0 = clock.wall()
    done = sess.serve(reqs)
    dt = clock.wall() - t0
    total = sum(len(r.tokens) - r.prompt_len for r in done)
    print(f"continuous-served {len(done)} requests, {total} tokens in "
          f"{dt:.2f}s ({total / dt:.1f} tok/s aggregate, gamma={gamma}"
          f"{' [forced]' if args.gamma is not None else ' [cost-model]'}, "
          f"B={args.batch}, backend={sess.backend_name})")
    cli_args.report_telemetry(sess, args)


if __name__ == "__main__":
    cli_args.enable_compile_cache()
    main()

