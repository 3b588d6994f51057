"""One batch-native speculative round core: draft -> verify -> commit -> rollback.

Every speculative execution path in the repo — the single-stream
``SpecEngine`` (batch-synchronized commits), the per-row
``BatchedSpecEngine``, the fixed-shape ``ContinuousSpecServer`` and the
paged ``PagedSpecServer`` — drives THIS module's ``spec_round()`` /
``ar_round()``. The round is generic over three seams:

  * **cache layout** via the ``CacheOps`` protocol (``repro.cache.ops``):
    ring buffers and paged block pools both expose
    init/spec/write/rollback/live_bound, so the round neither knows nor
    cares where the KV lives;
  * **draft strategy** via ``DraftPolicy``: ``LinearDraftPolicy`` is classic
    γ-step speculative sampling (Leviathan et al.); ``MultiDraftPolicy``
    drafts k candidate chains per row (top-k first-token alternates, greedy
    continuations), verifies all k in ONE stacked target pass, and commits
    the best accepted prefix — greedy mode, recompute (no-cache)
    verification; ``TreeDraftPolicy`` is its cached successor: a W-wide
    chain tree drafted against branch caches (ring rows replicated, paged
    tables CoW-forked), verified in ONE stacked cached target pass through
    the tree-attention kernel (``Model.apply(tree=...)``), winner path
    committed by cache compaction — greedy or sampled (multi-path rejection
    sampling keeps sampled mode lossless);
  * **commit semantics**: ``"per_row"`` (each row commits its own accepted
    prefix — serving) or ``"batch_min"`` (batch-synchronized commit of the
    batch-minimum emitted length — exact standard speculative sampling at
    B=1, the paper's operating point).

Greedy verification dispatches to the fused Pallas argmax kernel
(``kernels.spec_verify``) on TPU and to the jnp oracle
(``core.acceptance``) elsewhere; both are token-identical (tested in
interpret mode).

The three phases are exposed separately (``phase_fns``) so
``benchmarks/bench_strategies.py`` can time draft/verify/commit
individually — the phase functions ARE the round: ``spec_round`` is their
composition, nothing more.

CI grep guard: the draft-loop body is called ``dstep`` and must exist only
in this file — a second copy anywhere else is the duplication this module
deleted growing back.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache import ops as cache_ops
from repro.core import acceptance
from repro.core.tree import chain_tree
from repro.obs.trace import NULL_TRACER

COMMIT_MODES = ("batch_min", "per_row")


# ==================================================================== state
class RoundState(NamedTuple):
    """The one generation state every engine threads through the round core.

    ``length`` (and the derived stats) may be a scalar (batch-synchronized
    engines: all rows share one committed length) or a per-row ``[B]``
    vector (per-row/serving engines). ``active`` marks serving rows that
    still commit (frozen slots draft along but commit nothing); ``None``
    means all rows are live. ``t_off``/``d_off`` shift cache indices past
    any modality prefix the cache also holds (VLM vision tokens).
    """
    tokens: jnp.ndarray            # [B, T] token buffer
    length: jnp.ndarray            # scalar or [B] committed tokens
    dcache: Any = None
    tcache: Any = None
    key: Any = None                # PRNG key (sampled mode; None if greedy)
    active: Any = None             # [B] bool or None (= all rows live)
    n_rounds: Any = 0              # scalar
    n_accepted: Any = 0            # scalar (batch_min) or [B] (per_row)
    n_drafted: Any = 0             # scalar
    extras_t: Any = None           # modality extras (encdec cross, ...)
    extras_d: Any = None
    t_off: Any = 0                 # cache-index offset vs text length (VLM)
    d_off: Any = 0


class DraftOut(NamedTuple):
    """Draft-phase output: K candidate chains of gamma tokens per row."""
    drafts: jnp.ndarray            # [B, K, G] drafted tokens
    q_logits: Any                  # [B, K, G, V] drafter logits or None
    cand_tokens: Any               # [B, K, T] no-cache candidate buffers
    t_last: Any                    # [B] last committed token (cached path)
    dcache: Any = None
    snaps: Any = None              # stateful-drafter state trail (or 0)
    key: Any = None


class VerifyOut(NamedTuple):
    """Verify-phase output: per-row acceptance + the commit base buffer."""
    res: acceptance.VerifyResult   # n_accepted/out_tokens/n_emitted, [B]-shaped
    base_tokens: jnp.ndarray       # [B, T] buffer the commit scatters into
    tcache: Any = None
    key: Any = None


# ================================================================== helpers
def _write_col(tokens, pos, vals):
    """tokens[:, pos] = vals (pos is a traced scalar)."""
    return jax.lax.dynamic_update_slice(
        tokens, vals.astype(tokens.dtype)[:, None], (0, pos))


def _slice_logits(logits, start, width):
    B, T, V = logits.shape
    return jax.lax.dynamic_slice(logits, (0, start, 0), (B, width, V))


def _slice_tokens(tokens, start, width):
    B, T = tokens.shape
    return jax.lax.dynamic_slice(tokens, (0, start), (B, width))


def _gather_last(tokens, length):
    """tokens[b, length[b]-1] per row (length scalar or [B])."""
    B = tokens.shape[0]
    lvec = jnp.broadcast_to(jnp.asarray(length), (B,))
    return jnp.take_along_axis(tokens, (lvec - 1)[:, None], axis=1)[:, 0]


def _state_leaves(cache):
    """Small recurrent-state leaves (state/conv) — the only parts of a cache
    that need a per-step trail; KV ring buffers roll back by index."""
    from repro.models.specs import _path_str
    out = {}

    def walk(path, leaf):
        ps = _path_str(path)
        if ps.split("/")[-1] in ("state", "conv"):
            out[ps] = leaf
        return leaf

    jax.tree_util.tree_map_with_path(walk, cache)
    return out


def _restore_state_leaves(cache, snaps, j):
    """Rebuild cache with state leaves from scan-stacked snapshot j."""
    from repro.models.specs import _path_str

    def fix(path, leaf):
        ps = _path_str(path)
        if ps in snaps:
            return jnp.take(snaps[ps], j, axis=0)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, cache)


def _take_candidate(x, win):
    """x: [B, K, ...] -> winner candidate per row: [B, ...]."""
    B, K = x.shape[:2]
    idx = win.reshape((B,) + (1,) * (x.ndim - 1))
    return jnp.take_along_axis(x, idx, axis=1)[:, 0]


def _replicate_rows(cache, W):
    """Row-replicate a ring KV cache for tree drafting: [B] rows -> [B*W]
    branch rows (branch w of row b is row b*W + w). KV-family caches only —
    the drafter's branches are LINEAR chains, so each replica just runs
    plain causal decode steps."""
    if W == 1:
        return cache
    if not (isinstance(cache, dict) and "k" in cache and "v" in cache):
        raise NotImplementedError(
            "tree drafting needs a KV-family drafter cache")
    out = dict(cache)
    out["k"] = jnp.repeat(cache["k"], W, axis=1)
    out["v"] = jnp.repeat(cache["v"], W, axis=1)
    idx = jnp.asarray(cache["index"])
    if idx.ndim:
        out["index"] = jnp.repeat(idx, W, axis=0)
    return out


def _take_branch(cache, winner, W):
    """Inverse of ``_replicate_rows``: keep each row's winning branch from a
    [B*W]-row cache -> [B] rows. The winner's replica holds exactly the
    committed chain's KV at contiguous positions, so it simply BECOMES the
    next round's drafter cache (no compaction needed on the drafter side)."""
    if W == 1:
        return cache
    B = winner.shape[0]
    out = dict(cache)
    for kk in ("k", "v"):
        leaf = cache[kk]
        resh = leaf.reshape(leaf.shape[0], B, W, *leaf.shape[2:])
        idx = winner.reshape(1, B, 1, *([1] * (leaf.ndim - 2)))
        out[kk] = jnp.take_along_axis(resh, idx, axis=2)[:, :, 0]
    idx0 = jnp.asarray(cache["index"])
    if idx0.ndim:
        out["index"] = jnp.take_along_axis(idx0.reshape(B, W),
                                           winner[:, None], axis=1)[:, 0]
    return out


def _is_paged_branched(dcache, B):
    """A paged drafter cache whose table has B*W rows was pre-branched by
    the host (PagedTreeRound CoW forks); shapes are static under jit."""
    return (isinstance(dcache, dict) and "block_table" in dcache
            and dcache["block_table"].shape[0] != B)


# ================================================================= policies
@dataclass(frozen=True)
class LinearDraftPolicy:
    """Classic speculative sampling: ONE chain of gamma sequential draft
    steps per row. Works cached (single-token incremental steps) and
    no-cache (full-buffer recompute per step), greedy or sampled."""
    name: str = "linear"
    k: int = 1

    def draft_cached(self, drafter, params_d, state: RoundState, spec,
                     live0) -> DraftOut:
        G = spec.gamma
        ex_d = state.extras_d or {}
        t_last = _gather_last(state.tokens, state.length)

        def dstep(carry, i):
            tok, cache, k = carry
            ml = None if live0 is None else live0 + i
            logits, cache, _ = drafter.apply(params_d, tok[:, None], cache,
                                             logits_slice="last",
                                             max_live=ml, **ex_d)
            q = logits[:, -1]
            if spec.greedy:
                nxt = jnp.argmax(q, axis=-1)
            else:
                k, ks = jax.random.split(k)
                nxt = jax.random.categorical(ks, q / spec.temperature,
                                             axis=-1)
            nxt = nxt.astype(jnp.int32)
            snap = _state_leaves(cache) if spec.d_stateful else 0
            return (nxt, cache, k), (nxt, q, snap)

        # +1 step for stateful drafters so the snapshot trail covers the
        # full-acceptance rollback target
        n_steps = G + 1 if spec.d_stateful else G
        (_, dcache, key), (drafts, q_logits, snaps) = jax.lax.scan(
            dstep, (t_last, state.dcache, state.key), jnp.arange(n_steps))
        drafts = jnp.moveaxis(drafts, 0, 1)[:, :G]             # [B, G]
        q_logits = jnp.moveaxis(q_logits, 0, 1)[:, :G]
        return DraftOut(drafts=drafts[:, None], q_logits=q_logits[:, None],
                        cand_tokens=None, t_last=t_last, dcache=dcache,
                        snaps=snaps, key=key)

    def draft_nocache(self, drafter, params_d, state: RoundState,
                      spec) -> DraftOut:
        G = spec.gamma
        ex_d = state.extras_d or {}
        length = state.length

        def dstep(carry, i):
            toks, k = carry
            logits, _, _ = drafter.apply(params_d, toks, **ex_d)
            pos = length - 1 + i
            q_i = _slice_logits(logits, pos, 1)[:, 0]          # [B, V]
            if spec.greedy:
                d_i = jnp.argmax(q_i, axis=-1)
            else:
                k, ks = jax.random.split(k)
                d_i = jax.random.categorical(ks, q_i / spec.temperature,
                                             axis=-1)
            toks = _write_col(toks, pos + 1, d_i)
            return (toks, k), q_i

        (cand, key), q_logits = jax.lax.scan(
            dstep, (state.tokens, state.key), jnp.arange(G))
        q_logits = jnp.moveaxis(q_logits, 0, 1)                # [B, G, V]
        drafts = _slice_tokens(cand, length, G)
        return DraftOut(drafts=drafts[:, None], q_logits=q_logits[:, None],
                        cand_tokens=cand[:, None], t_last=None, key=key)


@dataclass(frozen=True)
class MultiDraftPolicy:
    """k parallel draft candidates per row: the drafter's top-k FIRST tokens
    each continued greedily, all k verified in ONE stacked target pass, the
    best accepted prefix committed. Recovers first-position drafter misses
    the target's argmax would have covered — the low-acceptance regime where
    linear drafting stalls at ~1 token/round.

    Greedy-only (best-of-k selection is not distribution-preserving under
    stochastic acceptance) and no-cache only (a cached verify would need
    k-replicated target rows or tree attention — the seam this policy
    proves is exactly where tree speculation plugs in, see ROADMAP).
    Token-identity: every candidate's emission is a prefix of THE target
    greedy continuation (accepted drafts equal the target argmax at each
    position given the shared committed prefix), so committing the longest
    one is still exact greedy decoding.
    """
    name: str = "multi"
    k: int = 2

    def draft_cached(self, drafter, params_d, state, spec, live0):
        raise NotImplementedError(
            "multi-draft needs recompute (no-cache) verification; cached "
            "k-candidate verify requires tree attention (roadmap)")

    def draft_nocache(self, drafter, params_d, state: RoundState,
                      spec) -> DraftOut:
        assert spec.greedy, "MultiDraftPolicy is greedy-only"
        K, G = self.k, spec.gamma
        tokens, length = state.tokens, state.length
        B, T = tokens.shape
        ex_d = state.extras_d or {}
        ex_k = {kk: jnp.repeat(v, K, axis=0) for kk, v in ex_d.items()}

        # chain heads: the drafter's top-k next tokens after the prefix
        logits, _, _ = drafter.apply(params_d, tokens, **ex_d)
        q0 = _slice_logits(logits, length - 1, 1)[:, 0]        # [B, V]
        _, heads = jax.lax.top_k(q0, K)                        # [B, K]
        cand = jnp.repeat(tokens[:, None], K, axis=1)          # [B, K, T]
        cand = _write_col(cand.reshape(B * K, T), length,
                          heads.reshape(B * K)).reshape(B, K, T)

        def dstep(cand, i):
            flat = cand.reshape(B * K, T)
            lg, _, _ = drafter.apply(params_d, flat, **ex_k)
            pos = length - 1 + i
            q_i = _slice_logits(lg, pos, 1)[:, 0]              # [B*K, V]
            d_i = jnp.argmax(q_i, axis=-1).astype(jnp.int32)
            return _write_col(flat, pos + 1, d_i).reshape(B, K, T), None

        if G > 1:
            cand, _ = jax.lax.scan(dstep, cand, jnp.arange(1, G))
        drafts = _slice_tokens(cand.reshape(B * K, T),
                               length, G).reshape(B, K, G)
        return DraftOut(drafts=drafts, q_logits=None, cand_tokens=cand,
                        t_last=None, key=state.key)


@dataclass(frozen=True)
class TreeDraftPolicy:
    """Tree drafting: ``width`` chains branching once at the root, drafted
    against branch caches and verified in ONE stacked CACHED target pass
    through the tree-attention kernel (``Model.apply(tree=...)``) — the
    cached successor ``MultiDraftPolicy``'s no-cache gate pointed at.

    Draft: one root step on the unbranched drafter cache yields the root
    distribution q0; the W chain heads are its top-k (greedy) or W i.i.d.
    samples (sampled — the i.i.d.-ness is what makes multi-path rejection
    sampling lossless, see ``acceptance.verify_tree_stochastic``). Each head
    then continues as a LINEAR chain against its own branch cache — ring
    rows replicated [B] -> [B*W], paged tables CoW-forked host-side
    (``PagedTreeRound``) — so the drafter itself never needs tree attention.

    Verify: the span [t_last, level-major nodes] goes through the target
    once with the chain tree's (depths, bits) mask; the winner path's KV is
    committed by cache compaction (``CacheOps.compact``), the winner's
    drafter branch becomes the next round's drafter cache.

    width == 1 is EXACTLY the linear round (same key-split sequence, same
    draws, same acceptance) — asserted in tests; ``k`` stays 1 so the
    multi-draft (no-cache, greedy-only) gates never fire for trees.
    """
    name: str = "tree"
    width: int = 2
    k: int = 1

    def draft_cached(self, drafter, params_d, state: RoundState, spec,
                     live0) -> DraftOut:
        W, D = self.width, spec.gamma
        ex_d = state.extras_d or {}
        t_last = _gather_last(state.tokens, state.length)
        B = t_last.shape[0]
        key = state.key
        branched = _is_paged_branched(state.dcache, B)
        ex_w = (ex_d if W == 1 else
                {kk: jnp.repeat(v, W, axis=0) for kk, v in ex_d.items()})

        # root step: consume t_last, read q0. Pre-branched paged caches run
        # it per branch row (each branch's private tail block gets t_last's
        # KV); branch logits are identical, so row 0 of each group is q0.
        if branched:
            logits, dcache, _ = drafter.apply(
                params_d, jnp.repeat(t_last, W)[:, None], state.dcache,
                logits_slice="last", max_live=live0, **ex_w)
            q0 = logits[:, -1].reshape(B, W, -1)[:, 0]
        else:
            logits, cache0, _ = drafter.apply(
                params_d, t_last[:, None], state.dcache,
                logits_slice="last", max_live=live0, **ex_d)
            q0 = logits[:, -1]                                 # [B, V]
            dcache = _replicate_rows(cache0, W)
        if spec.greedy:
            _, heads = jax.lax.top_k(q0, W)                    # [B, W]
        else:
            # W i.i.d. root draws: ONE categorical over the row-repeated q0
            # (at W == 1 this is bit-for-bit the linear round's draw)
            key, ks = jax.random.split(key)
            flat = jnp.repeat(q0 / spec.temperature, W, axis=0)
            heads = jax.random.categorical(ks, flat, axis=-1).reshape(B, W)
        heads = heads.astype(jnp.int32)

        def dstep(carry, i):
            tok, cache, k = carry                              # tok [B*W]
            ml = None if live0 is None else live0 + 1 + i
            lg, cache, _ = drafter.apply(params_d, tok[:, None], cache,
                                         logits_slice="last", max_live=ml,
                                         **ex_w)
            q = lg[:, -1]
            if spec.greedy:
                nxt = jnp.argmax(q, axis=-1)
            else:
                k, ks = jax.random.split(k)
                nxt = jax.random.categorical(ks, q / spec.temperature,
                                             axis=-1)
            return (nxt.astype(jnp.int32), cache, k), (nxt.astype(jnp.int32),
                                                       q)
        (_, dcache, key), (toks, q_lv) = jax.lax.scan(
            dstep, (heads.reshape(B * W), dcache, key), jnp.arange(D - 1))
        toks = jnp.moveaxis(toks, 0, 1).reshape(B, W, D - 1)
        q_lv = jnp.moveaxis(q_lv, 0, 1).reshape(B, W, D - 1, -1)
        drafts = jnp.concatenate([heads[..., None], toks], axis=2)
        q_logits = jnp.concatenate(
            [jnp.broadcast_to(q0[:, None, None], (B, W, 1, q0.shape[-1])),
             q_lv], axis=2)                                    # [B, W, D, V]
        return DraftOut(drafts=drafts, q_logits=q_logits, cand_tokens=None,
                        t_last=t_last, dcache=dcache, snaps=None, key=key)

    def draft_nocache(self, drafter, params_d, state, spec):
        raise NotImplementedError(
            "tree drafting is cached-only (branch caches + tree-attention "
            "verify); use MultiDraftPolicy for no-cache k-candidate rounds")


def make_policy(name: str, k: int = 2):
    if name == "linear":
        return LinearDraftPolicy()
    if name == "multi":
        if k < 2:
            raise ValueError(f"multi-draft needs k >= 2 candidates, got {k}")
        return MultiDraftPolicy(k=k)
    if name == "tree":
        if k < 1:
            raise ValueError(f"tree draft needs width >= 1, got {k}")
        return TreeDraftPolicy(width=k)
    raise ValueError(f"unknown draft policy {name!r} "
                     f"(expected 'linear', 'multi' or 'tree')")


# ===================================================================== spec
@dataclass(frozen=True)
class RoundSpec:
    """Static parameterization of one speculative round."""
    gamma: int = 4
    greedy: bool = True
    temperature: float = 1.0
    commit: str = "batch_min"              # COMMIT_MODES
    use_cache: bool = True
    d_stateful: bool = False               # drafter carries recurrent state
    policy: Any = field(default_factory=LinearDraftPolicy)
    fused_verify: Optional[bool] = None    # None = auto (TPU only)

    def __post_init__(self):
        if self.commit not in COMMIT_MODES:
            raise ValueError(f"commit must be one of {COMMIT_MODES}")
        if self.policy.k > 1:
            if not self.greedy:
                raise ValueError("multi-draft is greedy-only")
            if self.use_cache:
                raise ValueError("multi-draft needs no-cache verification")
        if self.commit == "per_row" and not self.use_cache:
            raise ValueError("per-row commits need per-row cache indices "
                             "(use_cache=True)")
        if self.d_stateful and (not self.use_cache
                                or self.commit != "batch_min"):
            raise ValueError("stateful drafters need the cached "
                             "batch-synchronized path (docs/DESIGN.md §5)")
        if getattr(self.policy, "name", "") == "tree":
            if not self.use_cache:
                raise ValueError("tree drafting is cached-only (branch "
                                 "caches + tree-attention verify)")
            if self.d_stateful:
                raise ValueError("tree drafting needs a KV-family drafter "
                                 "(branch caches replicate/fork KV rows)")
            # validates span = 1 + width*gamma <= MAX_SPAN up front
            chain_tree(self.policy.width, self.gamma)

    @property
    def drafted_per_round(self) -> int:
        # CHAIN-length accounting, independent of policy.k: alpha_hat =
        # accepted/drafted must estimate the per-position acceptance rate of
        # the verified (winning) chain — the alpha Eq. (1) and the
        # GammaController consume. k-candidate work cost is the cost model's
        # stack_cost concern, not an acceptance-rate deflator.
        return self.gamma


def _live0(state: RoundState, spec: RoundSpec):
    """Round-level live-token bound for paged block-scan reads (None for
    ring caches and batch-synchronized rounds, which mask on positions)."""
    if not spec.use_cache or spec.commit != "per_row":
        return None
    return cache_ops.ops_for(state.tcache).live_bound(state.length,
                                                      state.active)


# =================================================================== phases
def draft_phase(drafter, params_d, state: RoundState,
                spec: RoundSpec) -> DraftOut:
    """Phase 1: run the draft policy (the ONLY draft loop in the repo)."""
    if spec.use_cache:
        return spec.policy.draft_cached(drafter, params_d, state, spec,
                                        _live0(state, spec))
    return spec.policy.draft_nocache(drafter, params_d, state, spec)


def _greedy_verify(drafts, p_logits, spec: RoundSpec):
    """Greedy acceptance: fused Pallas argmax kernel on TPU (or when forced
    — interpret-mode parity tests), jnp oracle elsewhere."""
    fused = (spec.fused_verify if spec.fused_verify is not None
             else jax.default_backend() == "tpu")
    if fused:
        from repro.kernels import ops as kernel_ops
        return kernel_ops.verify_greedy(drafts, p_logits)
    return acceptance.verify_greedy(drafts, p_logits)


def verify_phase(target, params_t, state: RoundState, d: DraftOut,
                 spec: RoundSpec) -> VerifyOut:
    """Phase 2: one target pass over the draft(s) + acceptance + (for k>1)
    best-candidate selection."""
    G = spec.gamma
    K = d.drafts.shape[1]
    ex_t = state.extras_t or {}
    key = d.key

    if spec.use_cache and getattr(spec.policy, "name", "") == "tree":
        # ONE stacked cached pass over the whole tree: the span is
        # [t_last, level-major nodes]; the chain tree's (depths, bits)
        # select the tree-attention path in the target's attention layers
        B, W = d.drafts.shape[:2]
        tree = chain_tree(W, G)
        level_major = jnp.swapaxes(d.drafts, 1, 2).reshape(B, W * G)
        verify_in = jnp.concatenate([d.t_last[:, None], level_major], axis=1)
        live0 = _live0(state, spec)
        ml = None if live0 is None else live0 + tree.span - 1
        p_logits, tcache, _ = target.apply(params_t, verify_in, state.tcache,
                                           want_trail=True, max_live=ml,
                                           tree=(tree.depths, tree.bits),
                                           **ex_t)
        cs = jnp.asarray(tree.chain_slots)
        if spec.greedy:
            res = acceptance.verify_tree_greedy(d.drafts, p_logits, cs)
        else:
            key, kv = jax.random.split(key)
            res = acceptance.verify_tree_stochastic(kv, d.drafts, d.q_logits,
                                                    p_logits, cs,
                                                    spec.temperature)
        return VerifyOut(res=res, base_tokens=state.tokens, tcache=tcache,
                         key=key)

    if spec.use_cache:                     # incremental: [t_last, d_1..d_G]
        drafts = d.drafts[:, 0]
        verify_in = jnp.concatenate([d.t_last[:, None], drafts], axis=1)
        live0 = _live0(state, spec)
        ml = None if live0 is None else live0 + G
        p_logits, tcache, _ = target.apply(params_t, verify_in, state.tcache,
                                           want_trail=True, max_live=ml,
                                           **ex_t)
        if spec.greedy:
            res = _greedy_verify(drafts, p_logits, spec)
        else:
            key, kv = jax.random.split(key)
            res = acceptance.verify_stochastic(kv, drafts, d.q_logits[:, 0],
                                               p_logits, spec.temperature)
        return VerifyOut(res=res, base_tokens=state.tokens, tcache=tcache,
                         key=key)

    # recompute: full-buffer target pass over the K stacked candidates
    B, _, T = d.cand_tokens.shape
    flat = d.cand_tokens.reshape(B * K, T)
    ex_flat = (ex_t if K == 1 else
               {kk: jnp.repeat(v, K, axis=0) for kk, v in ex_t.items()})
    p_full, _, _ = target.apply(params_t, flat, **ex_flat)
    p_logits = _slice_logits(p_full, state.length - 1, G + 1)  # [B*K, G+1, V]
    drafts_flat = d.drafts.reshape(B * K, G)
    if spec.greedy:
        res = _greedy_verify(drafts_flat, p_logits, spec)
        if K > 1:
            # best accepted prefix wins; ties prefer the drafter-greedy
            # chain (candidate 0 — jnp.argmax takes the first maximum)
            win = jnp.argmax(res.n_emitted.reshape(B, K), axis=1)
            res = acceptance.VerifyResult(
                _take_candidate(res.n_accepted.reshape(B, K), win),
                _take_candidate(res.out_tokens.reshape(B, K, G + 1), win),
                _take_candidate(res.n_emitted.reshape(B, K), win))
            base = _take_candidate(d.cand_tokens, win)
            return VerifyOut(res=res, base_tokens=base, tcache=state.tcache,
                             key=key)
    else:
        key, kv = jax.random.split(key)
        res = acceptance.verify_stochastic(kv, drafts_flat,
                                           d.q_logits[:, 0], p_logits,
                                           spec.temperature)
    return VerifyOut(res=res, base_tokens=d.cand_tokens[:, 0],
                     tcache=state.tcache, key=key)


def _scatter_commit(tokens, length, out_tokens, n_eff, gamma):
    """THE commit: write each row's emitted prefix at its own offset.
    ``length`` may be scalar (batch-synchronized) or [B]; the batch-min mode
    is just this scatter with ``n_eff`` broadcast to the batch minimum."""
    B, T = tokens.shape
    pos = jnp.arange(gamma + 1)[None, :]                     # [1, G+1]
    lvec = jnp.broadcast_to(jnp.asarray(length), (B,))
    cols = jnp.clip(lvec[:, None] + pos, 0, T - 1)           # [B, G+1]
    keep = pos < n_eff[:, None]
    rows = jnp.arange(B)[:, None]
    cur = tokens[rows, cols]
    vals = jnp.where(keep, out_tokens, cur)
    return tokens.at[rows, cols].set(vals.astype(tokens.dtype))


def _tree_commit(target, state: RoundState, d: DraftOut, v: VerifyOut,
                 spec: RoundSpec) -> RoundState:
    """Tree-round commit: compact the winner path's scattered KV into the
    committed tail, then the ordinary rollback. The winner chain's level-l
    token sits at cache position (length-1) + chain_slots[winner][l-1]; its
    committed home is length + l - 1 — src >= dst always, and the compact
    primitives gather before they scatter, so the move is overlap-safe.
    Compacting all G levels is fine: rollback masks everything past the
    accepted length. The drafter side needs NO compaction — the winner's
    branch cache already holds the committed chain contiguously, so it
    simply becomes the next round's drafter cache (ring: ``_take_branch``;
    paged: the host adopts the winning CoW branch, see ``PagedTreeRound``).
    """
    G = spec.gamma
    res = v.res
    B, W = d.drafts.shape[:2]
    ops_t = cache_ops.ops_for(v.tcache)
    cs = jnp.asarray(chain_tree(W, G).chain_slots)            # [W, G]
    lvec = jnp.broadcast_to(jnp.asarray(state.length), (B,))
    src = (lvec - 1)[:, None] + cs[res.winner] + state.t_off
    dst = lvec[:, None] + jnp.arange(G, dtype=jnp.int32) + state.t_off
    tcache = ops_t.compact(v.tcache, src, dst)

    if spec.commit == "per_row":
        active = (state.active if state.active is not None
                  else jnp.ones((B,), bool))
        n_eff = jnp.where(active, res.n_emitted, 0)
        tokens = _scatter_commit(v.base_tokens, state.length,
                                 res.out_tokens, n_eff, G)
        new_len = state.length + n_eff
        tcache = ops_t.rollback(tcache, new_len - 1)
        dcache = d.dcache
        if dcache is not None and not _is_paged_branched(dcache, B):
            dcache = _take_branch(dcache, res.winner, W)
            dcache = cache_ops.ops_for(dcache).rollback(dcache, new_len - 1)
        return state._replace(
            tokens=tokens, length=new_len, key=v.key,
            dcache=dcache, tcache=tcache,
            n_rounds=state.n_rounds + 1,
            n_accepted=state.n_accepted + jnp.where(active, res.n_accepted, 0),
            n_drafted=state.n_drafted + spec.drafted_per_round)

    n_commit = jnp.min(res.n_emitted)
    n_eff = jnp.broadcast_to(n_commit, (B,))
    tokens = _scatter_commit(v.base_tokens, state.length, res.out_tokens,
                             n_eff, G)
    new_len = state.length + n_commit
    st = state._replace(tokens=tokens, length=new_len, key=v.key,
                        n_rounds=state.n_rounds + 1,
                        n_accepted=state.n_accepted + (n_commit - 1),
                        n_drafted=state.n_drafted + spec.drafted_per_round)
    tcache = target.rollback(tcache, new_len - 1 + state.t_off,
                             1 + W * G)
    dcache = d.dcache
    if dcache is not None and not _is_paged_branched(dcache, B):
        dcache = _take_branch(dcache, res.winner, W)
        dcache = cache_ops.ops_for(dcache).rollback(
            dcache, new_len - 1 + state.d_off)
    return st._replace(dcache=dcache, tcache=tcache)


def commit_phase(target, state: RoundState, d: DraftOut, v: VerifyOut,
                 spec: RoundSpec) -> RoundState:
    """Phase 3: commit the accepted prefix + roll both caches back.

    ``d.dcache is None`` marks a PLACED round (the drafter cache lives on
    its own submesh): the drafter rollback is skipped here and dispatched
    separately on the drafter mesh (``PlacedRound``); the committed state
    then carries ``dcache=None`` until the runner reattaches it.
    """
    if getattr(spec.policy, "name", "") == "tree":
        return _tree_commit(target, state, d, v, spec)

    G = spec.gamma
    res = v.res
    B = state.tokens.shape[0]
    ops_t = cache_ops.ops_for(v.tcache)

    if spec.commit == "per_row":
        active = (state.active if state.active is not None
                  else jnp.ones((B,), bool))
        n_eff = jnp.where(active, res.n_emitted, 0)
        tokens = _scatter_commit(v.base_tokens, state.length,
                                 res.out_tokens, n_eff, G)
        new_len = state.length + n_eff                       # PER ROW
        tcache = ops_t.rollback(v.tcache, new_len - 1)
        dcache = (None if d.dcache is None else
                  cache_ops.ops_for(d.dcache).rollback(d.dcache, new_len - 1))
        return state._replace(
            tokens=tokens, length=new_len, key=v.key,
            dcache=dcache, tcache=tcache,
            n_rounds=state.n_rounds + 1,
            n_accepted=state.n_accepted + jnp.where(active, res.n_accepted, 0),
            n_drafted=state.n_drafted + spec.drafted_per_round)

    # batch_min: commit the batch-minimum emitted length (discarded
    # acceptances are simply re-drafted; exact at B=1)
    n_commit = jnp.min(res.n_emitted)
    n_eff = jnp.broadcast_to(n_commit, (B,))
    tokens = _scatter_commit(v.base_tokens, state.length, res.out_tokens,
                             n_eff, G)
    new_len = state.length + n_commit                        # stays scalar
    n_acc = n_commit - 1
    st = state._replace(tokens=tokens, length=new_len, key=v.key,
                        n_rounds=state.n_rounds + 1,
                        n_accepted=state.n_accepted + n_acc,
                        n_drafted=state.n_drafted + spec.drafted_per_round)
    if not spec.use_cache:
        return st
    # caches end at (committed length - 1) consumed inputs, shifted by any
    # modality prefix the cache also holds (VLM vision tokens)
    tcache = target.rollback(v.tcache, new_len - 1 + state.t_off, G + 1)
    if d.dcache is None:                   # placed round: drafter-mesh rollback
        return st._replace(dcache=None, tcache=tcache)
    if spec.d_stateful:
        # snapshot j = state after consuming j+1 inputs; we need n_acc+1
        dcache = _restore_state_leaves(d.dcache, d.snaps, n_acc)
        dcache = {**dcache,
                  "index": (new_len - 1 + state.d_off).astype(jnp.int32)}
    else:
        dcache = cache_ops.ops_for(d.dcache).rollback(
            d.dcache, new_len - 1 + state.d_off)
    return st._replace(dcache=dcache, tcache=tcache)


# ==================================================================== rounds
def spec_round(target, drafter, params_t, params_d, state: RoundState,
               spec: RoundSpec) -> RoundState:
    """ONE speculative round: the composition of the three phases."""
    d = draft_phase(drafter, params_d, state, spec)
    v = verify_phase(target, params_t, state, d, spec)
    return commit_phase(target, state, d, v, spec)


def ar_round(target, params_t, state: RoundState) -> RoundState:
    """γ*=0 fallback round: one committed greedy token per active row,
    target model only (the cost model said drafting does not pay)."""
    B, T = state.tokens.shape
    rows = jnp.arange(B)
    ops_t = cache_ops.ops_for(state.tcache)
    lvec = jnp.broadcast_to(jnp.asarray(state.length), (B,))
    t_last = state.tokens[rows, lvec - 1]
    logits, tcache, _ = target.apply(
        params_t, t_last[:, None], state.tcache, logits_slice="last",
        max_live=ops_t.live_bound(state.length, state.active),
        **(state.extras_t or {}))
    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    active = (state.active if state.active is not None
              else jnp.ones((B,), bool))
    cols = jnp.clip(lvec, 0, T - 1)
    cur = state.tokens[rows, cols]
    tokens = state.tokens.at[rows, cols].set(jnp.where(active, nxt, cur))
    new_len = state.length + active.astype(jnp.int32)
    tcache = ops_t.rollback(tcache, new_len - 1)
    return state._replace(tokens=tokens, length=new_len, tcache=tcache,
                          n_rounds=state.n_rounds + 1)


# =========================================================== placed execution
def place_state(state: RoundState, placement, target_model=None,
                drafter_model=None) -> RoundState:
    """Pin a RoundState onto a realized Placement (api/placement.py): the
    drafter cache moves to the drafter submesh, everything else — tokens,
    lengths, target cache, counters — to the target submesh (where verify
    and commit run). No-op for the degenerate lowering.

    NOTE: device_put may ALIAS source shards that already sit on a member
    device, and PlacedRound donates the caches — treat the input state as
    consumed (and don't place the same state twice expecting independent
    buffers)."""
    if not placement.heterogeneous:
        return state
    if state.extras_t or state.extras_d:
        raise NotImplementedError(
            "placed rounds do not carry decode-time modality extras "
            "(encdec cross-KV) — use the degenerate placement")
    B = state.tokens.shape[0]
    dcache = (placement.drafter.put_cache(drafter_model, state.dcache, B)
              if drafter_model is not None
              else placement.to_drafter(state.dcache))
    tcache = (placement.target.put_cache(target_model, state.tcache, B)
              if target_model is not None
              else placement.to_target(state.tcache))
    rest = placement.to_target(state._replace(dcache=None, tcache=None))
    return rest._replace(dcache=dcache, tcache=tcache)


class PlacedRound:
    """ONE speculative round with plan-carried placement: the same three
    phases as ``spec_round``, split at the draft/verify handoff and jitted
    per role —

        drafter submesh : draft scan (``draft_phase``) + drafter rollback
        target submesh  : verify + commit (``verify_phase``/``commit_phase``)

    with the gamma-token package (γ drafts + the last committed token; plus
    drafter logits and the PRNG key in sampled mode) explicitly transferred
    across submeshes between them — the paper's tiny PU-to-PU handoff.

    Because each side is its own async-dispatched program on its own device
    set, the host can enqueue the drafter rollback and the NEXT round's
    draft while the current verify is still in flight on the target submesh
    (``SpecEngine``'s overlap loop) — the idle-PU elimination the planner's
    overlapped-round term (``cost_model.round_time``) prices.

    Token-identity: phases run the SAME code ``spec_round`` composes, so a
    placed round commits exactly the tokens the fused round would
    (goldens-tested); only device residency and dispatch order change.

    Supported: cached linear rounds (both commit modes, greedy or sampled),
    KV-family drafters. Multi-draft (no-cache) and stateful drafters keep
    the single-mesh path.
    """

    def __init__(self, target, drafter, spec: RoundSpec, placement,
                 tracer=None):
        if spec.policy.k > 1 or getattr(spec.policy, "name", "") != "linear":
            raise ValueError("placed rounds are linear-draft only")
        if not spec.use_cache:
            raise ValueError("placed rounds need cached execution "
                             "(no-cache rounds recompute on one buffer)")
        if spec.d_stateful:
            raise ValueError("placed rounds need KV-family drafters "
                             "(state-trail rollback is single-mesh)")
        self.target, self.drafter = target, drafter
        self.spec, self.placement = spec, placement
        self.tracer = tracer if tracer is not None else NULL_TRACER
        sp = spec

        def draft(params_d, t_last, length, dcache, key, active):
            # the cached linear draft reads ONLY the last committed token
            # from the buffer — a [B] vector is the whole visible prefix
            # (the real ``length`` feeds the paged live-block bound)
            live0 = None
            if sp.commit == "per_row":
                live0 = cache_ops.ops_for(dcache).live_bound(length, active)
            st = RoundState(tokens=t_last[:, None],
                            length=jnp.ones((), jnp.int32),
                            dcache=dcache, key=key, active=active)
            d = sp.policy.draft_cached(drafter, params_d, st, sp, live0)
            q = None if sp.greedy else d.q_logits[:, 0]
            return d.drafts[:, 0], q, d.dcache, d.key

        def verify_commit(params_t, state, tcache, drafts, t_last, q_logits,
                          key):
            state = state._replace(tcache=tcache)
            d = DraftOut(drafts=drafts[:, None],
                         q_logits=None if q_logits is None
                         else q_logits[:, None],
                         cand_tokens=None, t_last=t_last, dcache=None,
                         snaps=None, key=key)
            v = verify_phase(target, params_t, state, d, sp)
            return commit_phase(target, state, d, v, sp)

        def drafter_rollback(dcache, new_len, d_off):
            return cache_ops.ops_for(dcache).rollback(dcache,
                                                      new_len - 1 + d_off)

        # the CACHES are donated (updated in place at each jit boundary,
        # like the unplaced engines' donated round state); the small leaves
        # (tokens/length/counters) are NOT, so callers may still read e.g.
        # a prior state's committed length after dispatching the next round
        # (the overlap lookahead loop does exactly that)
        self._draft_jit = placement.drafter.jit(draft, donate_argnums=(3,))
        self._vc_jit = placement.target.jit(verify_commit, donate_argnums=(2,))
        self._drb_jit = placement.drafter.jit(drafter_rollback,
                                              donate_argnums=(0,))

    def __call__(self, params_t, params_d, state: RoundState,
                 **tags) -> RoundState:
        # Tracing note: placed spans deliberately do NOT block — blocking
        # would serialize exactly the async pipelining this class exists to
        # exploit. A placed span therefore measures host enqueue + transfer
        # time (kind="dispatch"/"handoff"); per-phase DEVICE time comes from
        # the phase-split TracedRound (see docs/DESIGN.md §7).
        pm, tr = self.placement, self.tracer
        # last committed token + row lengths -> drafter submesh: a [B]
        # vector each, NOT the [B, T] buffer — the whole cross-domain
        # traffic really is gamma-token sized
        t_last_t = _gather_last(state.tokens, state.length)
        with tr.span("draft.dispatch", phase="draft", role="drafter",
                     kind="dispatch", **tags):
            t_last_d, length_d, active_d, key_d, d_off_d = pm.to_drafter(
                (t_last_t, state.length, state.active, state.key,
                 state.d_off))
            drafts, q_log, dcache, key2 = self._draft_jit(
                params_d, t_last_d, length_d, state.dcache, key_d, active_d)
        # the gamma-token handoff -> target submesh
        with tr.span("handoff", phase="handoff", role="target",
                     kind="handoff", **tags):
            drafts_t, q_t, key_t = pm.to_target((drafts, q_log, key2))
        with tr.span("verify_commit.dispatch", phase="verify", role="target",
                     kind="dispatch", **tags):
            new = self._vc_jit(params_t,
                               state._replace(dcache=None, tcache=None),
                               state.tcache, drafts_t, t_last_t, q_t, key_t)
        # commit result -> drafter submesh; rollback dispatches there while
        # the caller is free to enqueue the next round (async dispatch)
        with tr.span("rollback.dispatch", phase="commit", role="drafter",
                     kind="dispatch", **tags):
            new_len_d = pm.to_drafter(new.length)
            dcache = self._drb_jit(dcache, new_len_d, d_off_d)
        return new._replace(dcache=dcache)


class PagedTreeRound:
    """ONE paged tree round driven from the host: CoW-fork each row's
    drafter block table (one branch per chain, shared prefix blocks
    refcounted, partial tail copied — ``BlockAllocator.fork_row``), run the
    SAME jitted three phases ``spec_round`` composes against the
    pre-branched [B*W]-row drafter cache, then adopt each row's winning
    branch and free the losers (``adopt_branch``). The target cache needs no
    forks — the stacked verify writes every tree slot to its own position
    past the committed tail and ``_tree_commit`` compacts the winner path in
    place.

    ``TreeDraftPolicy`` detects the pre-branched table purely by shape
    (``_is_paged_branched``), so the device round stays one jit-compatible
    program; this class owns only the host/allocator choreography around
    it. Scope: a fully-live batch (tests/benchmarks) — serving admission,
    preemption and capacity degradation stay with the scheduler.
    """

    def __init__(self, target, drafter, spec: RoundSpec, alloc_t, alloc_d):
        if getattr(spec.policy, "name", "") != "tree":
            raise ValueError("PagedTreeRound needs a TreeDraftPolicy spec")
        if spec.commit != "per_row":
            raise ValueError("paged rounds are per-row (serving) rounds")
        self.spec = spec
        self.W = spec.policy.width
        self.alloc_t, self.alloc_d = alloc_t, alloc_d
        d, v, c = phase_fns(target, drafter, spec)
        self._draft_jit = jax.jit(d)
        self._verify_jit = jax.jit(v)
        self._commit_jit = jax.jit(c)

    def _fork(self, state: RoundState) -> RoundState:
        from repro.cache import paged_kv
        W, D = self.W, self.spec.gamma
        span = 1 + W * D
        B = state.tokens.shape[0]
        lengths = np.asarray(jax.device_get(state.length))
        pairs = []
        for b in range(B):
            L = int(lengths[b])
            if not self.alloc_t.ensure(b, L - 1 + span):
                raise RuntimeError(f"target pool exhausted growing row {b} "
                                   f"to {L - 1 + span} tokens")
            # the adopted branch was only ever grown to last round's draft
            # horizon — a fully-accepted round can commit past it, so the
            # row must be re-ensured to its new tail before forking
            if not self.alloc_d.ensure(b, L - 1):
                raise RuntimeError(f"drafter pool exhausted growing row {b} "
                                   f"to {L - 1} tokens")
            p = self.alloc_d.fork_row(b, L - 1, W)
            if p is None:
                raise RuntimeError(f"drafter pool exhausted forking row {b} "
                                   f"into {W} branches")
            pairs += p
            for w in range(W):
                if not self.alloc_d.ensure_branch(b, w, L - 1 + D):
                    raise RuntimeError(f"drafter pool exhausted growing "
                                       f"branch {w} of row {b}")
        dcache = paged_kv.copy_blocks(state.dcache, pairs)
        tbl = np.stack([self.alloc_d.branch_tables(b) for b in range(B)])
        dcache = {**dcache,
                  "block_table": jnp.asarray(tbl.reshape(B * W, -1)),
                  "index": jnp.repeat(jnp.asarray(state.dcache["index"],
                                                  jnp.int32), W)}
        tcache = {**state.tcache,
                  "block_table": self.alloc_t.device_table()}
        return state._replace(dcache=dcache, tcache=tcache)

    def __call__(self, params_t, params_d, state: RoundState) -> RoundState:
        B = state.tokens.shape[0]
        state = self._fork(state)
        d = self._draft_jit(params_d, state)
        v = self._verify_jit(params_t, state, d)
        new = self._commit_jit(state, d, v)
        winner, new_len = map(np.asarray, jax.device_get(
            (v.res.winner, new.length)))
        for b in range(B):
            self.alloc_d.adopt_branch(b, int(winner[b]))
            keep = max(int(new_len[b]) - 1, 1)
            self.alloc_d.free_tail(b, keep)
            self.alloc_t.free_tail(b, keep)
        dcache = {**new.dcache,
                  "block_table": self.alloc_d.device_table(),
                  "index": jnp.asarray(new_len - 1, jnp.int32)}
        tcache = {**new.tcache,
                  "block_table": self.alloc_t.device_table()}
        return new._replace(dcache=dcache, tcache=tcache)


def phase_fns(target, drafter, spec: RoundSpec):
    """(draft, verify, commit) callables over the SAME phase code
    ``spec_round`` composes — jit each for per-phase benchmarking."""
    def draft(params_d, state):
        return draft_phase(drafter, params_d, state, spec)

    def verify(params_t, state, d):
        return verify_phase(target, params_t, state, d, spec)

    def commit(state, d, v):
        return commit_phase(target, state, d, v, spec)

    return draft, verify, commit


class TracedRound:
    """ONE speculative round, phase-split for observability: the three
    ``phase_fns`` are jitted as separate programs and each is host-blocked
    (``jax.block_until_ready``) INSIDE its span, so a span's wall time is
    that phase's device time — measured once, at the block point, never
    double-counted against async dispatch.

    The observability tax vs the fused round: three dispatches instead of
    one, no buffer donation (phase outputs cross jit boundaries), and a
    host sync per phase that forfeits pipelining. That is why engines build
    a TracedRound only when handed an ENABLED tracer and keep the fused
    donated round otherwise (the <1% disabled-overhead budget).

    Token identity with ``spec_round`` is the phase-decomposition invariant
    (tests/test_rounds.py): ``spec_round`` IS the composition of these
    phases, so tracing changes when the host waits, never what the round
    commits.

    ``last_phase_times`` holds the most recent round's per-phase seconds —
    servers turn it into RoundEvents and drift-monitor observations.
    """

    def __init__(self, target, drafter, spec: RoundSpec, tracer, **tags):
        self.spec = spec
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.tags = tags
        d, v, c = phase_fns(target, drafter, spec)
        self._draft = jax.jit(d)
        self._verify = jax.jit(v)
        self._commit = jax.jit(c)
        self.last_phase_times: dict = {}

    def __call__(self, params_t, params_d, state: RoundState,
                 **tags) -> RoundState:
        tr = self.tracer
        t = {**self.tags, **tags}      # caller tags may override role etc.
        with tr.span("draft",
                     **{"phase": "draft", "role": "drafter", **t}) as s_d:
            d = jax.block_until_ready(self._draft(params_d, state))
        with tr.span("verify",
                     **{"phase": "verify", "role": "target", **t}) as s_v:
            v = jax.block_until_ready(self._verify(params_t, state, d))
        with tr.span("commit",
                     **{"phase": "commit", "role": "target", **t}) as s_c:
            new = jax.block_until_ready(self._commit(state, d, v))
        self.last_phase_times = {"draft": s_d.duration,
                                 "verify": s_v.duration,
                                 "commit": s_c.duration}
        return new
