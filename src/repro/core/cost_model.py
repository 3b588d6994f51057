"""The paper's analytical cost model (Eq. 1, from Leviathan et al. [3]).

    S(α, γ, c) = (1 − α^(γ+1)) / ((1 − α)(γ·c + 1))

α — expected acceptance rate (drafter/target distribution alignment),
γ — draft length (tokens speculated per round),
c — cost coefficient t_draft / t_target (hardware+mapping dependent).

The model is used *prescriptively*, exactly as in the paper:
  (i)  decide whether speculative sampling helps at all (requires c < α), and
  (ii) pick the speedup-optimal γ* for a given (α, c),
and it is the objective function of the heterogeneous-mapping DSE
(repro.core.partition). Pure float/numpy — usable inside and outside jit.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Iterable, Optional, Tuple

import numpy as np

GAMMA_MAX_DEFAULT = 16


def speedup(alpha: float, gamma: int, c: float) -> float:
    """Eq. (1). gamma=0 degenerates to 1.0 (no speculation)."""
    alpha = float(alpha)
    gamma = int(gamma)
    if gamma == 0:
        return 1.0
    if alpha >= 1.0:
        return (gamma + 1.0) / (gamma * c + 1.0)
    num = 1.0 - alpha ** (gamma + 1)
    den = (1.0 - alpha) * (gamma * c + 1.0)
    return num / den


def expected_accepted(alpha: float, gamma: int) -> float:
    """E[# tokens produced per verification round] = (1 − α^(γ+1)) / (1 − α).

    Counts the accepted draft prefix plus the bonus/resampled token; this is the
    numerator of Eq. (1) and a quantity we validate empirically."""
    if alpha >= 1.0:
        return gamma + 1.0
    return (1.0 - alpha ** (gamma + 1)) / (1.0 - alpha)


def multi_draft_gain(alpha: float, alpha_topk: float, gamma: int) -> float:
    """Expected emitted-tokens multiplier of k-candidate drafting over linear
    drafting at equal gamma (core.rounds.MultiDraftPolicy).

    The k candidates differ only in their FIRST token (drafter top-k
    alternates, greedy continuations), so the alternates recover exactly the
    rounds where the drafter's argmax misses but its top-k covers: with
    probability (alpha_topk − alpha) a recovered chain emits like a linear
    chain whose head was accepted. k enters ONLY through alpha_topk, which
    must be P[target argmax ∈ drafter top-k] measured at the SAME k the
    policy will run (benchmarks/bench_strategies.py reports it).
    """
    e1 = expected_accepted(alpha, gamma)
    lift = max(float(alpha_topk) - float(alpha), 0.0)
    ek = e1 + lift * expected_accepted(alpha, max(gamma - 1, 0))
    return ek / e1


def multi_draft_speedup(alpha: float, alpha_topk: float, gamma: int,
                        c: float, k: int,
                        stack_cost: float = 0.35) -> float:
    """Round-speedup of MultiDraftPolicy(k) over linear at equal (γ, c).

    Per-phase cost in the recompute (no-cache) mode where multi-draft runs:
    a linear round is γ drafter passes + 1 target verify = γ·c + 1; the
    multi round's FIRST draft step runs unstacked (the chains branch on its
    top-k), then γ−1 draft steps and the verify stack the k candidates on
    the batch axis at ``m = 1 + (k−1)·stack_cost`` relative cost each —
    ``stack_cost`` < 1 is the vectorization discount of widening a batch
    instead of running a second pass (measure it: bench_strategies.py).
    ``alpha_topk`` must be measured at this k (see multi_draft_gain).
    Speedup = emitted gain / relative round cost."""
    gain = multi_draft_gain(alpha, alpha_topk, gamma)
    m = 1.0 + (k - 1) * float(stack_cost)
    cost_lin = gamma * c + 1.0
    cost_multi = c * (1.0 + (gamma - 1) * m) + m
    return gain * cost_lin / cost_multi


MAX_TREE_SPAN = 31   # core.tree: 1 + width*depth <= 31 (int32 ancestor masks)


def tree_gain(alpha: float, alpha_topk: float, width: int,
              depth: int) -> float:
    """Expected emitted-tokens multiplier of a (width × depth) chain tree
    over linear drafting at gamma = depth (core.rounds.TreeDraftPolicy).

    The tree branches once at the root: width head alternates, each continued
    as a linear chain. A round emits the bonus/correction token always, plus
    — iff SOME head is accepted, probability ``head_alpha`` — that chain's
    linear continuation:

        E_tree = 1 + head_alpha · E(alpha, depth − 1)

    ``head_alpha`` is alpha_topk (P[target argmax ∈ drafter top-width],
    measured at THIS width) for width ≥ 2 and plain alpha for width = 1,
    where the identity E(α, d) = 1 + α·E(α, d−1) makes the tree reduce
    exactly to linear. Gain = E_tree / E(alpha, depth)."""
    head = float(alpha_topk) if width >= 2 else float(alpha)
    head = max(head, float(alpha))
    e_tree = 1.0 + head * expected_accepted(alpha, depth - 1)
    return e_tree / expected_accepted(alpha, depth)


def tree_speedup(alpha: float, alpha_topk: float, width: int, depth: int,
                 c: float, stack_cost: float = 0.35) -> float:
    """Round-speedup of TreeDraftPolicy(width) over LINEAR drafting at
    gamma = depth and equal c.

    Cost side mirrors multi_draft_speedup, but for cached rounds: the root
    draft step runs unstacked (chains branch on its top-width), the
    remaining depth−1 draft steps run the width branches stacked on the
    batch axis at ``m = 1 + (width−1)·stack_cost`` each, and the single
    tree-attention verify stacks the span's queries at the same m:

        cost_tree = c·(1 + (depth−1)·m) + m     vs     cost_lin = depth·c + 1

    Speedup = emitted gain / relative round cost; width = 1 gives exactly
    1.0 (the tree degenerates to the linear round it replaces)."""
    gain = tree_gain(alpha, alpha_topk, width, depth)
    m = 1.0 + (width - 1) * float(stack_cost)
    cost_lin = depth * c + 1.0
    cost_tree = c * (1.0 + (depth - 1) * m) + m
    return gain * cost_lin / cost_tree


def optimal_tree(alpha: float, alpha_topk: Optional[float], c: float,
                 gamma_max: int = GAMMA_MAX_DEFAULT, width_max: int = 4,
                 stack_cost: float = 0.35,
                 max_span: int = MAX_TREE_SPAN) -> Tuple[Tuple[int, int], float]:
    """Best (width, depth) over the span-feasible grid, scored as ABSOLUTE
    speedup over autoregressive decoding:

        S_tree(W, D) = S(alpha, D, c) · tree_speedup(alpha, alpha_topk, W, D)

    (the second factor is relative to linear at the same depth, so the
    product composes). width = 1 rows ARE the linear candidates, so the
    returned optimum never loses to plain optimal_gamma; a (1, D) winner
    means 'stay linear'. Returns ((width, depth), S)."""
    topk = alpha if alpha_topk is None else float(alpha_topk)
    best = ((1, 0), 1.0)
    for w in range(1, width_max + 1):
        for d in range(1, gamma_max + 1):
            if 1 + w * d > max_span:
                continue
            s = speedup(alpha, d, c) * tree_speedup(alpha, topk, w, d, c,
                                                    stack_cost)
            if s > best[1] + 1e-12:
                best = ((w, d), s)
    return best


# ---------------------------------------------------------------------------
# Overlapped-round time (placement realization, api/placement.py)
# ---------------------------------------------------------------------------
# Host dispatch + cross-submesh gamma-token handoff per round, in t_target
# units. The prior matches the measured modular-vs-monolithic dispatch gap on
# the bench pair (benchmarks/bench_strategies.py); bench_dse.py re-measures it.
DISPATCH_OVERHEAD_DEFAULT = 0.05


def round_time(gamma: int, c: float,
               dispatch_overhead: float = DISPATCH_OVERHEAD_DEFAULT,
               overlap: bool = False) -> float:
    """Expected speculative-round time in t_target units.

    Serialized (one implicit mesh, host between phases):
        T = γ·c + 1 + h        (draft chain + verify + dispatch/handoff h)
    Overlapped (per-role submeshes + async dispatch): the host enqueues the
    drafter rollback and the NEXT round's draft while the verify is still in
    flight on the target submesh, so h hides under the verify — but no more
    of it than the verify is long (one t_target):
        T = γ·c + 1 + max(h − 1, 0)
    This is the idle-PU elimination of the paper's two-PU mapping — the
    drafter domain never waits out a host round-trip it could overlap.
    (benchmarks/bench_dse.py calibrates h per platform and reports the
    MEASURED overlap gain next to this model's credit.)
    """
    base = gamma * c + 1.0
    if overlap:
        return base + max(dispatch_overhead - 1.0, 0.0)
    return base + dispatch_overhead


def prefill_time(prompt_len: int, chunk: Optional[int] = None,
                 prefix_hit_tokens: int = 0, c: float = 0.0,
                 dispatch_overhead: float = DISPATCH_OVERHEAD_DEFAULT) -> float:
    """Expected prefill cost in t_target units under chunking + prefix reuse.

    Prefill feeds ``prompt_len - 1`` positions through BOTH caches (the
    drafter must hold the same prefix KV to draft from it), minus any prefix
    tokens attached from the shared-prefix block cache. On the edge-class
    models this repo targets, a forward pass is launch-latency dominated well
    past typical chunk sizes, so each chunk program prices like one combined
    target+drafter step plus its dispatch:

        T = ceil(max(P − 1 − hit, 0) / chunk) · (1 + c + h)

    ``chunk=None`` means the legacy all-at-once path (one program). The
    planner uses the RATIO of this across configurations (chunked vs not,
    hit vs cold) to stamp plan.cache rationale — same prescriptive use as
    Eq. (1), not an absolute-seconds claim.
    """
    suffix = max(int(prompt_len) - 1 - max(int(prefix_hit_tokens), 0), 0)
    if suffix == 0:
        return 0.0
    n_chunks = 1 if chunk is None else -(-suffix // max(int(chunk), 1))
    return n_chunks * (1.0 + float(c) + float(dispatch_overhead))


def overlap_gain(gamma: int, c: float,
                 dispatch_overhead: float = DISPATCH_OVERHEAD_DEFAULT) -> float:
    """Round-speedup of overlapped dispatch over serialized dispatch at equal
    (γ, c) — the multiplier decision ③ applies to heterogeneous mappings."""
    return (round_time(gamma, c, dispatch_overhead, overlap=False)
            / round_time(gamma, c, dispatch_overhead, overlap=True))


def feasible(alpha: float, c: float) -> bool:
    """Paper §II-B: c < α must hold for ANY γ to give S > 1."""
    return c < alpha


def optimal_gamma(alpha: float, c: float, gamma_max: int = GAMMA_MAX_DEFAULT) -> Tuple[int, float]:
    """γ* maximizing Eq. (1) over 0..gamma_max; returns (γ*, S(γ*)).

    γ=0 (no speculation, S=1) is always a candidate, so an infeasible (α, c)
    yields (0, 1.0) — 'do not speculate', matching paper Tables II/III."""
    best = (0, 1.0)
    for g in range(1, gamma_max + 1):
        s = speedup(alpha, g, c)
        if s > best[1] + 1e-12:
            best = (g, s)
    return best


def speedup_curve(alpha_grid: Iterable[float], gamma: int, c: float) -> np.ndarray:
    """S as a function of α for fixed (γ, c) — paper Fig. 7 predicted curves."""
    return np.array([speedup(a, gamma, c) for a in alpha_grid])


# ---------------------------------------------------------------------------
# Per-chip peaks (the TPU analogue of the paper's profiled silicon), keyed by
# the device_kind JAX reports
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_flops: float                 # bf16 FLOP/s per chip
    peak_int8_ops: float              # int8 OP/s per chip
    hbm_bytes: float                  # HBM capacity per chip
    hbm_bw: float                     # bytes/s per chip
    ici_bw: float                     # bytes/s per link (4 links per chip)
    source: str


PEAKS = {
    "TPU v5 lite": HardwareSpec(
        name="TPU v5e", peak_flops=197e12, peak_int8_ops=393e12,
        hbm_bytes=16e9, hbm_bw=819e9, ici_bw=1600e9 / 8 / 4,
        source="Google Cloud documentation, 'TPU v5e' (per-chip specs)"),
}

V5E = PEAKS["TPU v5 lite"]


def peaks_for(device_kind: str) -> HardwareSpec:
    """The peaks row of a device kind (``jax.devices()[0].device_kind``).
    A kind missing from the table is an error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


@dataclass(frozen=True)
class RooflineTerms:
    """Three-term roofline estimate for one compiled step on a submesh."""
    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """Roofline step time: max of the three terms (perfect overlap bound)."""
        return max(self.compute_s, self.memory_s, self.collective_s)


def roofline_terms(flops: float, hbm_bytes: float, collective_bytes: float,
                   chips: int, hw: HardwareSpec = V5E,
                   links_per_chip: float = 4.0) -> RooflineTerms:
    """Convert dry-run cost-analysis numbers into per-step roofline seconds.

    collective_bytes is the sum of collective operand bytes across the program
    (already a global quantity); each chip drives ``links_per_chip`` ICI links.
    """
    return RooflineTerms(
        compute_s=flops / (chips * hw.peak_flops),
        memory_s=hbm_bytes / (chips * hw.hbm_bw),
        collective_s=collective_bytes / (chips * links_per_chip * hw.ici_bw),
    )


def cost_coefficient(t_draft: float, t_target: float) -> float:
    """c = t_draft / t_target (paper §II-B). Works on measured or roofline times."""
    return float(t_draft) / float(t_target)
