"""Partition-spec derivation for params, caches, and step inputs/outputs.

Specs are derived *structurally*: we walk the (eval_shape'd) param pytree and
assign a PartitionSpec per leaf from its key-path and rank. This keeps specs in
lockstep with init functions by construction (tests assert the trees match).

The sharding policy is the compiler-level "device affinity" abstraction of the
paper (§III-D): the speculative-sampling engine assigns the drafter and target
each their own policy/submesh, and the DSE in repro.core.partition searches over
these assignments.

Baseline layout (megatron-style):
  * attention q/k/v: output (heads) on ``model``;  o: input on ``model``
  * mlp gate/up: d_ff on ``model``;  down: d_ff (input) on ``model``
  * embeddings & lm_head: vocab on ``model``
  * MoE experts: expert axis on ``model`` when divisible, else d_ff
  * batch on ``data`` (and ``pod``) when divisible, else replicated
  * with ``fsdp=True``, the non-model axis of every weight is additionally
    sharded over ``data`` (ZeRO-3 style; used by the train step)
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple, Union

import jax
from jax.sharding import PartitionSpec as P

Axis = Union[str, Tuple[str, ...], None]


@dataclass(frozen=True)
class ShardingPolicy:
    data: Axis = "data"            # batch axis name(s) — ("pod","data") multi-pod
    model: Axis = "model"          # tensor axis name
    fsdp: bool = False             # additionally shard weights over `data`
    shard_experts: bool = True     # expert-parallel MoE when divisible
    expert_2d: bool = False        # also shard expert d_ff over `data` (huge MoE)
    replicate_batch: bool = False  # 2D-TP serving: batch replicated, weights 2D
    mesh_axis_sizes: dict = field(default_factory=dict)  # name -> size (for divisibility)

    def axis_size(self, ax: Axis) -> int:
        if ax is None:
            return 1
        names = (ax,) if isinstance(ax, str) else ax
        n = 1
        for a in names:
            n *= self.mesh_axis_sizes.get(a, 1)
        return n

    def batch_axis(self, batch: int) -> Axis:
        if self.replicate_batch:
            return None
        return self.data if batch % max(self.axis_size(self.data), 1) == 0 else None


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
    return "/".join(parts)


def _wspec(pol: ShardingPolicy, rank: int, shard_dim: int, leaf, stacked_dims: int):
    """Weight spec: `shard_dim` (relative to the matrix dims) on model axis.
    `stacked_dims` leading axes (layer/expert stacks) are unsharded unless noted."""
    spec = [None] * rank
    mat_start = stacked_dims
    spec[mat_start + shard_dim] = pol.model
    if pol.fsdp:
        other = mat_start + (1 - shard_dim)
        size = pol.axis_size(pol.data)
        if leaf.shape[other] % max(size, 1) == 0 and size > 1:
            spec[other] = pol.data
    return P(*spec)


OUT_SHARDED = ("q", "k", "v", "gate", "up", "fc1", "in_x", "in_gate",
               "in_proj", "gate_r", "gate_i", "lm_head")


def _quant_scale_spec(ps, leaf, pol, m_size):
    """Spec for int8 per-output-channel scales [..., N]: follows the sibling
    weight's output-dim sharding; K-sharded weights have replicated scales."""
    rank = len(leaf.shape)
    parent = ps.rsplit("/", 2)[-2]
    spec = [None] * rank
    if "/experts/" in ps:
        # expert scales [L, E, N] (or [L, E, D] for down): expert dim rank-2
        if pol.shard_experts and leaf.shape[rank - 2] % max(m_size, 1) == 0:
            spec[rank - 2] = pol.model
            if pol.expert_2d and not ps.endswith("down/scale"):
                d_size = pol.axis_size(pol.data)
                if d_size > 1 and leaf.shape[-1] % d_size == 0:
                    spec[-1] = pol.data
            return P(*spec)
        if parent in ("gate", "up") and leaf.shape[-1] % max(m_size, 1) == 0:
            spec[-1] = pol.model
        return P(*spec)
    if parent in OUT_SHARDED and leaf.shape[-1] % max(m_size, 1) == 0:
        spec[-1] = pol.model
    return P(*spec)


def param_specs(cfg, params_shape, pol: ShardingPolicy):
    """params_shape: pytree of ShapeDtypeStruct (from jax.eval_shape(model.init))."""
    m_size = pol.axis_size(pol.model)

    def rule(path, leaf):
        ps = _path_str(path).replace("/w_q", "/w")   # int8 weights share w rules
        rank = len(leaf.shape)
        stacked = rank - 2  # layer-stack (and expert) leading dims for matrices
        if ps.endswith("/scale") and "norm" not in ps.rsplit("/", 2)[-2]:
            return _quant_scale_spec(ps, leaf, pol, m_size)

        def div(dim_idx):
            return leaf.shape[dim_idx] % max(m_size, 1) == 0

        # ---- embeddings / unembedding: vocab on model
        if ps.endswith("embed/table"):
            return P(pol.model, None) if div(0) else P(None, None)
        if "lm_head" in ps:
            return _wspec(pol, rank, 1, leaf, rank - 2) if div(rank - 1) else P(*([None] * rank))
        # ---- MoE experts: [L, E, D, F]
        if "/experts/" in ps or ps.startswith("experts/"):
            # expert weights are [E, D, F] or layer-stacked [L, E, D, F]:
            # the expert axis is always third-from-last.
            E = leaf.shape[rank - 3]
            if pol.shard_experts and E % max(m_size, 1) == 0:
                spec = [None] * rank
                spec[rank - 3] = pol.model          # expert dim
                if pol.expert_2d:
                    d_size = pol.axis_size(pol.data)
                    ff_dim = rank - 1 if not ps.endswith("down/w") else rank - 2
                    if d_size > 1 and leaf.shape[ff_dim] % d_size == 0:
                        spec[ff_dim] = pol.data     # 2D expert sharding
                return P(*spec)
            shard_dim = 0 if ps.endswith("down/w") else 1
            return _wspec(pol, rank, shard_dim, leaf, rank - 2)
        if "router" in ps:
            return P(*([None] * rank))
        # ---- attention
        if any(ps.endswith(f"{n}/w") for n in ("q", "k", "v")) or "/in_" in ps or ps.endswith("in_proj/w"):
            return _wspec(pol, rank, 1, leaf, rank - 2) if div(rank - 1) else P(*([None] * rank))
        if ps.endswith("o/w") or ps.endswith("out/w") or ps.endswith("out_proj/w"):
            return _wspec(pol, rank, 0, leaf, rank - 2) if div(rank - 2) else P(*([None] * rank))
        # ---- mlp
        if ps.endswith("gate/w") or ps.endswith("up/w") or ps.endswith("fc1/w"):
            return _wspec(pol, rank, 1, leaf, rank - 2) if div(rank - 1) else P(*([None] * rank))
        if ps.endswith("down/w") or ps.endswith("fc2/w"):
            return _wspec(pol, rank, 0, leaf, rank - 2) if div(rank - 2) else P(*([None] * rank))
        # ---- hybrid gates (w x w): shard output
        if ps.endswith("gate_r/w") or ps.endswith("gate_i/w"):
            return _wspec(pol, rank, 1, leaf, rank - 2) if div(rank - 1) else P(*([None] * rank))
        # ---- everything else (norms, biases, conv, scalars): replicated
        return P(*([None] * rank))

    return jax.tree_util.tree_map_with_path(rule, params_shape)


def cache_specs(cfg, cache_shape, pol: ShardingPolicy, batch: int,
                shard_seq: bool = True):
    """KV/state caches: batch on data when divisible.

    KV leaves additionally shard one axis on the model axis when divisible.
    For ring buffers [L, B, W, Kv, D] that is the sequence axis W
    (sequence-parallel cache): attention over the cache becomes a sharded
    contraction that GSPMD resolves with partial softmax terms + a small
    all-reduce. For paged pools [L, NB, BS, Kv*D] (token-major, see
    cache/paged_kv.py) it is the minor axis, cut into whole-head ``Kv/n*D``
    pieces: the split the Pallas paged kernels run under ``shard_map``
    (kernels/ops.py) alongside the head-sharded q projection. Either way
    the cache — the dominant serving tensor — shrinks by the model-axis size
    per device.
    """
    b_ax = pol.batch_axis(batch)
    m_size = pol.axis_size(pol.model)
    paged = isinstance(cache_shape, dict) and "block_table" in cache_shape

    def rule(path, leaf):
        rank = len(leaf.shape)
        if rank == 0:
            return P()
        spec = [None] * rank
        # batch dim position is structural: hybrid "tail" caches are unstacked
        # ([B, ...]); every other cache carries a leading layer/block stack
        # ([L, B, ...]). Never guess by size — L can collide with B.
        bdim = 0 if "tail" in _path_str(path) else 1
        if bdim < rank and leaf.shape[bdim] == batch:
            spec[bdim] = b_ax
        key = _path_str(path).split("/")[-1]
        if paged and rank == 4:          # pool: split whole kv heads
            ax, n = 3, leaf.shape[3] // cfg.head_dim
        else:                            # ring: split the sequence axis
            ax, n = 2, leaf.shape[2] if rank == 5 else 0
        if shard_seq and key in ("k", "v") and n and m_size > 1:
            if b_ax is None:
                # batch replicated (2D-TP serving): spread over EVERY axis
                d_names = (() if pol.data is None else
                           ((pol.data,) if isinstance(pol.data, str) else tuple(pol.data)))
                m_names = ((pol.model,) if isinstance(pol.model, str)
                           else tuple(pol.model))
                full = d_names + m_names
                sz = pol.axis_size(pol.data) * m_size
                if n % sz == 0:
                    spec[ax] = full
                elif n % m_size == 0:
                    spec[ax] = pol.model
            elif n % m_size == 0:
                spec[ax] = pol.model
        return P(*spec)

    return jax.tree_util.tree_map_with_path(rule, cache_shape)


def io_specs(pol: ShardingPolicy, batch: int):
    """(tokens_spec, logits_spec) for step functions."""
    b_ax = pol.batch_axis(batch)
    return P(b_ax, None), P(b_ax, None, pol.model)


# ---------------------------------------------------------------------------
# spec-tree -> sharding-tree assembly (shared by the dry-run step builders in
# launch/steps.py and the placement lowering layer in api/placement.py)
# ---------------------------------------------------------------------------
def ns_tree(mesh, spec_tree):
    """PartitionSpec tree -> NamedSharding tree on ``mesh``."""
    from jax.sharding import NamedSharding
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def sds_with(shard_tree, shape_tree):
    """Attach a sharding tree to a ShapeDtypeStruct tree (dry-run inputs)."""
    return jax.tree.map(
        lambda sds, sh: jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=sh),
        shape_tree, shard_tree)
