"""Lowering layer: ``PlacementPlan`` -> per-role meshes + sharding trees.

The planner's decision ③ (core/partition.py DSE) records WHERE drafter and
target live as two ``SubmeshSpec``s — until now a purely advisory artifact:
every execution path ran on one implicit caller-supplied mesh. This module
makes the decision real. ``lower(plan.placement)`` turns the plan into a
``Placement``:

  * one ``jax.sharding.Mesh`` per role, carved out of the visible devices
    as disjoint device sets — the paper's drafter-PU/target-PU split. A plan
    whose roles do not fit the visible devices side by side raises
    ``PlacementError``: it never runs overlapped or single-mesh in its
    place;
  * a ``ShardingPolicy`` per role (submesh axes named ``data``/``pod``
    become the role's batch axes, everything else its tensor axes), from
    which the ``models/specs.py`` builders derive ``NamedSharding`` trees
    for params, KV caches, and token streams;
  * ``device_put`` helpers that pin each role's params/cache onto its own
    submesh and perform the explicit cross-submesh transfer of the
    gamma-token draft/verify handoff (``Placement.to_target`` /
    ``Placement.to_drafter`` — the only data that crosses domains per round,
    exactly the paper's tiny PU-to-PU token exchange).

The single-mesh case is the DEGENERATE lowering: when the plan places
drafter and target on the same submesh (the default replicated plan), no
meshes are constructed and every helper is the identity — execution is
bit-identical to the pre-placement stack (goldens-tested).

This module (plus the device-level factories in ``launch/mesh.py``) is the
ONLY place inference code may construct a ``jax.sharding.Mesh`` — a CI grep
guard enforces it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.api.plan import PlacementPlan, SubmeshSpec
from repro.models.specs import (ShardingPolicy, cache_specs, io_specs,
                                ns_tree, param_specs, sds_with)

DATA_AXES = ("data", "pod")      # submesh axes that carry batch, not tensors


class PlacementError(ValueError):
    """The PlacementPlan cannot be realized on the visible devices."""


# spec-tree -> sharding-tree assembly lives beside the spec builders
# (models/specs.py) — re-exported here for the lowering layer's callers


# -------------------------------------------------------------- role lowering
@dataclass(frozen=True)
class RolePlacement:
    """One partition's realized execution domain: mesh + sharding policy.

    ``mesh is None`` is the degenerate role (implicit default device(s));
    every helper then degrades to the identity so placed and un-placed code
    paths share one call shape.
    """
    spec: SubmeshSpec
    mesh: Optional[Mesh] = None
    policy: ShardingPolicy = ShardingPolicy(data=None, model=None)

    @property
    def devices(self) -> tuple:
        return () if self.mesh is None else tuple(self.mesh.devices.flat)

    @property
    def _replicated(self) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P())

    # ------------------------------------------------------------- transfers
    def put(self, tree):
        """Replicate a (small) tree onto this role's submesh — the
        cross-submesh handoff primitive. Identity when degenerate."""
        if self.mesh is None or tree is None:
            return tree
        return jax.device_put(tree, self._replicated)

    # ------------------------------------------------------------- shardings
    def param_shardings(self, model):
        # memoized per model CONFIG (shardings are a pure function of the
        # config + this role's policy, and cfg identity cannot be recycled
        # the way id(model) can): engines call put_params every generate(),
        # and the eval_shape + spec walk are invariant host work on the hot
        # path (object.__setattr__ because the dataclass is frozen)
        cache = self.__dict__.get("_param_shardings")
        if cache is None:
            cache = {}
            object.__setattr__(self, "_param_shardings", cache)
        key = model.cfg
        if key not in cache:
            pshape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
            cache[key] = ns_tree(self.mesh,
                                 param_specs(model.cfg, pshape, self.policy))
        return cache[key]

    def cache_shardings(self, model, cache, batch: int):
        return ns_tree(self.mesh,
                       cache_specs(model.cfg, cache, self.policy, batch))

    def token_sharding(self, batch: int) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        tok_spec, _ = io_specs(self.policy, batch)
        return NamedSharding(self.mesh, tok_spec)

    # ------------------------------------------------------------ placement
    def put_params(self, model, params):
        """Pin a role's params onto its submesh with the derived shardings."""
        if self.mesh is None:
            return params
        return jax.device_put(params, self.param_shardings(model))

    def put_cache(self, model, cache, batch: int):
        if self.mesh is None or cache is None:
            return cache
        return jax.device_put(cache, self.cache_shardings(model, cache, batch))

    def jit(self, fn, **jit_kw):
        """``jax.jit(fn)`` whose calls trace under this role's mesh, so the
        Pallas kernels inside find it and run per device under
        ``shard_map`` (kernels/ops.py) — XLA cannot partition them itself.
        Plain ``jax.jit`` when degenerate."""
        jitted = jax.jit(fn, **jit_kw)
        if self.mesh is None:
            return jitted
        mesh = self.mesh.abstract_mesh

        def on_mesh(*args, **kw):
            with jax.sharding.use_abstract_mesh(mesh):
                return jitted(*args, **kw)
        return on_mesh


def _role_policy(spec: SubmeshSpec) -> ShardingPolicy:
    data = tuple(a for a in spec.axes if a in DATA_AXES)
    model = tuple(a for a in spec.axes if a not in DATA_AXES)
    return ShardingPolicy(
        data=(data if len(data) > 1 else (data[0] if data else None)),
        model=(model if len(model) > 1 else (model[0] if model else None)),
        mesh_axis_sizes=dict(zip(spec.axes, spec.sizes)))


def _role_mesh(spec: SubmeshSpec, devices: Sequence) -> Mesh:
    if spec.chips > len(devices):
        raise PlacementError(
            f"submesh {spec.name!r} needs {spec.chips} devices, "
            f"{len(devices)} visible")
    if not spec.axes:                      # replicated = single-chip analogue
        return Mesh(np.asarray(devices[:1]), ("rep",))
    return Mesh(np.asarray(devices[:spec.chips]).reshape(spec.sizes),
                spec.axes)


# ------------------------------------------------------------- the Placement
@dataclass(frozen=True)
class Placement:
    """Realized placement for one (drafter, target) deployment.

    ``heterogeneous`` placements carry two live meshes; the degenerate
    lowering carries none and every helper is the identity, so callers
    thread one Placement object unconditionally.
    """
    drafter: RolePlacement
    target: RolePlacement
    overlap: bool = False              # dispatch next draft under in-flight verify

    @property
    def heterogeneous(self) -> bool:
        return self.drafter.mesh is not None or self.target.mesh is not None

    @property
    def disjoint(self) -> bool:
        """True when drafter and target own non-overlapping device sets (the
        paper's two-PU mapping — required for draft/verify overlap to buy
        wall-clock, not just dispatch slack)."""
        d, t = set(self.drafter.devices), set(self.target.devices)
        return bool(d) and bool(t) and not (d & t)

    # ---------------------------------------------------- per-round handoffs
    def to_target(self, tree):
        """Move the gamma-token draft package onto the target submesh."""
        return self.target.put(tree)

    def to_drafter(self, tree):
        """Move commit results (tokens/lengths) back to the drafter submesh."""
        return self.drafter.put(tree)

    def describe(self) -> str:
        if not self.heterogeneous:
            return "placement: degenerate (single implicit mesh)"
        def one(r: RolePlacement):
            return (f"{r.spec.name}[{len(r.devices)} dev: "
                    f"{','.join(str(d.id) for d in r.devices)}]")
        kind = "disjoint" if self.disjoint else "overlapping"
        return (f"placement: drafter@{one(self.drafter)} "
                f"target@{one(self.target)} ({kind}"
                f"{', overlap-dispatch' if self.overlap else ''})")


DEGENERATE = Placement(drafter=RolePlacement(SubmeshSpec()),
                       target=RolePlacement(SubmeshSpec()))


def lower(plan: PlacementPlan, devices: Optional[Sequence] = None) -> Placement:
    """Lower a PlacementPlan to concrete per-role meshes.

    Identical drafter/target submeshes (the default replicated plan) lower
    to the DEGENERATE placement — a no-op, token-identical to the
    mesh-implicit stack. Distinct submeshes get their own meshes on
    disjoint device sets; raises PlacementError when ``chips_d + chips_t``
    exceed the visible devices.
    """
    if plan.drafter == plan.target:
        return DEGENERATE
    devices = list(jax.devices() if devices is None else devices)
    cd, ct = plan.drafter.chips, plan.target.chips
    if cd + ct > len(devices):
        raise PlacementError(
            f"placement needs {cd}+{ct} devices for disjoint drafter/target "
            f"submeshes, {len(devices)} visible")
    mk = lambda spec, devs: RolePlacement(spec, _role_mesh(spec, devs),
                                          _role_policy(spec))
    return Placement(drafter=mk(plan.drafter, devices[:cd]),
                     target=mk(plan.target, devices[cd:cd + ct]),
                     overlap=getattr(plan, "overlap", False))


def role(spec: SubmeshSpec, devices: Optional[Sequence] = None) -> RolePlacement:
    """Lower ONE submesh to a RolePlacement (its own mesh + policy) — used
    by bench_dse.py to measure per-submesh step times independent of any
    mapping."""
    devices = list(jax.devices() if devices is None else devices)
    return RolePlacement(spec, _role_mesh(spec, devices), _role_policy(spec))

