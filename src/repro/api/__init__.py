"""Unified compile-plan -> session API over all speculative execution paths.

Two phases, mirroring the paper's compile-then-run split:

  1. PLAN — ``Planner(DeploymentSpec).plan()`` runs the analytical cost model
     (Eq. 1) and the heterogeneous-mapping DSE offline and freezes every
     decision (strategy, gamma schedule or AR fallback, cache layout,
     batching mode, submesh placement) into a serializable ``ExecutionPlan``.
  2. RUN — ``Session(target, drafter, params_t, params_d, plan)`` executes
     any plan through one facade: ``generate()``, ``generate_batch()``,
     ``serve()``. The legacy engines are internal backends behind the
     ``SpecBackend`` protocol.

See docs/API.md for the lifecycle and the migration table from legacy
constructors.
"""
from repro.api.backends import SpecBackend
from repro.api.feedback import (AlphaEma, GammaController, best_gamma,
                                respec_from_drift)
from repro.api.placement import (Placement, PlacementError, RolePlacement,
                                 lower)
from repro.api.plan import (CacheLayout, DeploymentSpec, ExecutionPlan,
                            GammaSchedule, PlacementPlan, SubmeshSpec)
from repro.api.planner import Planner
from repro.api.planner import plan as plan_deployment
from repro.api.session import Session
from repro.serving.scheduler import ServeRequest

__all__ = ["AlphaEma", "CacheLayout", "DeploymentSpec", "ExecutionPlan",
           "GammaController", "GammaSchedule", "Placement", "PlacementError",
           "PlacementPlan", "Planner", "RolePlacement", "ServeRequest",
           "Session", "SpecBackend", "SubmeshSpec", "best_gamma", "lower",
           "plan_deployment", "respec_from_drift"]
