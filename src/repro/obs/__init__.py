"""Structured telemetry for the speculative-decoding stack.

Three cooperating pieces, all host-side:

  * ``trace``  — span-based tracing with Chrome-trace/Perfetto export, so a
    served workload renders as a draft/verify/commit timeline across the
    drafter-mesh/target-mesh rows; every span is also a
    ``jax.profiler.TraceAnnotation``, so a captured profile shows it on the
    device trace's clock.
  * ``events`` — a typed per-round event log (RoundEvent) that subsumes the
    round-level counters in ``serving/metrics.py``.
  * ``drift``  — an online predicted-vs-measured monitor that runs the
    paper's cost-model validation loop continuously: each measured round is
    compared against the ``cost_model.round_time`` terms the planner used,
    and sustained disagreement is surfaced per component.

``clock`` is the ONE module in ``src/repro`` allowed to read wall/perf
clocks (CI-enforced); everything else takes an injectable clock so tests
can drive time manually.
"""
from repro.obs.drift import DriftConfig, DriftMonitor
from repro.obs.events import RoundEvent, RoundEventLog
from repro.obs.trace import NULL_TRACER, Span, Tracer

__all__ = [
    "DriftConfig",
    "DriftMonitor",
    "NULL_TRACER",
    "RoundEvent",
    "RoundEventLog",
    "Span",
    "Tracer",
]
