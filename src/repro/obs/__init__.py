"""Unified observability: metrics registry, span tracer, provenance.

One :class:`Obs` bundle threads through the routing stack
(``Router(..., obs=...)`` → pipeline → simulators):

* ``obs.registry`` — :class:`~repro.obs.registry.MetricsRegistry`
  (counters/gauges/histograms, snapshot/merge; see that module for the
  worker fixed-slot schema the process backend's shared-memory metrics
  block follows),
* ``obs.tracer`` — :class:`~repro.obs.trace.SpanTracer` (deterministic
  virtual-clock Chrome trace JSON, or ``jax.profiler`` annotations on
  the device trace's clock),
* ``obs.provenance`` — :class:`~repro.obs.provenance
  .ProvenanceRecorder` (per-decision top-k landscape + the
  multiplication-failure detector).

The served path takes the same bundle: ``EngineCluster(..., obs=...)``
hands it to its router and to each ``InstanceEngine``.

**Disabled-mode identity (Contract 5).**  Observability off is not a
cheap mode — it is *no* mode: every integration point in the hot path
is an ``obs is None`` (or component ``is None``) branch, so with the
default ``obs=None`` the routing stack and the engines execute the
exact pre-PR instruction sequence.  Bit-identity with the frozen references is
therefore structural, and ``bench_router_scale`` stays within noise.
With tracing enabled at the default every-8th-wave sampling, the
enabled-mode budget is ≤5 % closed-loop overhead
(``tests/test_obs.py`` enforces both).
"""
from __future__ import annotations

from typing import Optional

from .provenance import ProvenanceRecorder
from .registry import (MetricsRegistry, WORKER_SLOTS, N_WORKER_SLOTS,
                       ingest_router, merge_snapshots)
from .trace import (DEFAULT_SAMPLE_EVERY, ROUTER_PID, TRACE_CLOCKS,
                    SpanTracer, load_trace, validate_events)


class Obs:
    """Observability bundle: any component may be ``None`` (off)."""

    __slots__ = ("registry", "tracer", "provenance")

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[SpanTracer] = None,
                 provenance: Optional[ProvenanceRecorder] = None):
        self.registry = registry
        self.tracer = tracer
        self.provenance = provenance


def make_obs(metrics: bool = True, trace=False,
             provenance: bool = False,
             sample_every: int = DEFAULT_SAMPLE_EVERY,
             top_k: int = 4) -> Obs:
    """Build an :class:`Obs` bundle.

    ``metrics`` is on by default (a registry alone costs a few dict
    increments per *wave*); ``trace`` and ``provenance`` are opt-in —
    tracing records the span tree for every ``sample_every``-th wave,
    provenance pays one extra walk + score row per decision.
    ``trace=True`` is the simulators' virtual clock; ``trace="profiler"``
    puts every span on the ``jax.profiler`` clock (see
    ``repro.obs.trace``).
    """
    if trace not in (False, True, "profiler"):
        raise ValueError(f"trace must be False, True or 'profiler', "
                         f"not {trace!r}")
    reg = MetricsRegistry() if metrics else None
    return Obs(
        registry=reg,
        tracer=(SpanTracer(sample_every=sample_every,
                           clock="profiler" if trace == "profiler"
                           else "virtual")
                if trace else None),
        provenance=(ProvenanceRecorder(registry=reg, top_k=top_k)
                    if provenance else None))


__all__ = [
    "Obs", "make_obs", "MetricsRegistry", "SpanTracer",
    "ProvenanceRecorder", "WORKER_SLOTS", "N_WORKER_SLOTS",
    "ingest_router", "merge_snapshots", "load_trace", "validate_events",
    "ROUTER_PID", "DEFAULT_SAMPLE_EVERY", "TRACE_CLOCKS",
]
