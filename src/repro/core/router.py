"""Global scheduler (paper Fig. 3): filter → score → route.

The router owns the indicator factory and a policy; instance engines
(simulated or real) push state updates through the factory hooks —
piggybacked on responses in a real deployment.  Per-decision latency is
recorded (the paper's §3 highlights router-implementation overhead).

``route_batch`` coalesces an arrival wave: the policy plans every
assignment in one fused device computation (see
``repro.kernels.route_score``) and the router commits the plan through
the exact per-request hook sequence ``route`` performs — so the batch is
bit-identical to k sequential ``route`` calls.  The one effect the
device plan cannot model is a KV$ eviction fired by a mid-wave insert;
the factory's eviction counter detects that and the remaining requests
re-route sequentially (the tie counter is consumed per *committed*
decision, so the fallback resumes exactly where sequential routing
would be).
"""
from __future__ import annotations

import time
from typing import List, Optional, Sequence

from .indicators import IndicatorFactory
from .pipeline import RoutingPipeline
from .policies import Policy
from .types import Request


def commit_wave_plan(factory: IndicatorFactory, reqs: Sequence[Request],
                     commit, fallback) -> List:
    """Commit a device wave plan with the mid-wave eviction guard.

    The plan's hit model is exact *unless* a commit's KV$ insert evicts
    (caches only grow otherwise): snapshot the factory's eviction
    counter first, re-check it before every commit, and hand the rest of
    the wave to ``fallback`` (sequential routing of that tail, returning
    its decisions) the moment it moves.
    This ordering is the bit-identity invariant shared by
    ``Router.route_batch`` and ``PDDisaggSim._on_arrivals`` — keep it in
    one place.
    """
    ev0 = factory.evictions
    out: List = []
    for j, req in enumerate(reqs):
        if factory.evictions != ev0:
            out.extend(fallback(reqs[j:]))
            return out
        out.append(commit(j, req))
    return out


class Router:
    """Owns the factory and a policy; see ``docs/ARCHITECTURE.md`` for
    the layer map a ``route_batch`` call traverses.

    ``n_shards > 1`` shards the factory's aggregated prefix index (and
    the device-mirror partition) by instance-id range — the multi-
    worker router-tier shape for clusters past ~4k instances.  Routing
    decisions are bit-identical at any shard count;
    ``parallel_walks=True`` additionally fans index walks over a
    thread pool with a deterministic merge (each shard owns a disjoint
    slice of the hit vector — see ``repro.core.sharded_index``).
    ``walk_telemetry`` reports the per-shard walk costs either way.
    """

    def __init__(self, policy: Policy, n_instances: int,
                 kv_capacity_tokens: int = 1 << 62, block_size: int = 64,
                 exact_only: bool = False,
                 insert_on_route: bool = True,
                 n_shards: int = 1, parallel_walks: bool = False,
                 walk_backend: Optional[str] = None,
                 pipeline_overlap: Optional[bool] = None,
                 shard_timeout_s: Optional[float] = None,
                 anti_entropy_k: int = 0,
                 fleet=None,
                 obs=None):
        self.policy = policy
        self.factory = IndicatorFactory(
            n_instances, kv_capacity_tokens=kv_capacity_tokens,
            block_size=block_size, exact_only=exact_only,
            n_shards=n_shards, parallel_walks=parallel_walks,
            walk_backend=walk_backend, shard_timeout_s=shard_timeout_s,
            fleet=fleet)
        self.insert_on_route = insert_on_route
        self.decision_ns: List[int] = []
        self.routed = 0
        self.pipeline = RoutingPipeline(self, overlap=pipeline_overlap)
        #: anti-entropy budget: shards digest-verified (and repaired on
        #: mismatch) at the tail of every routed wave; 0 (the default)
        #: disables the sweep entirely
        self.anti_entropy_k = int(anti_entropy_k)
        # observability bundle (repro.obs.Obs) — None (the default)
        # means *no* observability code runs anywhere in the routing
        # stack: every integration point is an ``is None`` branch, so
        # the disabled path is the exact pre-observability instruction
        # sequence (Contract 5, docs/ARCHITECTURE.md)
        self.obs = obs
        if obs is not None and (obs.registry is not None
                                or obs.tracer is not None):
            self.factory.on_degraded_rebuild = self._on_degraded_rebuild
            self.factory.on_shard_repair = self._on_shard_repair
            self.factory.attach_backend_events(self._on_backend_event)

    # ---- lifecycle ----------------------------------------------------
    def close(self):
        """Tear down the factory's walk backend (thread pools, process
        workers + their shared-memory segments).  Required for process
        backends; a no-op for serial ones."""
        self.pipeline.drop_prefetch()
        self.factory.close()

    def __enter__(self) -> "Router":
        return self

    def __exit__(self, *exc):
        self.close()

    # ---- observability -----------------------------------------------
    def _on_degraded_rebuild(self, n: int):
        """Exactly-once degraded-rebuild event (fired by the factory at
        the counter increment — see ``IndicatorFactory
        .on_degraded_rebuild``)."""
        obs = self.obs
        if obs.registry is not None:
            obs.registry.inc("events.degraded_rebuild")
        if obs.tracer is not None:
            obs.tracer.instant("index.degraded_rebuild",
                               args={"n": n})

    def _on_shard_repair(self, s: int, n: int):
        """Exactly-once scoped-repair event (fired by the factory at
        the ``shard_repairs`` increment)."""
        obs = self.obs
        if obs.registry is not None:
            obs.registry.inc("events.index_repair")
        if obs.tracer is not None:
            obs.tracer.instant("index.shard_repair",
                               args={"shard": s, "n": n})

    def _on_backend_event(self, kind: str, shard: int, info: dict):
        """Shard-backend recovery events (``worker_restart`` /
        ``worker_timeout`` / ``shard_escalated`` / ``shard_repair``) →
        obs registry counter + tracer instant."""
        obs = self.obs
        if obs.registry is not None:
            obs.registry.inc(f"events.{kind}")
        if obs.tracer is not None:
            obs.tracer.instant(f"shard.{kind}",
                               args={"shard": shard, **info})

    def _emit_churn(self, kind: str, iid: int):
        obs = self.obs
        if obs is None:
            return
        if obs.registry is not None:
            obs.registry.inc(f"churn.{kind}")
        if obs.tracer is not None:
            obs.tracer.instant(f"churn.{kind}", args={"iid": iid})

    def metrics_snapshot(self) -> dict:
        """The unified cluster metrics view: one registry snapshot
        merging the live obs registry (if attached), every legacy
        telemetry accumulator (factory walks, pipeline stages,
        degraded rebuilds — ``repro.obs.registry.ingest_router``), and
        the shard backend's fixed-slot worker block.  Works with or
        without an attached obs bundle; ``walk_telemetry`` /
        ``stage_stats`` remain as compatibility shims over the same
        accumulators."""
        from repro.obs.registry import MetricsRegistry, ingest_router
        reg = (self.obs.registry if self.obs is not None
               and self.obs.registry is not None else MetricsRegistry())
        ingest_router(reg, self)
        return reg.snapshot()

    # ------------------------------------------------------------------
    def _route_masked(self, req: Request, mask, now: float) -> int:
        """Capability-masked decision (Contract 7): the feasibility mask
        is intersected into the policy's candidate set exactly like the
        alive mask — a *pre-score filter*, restored afterwards so the
        next (unconstrained) request sees the legacy path.  A request no
        live instance can serve must be shed upstream
        (``AdmissionController``); reaching the policy with an empty
        candidate set is a caller bug."""
        pol = self.policy
        saved = pol.alive
        eff = mask if saved is None else (mask & saved)
        if not eff.any():
            raise ValueError(
                f"no live instance serves model_requirement="
                f"{req.model_requirement!r} (shed it at admission)")
        pol.alive = eff
        try:
            return pol.route(req, self.factory, now)
        finally:
            pol.alive = saved

    def route(self, req: Request, now: float) -> int:
        t0 = time.perf_counter_ns()
        mask = self.factory.feasible_mask(req.model_requirement)
        if mask is None:
            iid = self.policy.route(req, self.factory, now)
        else:
            iid = self._route_masked(req, mask, now)
        self.decision_ns.append(time.perf_counter_ns() - t0)
        obs = self.obs
        if obs is not None and obs.provenance is not None:
            # before any commit hook mutates indicators, so the record
            # captures the landscape the argmin actually saw
            obs.provenance.record(req, iid, self.factory, now,
                                  policy=self.policy)
        inst = self.factory[iid]
        hit = inst.kv_hit(req, touch=True)
        req.sched_to = iid
        req.hit_tokens = hit
        req.t_sched = now
        inst.on_route(req, now, hit)
        if self.insert_on_route:
            # prefill will materialise this KV$ promptly; index it now so
            # follow-up requests in the same class see the hit.
            inst.kv.insert(req.blocks)
        self.routed += 1
        return iid

    # ------------------------------------------------------------------
    def route_batch(self, reqs: Sequence[Request],
                    now: float) -> List[int]:
        """Route a coalesced arrival wave; bit-identical to sequential
        ``route`` calls.  k <= 1 and host-fallback policies degenerate to
        the scalar path; a mid-wave eviction aborts the remaining plan.

        The wave path is the three-stage ``RoutingPipeline`` (walk →
        score → commit, see ``repro.core.pipeline``): the factory
        computes one aggregated-index walk per unique prompt (sharded
        factories concatenate per-shard hit vectors — same full-width
        matrix) plus the pairwise-LCP credit, the policy's score stage
        runs the fused score→argmin→feedback loop on device over the
        factory's device mirror (``device_view`` re-uploads only dirty
        shards), and the plan commits through the identical per-request
        hooks — in-place numpy writes that re-flip the dirty flags.
        Device code never writes indicators back; the numpy arrays stay
        the single source of truth (the sync contract in
        ``repro.core.indicators``).  On asynchronous walk backends the
        pipeline overlaps the *next* wave's walk with this wave's score
        stage — still bit-identical (insert capture + LCP patch).

        ``decision_ns`` telemetry records the plan cost amortized over
        the wave (the same policy-decision cost ``route`` records)."""
        if not reqs:
            return []
        if (len(reqs) == 1 or not self.insert_on_route
                or not self.policy.batch_supported(self.factory)
                or (self.factory.fleet is not None
                    and any(r.model_requirement for r in reqs))):
            # a wave carrying model_requirements needs the per-request
            # capability mask (Contract 7), which the fused device plan
            # has no input for — documented host fallback.
            # without insert-on-route the plan's intra-wave LCP credit
            # would model KV$ inserts that never happen — host path.
            # any pending speculative walk targeted the wave path; the
            # scalar path mutates the index without capture, so drop it
            self.pipeline.drop_prefetch()
            return [self.route(r, now) for r in reqs]
        return self.pipeline.run_wave(reqs, now)

    # ---- overload control --------------------------------------------
    def on_retract(self, iid: int, req: Request, prefill_left: int):
        """A queued-or-prefilling request was cancelled (deadline blown):
        reverse its ``on_route`` contribution to the indicators so the
        instance's score reflects the freed work.  The speculative KV$
        insert from routing stays — the LRU evicts it like any other
        cold lineage (re-indexing a retraction would cost a walk for
        state the engine may genuinely keep)."""
        self.factory[iid].on_retract(req, prefill_left)

    # ---- instance churn ----------------------------------------------
    def mark_failed(self, iid: int):
        """An instance died: before the next wave commits, the failure
        must reach scoring (policy alive mask), the aggregated index
        (``remove_instance`` through the shard backend's owner-routed
        mutation), the device mirror (dirty flags on the zeroed
        indicator columns), and speculation (pending captured walks
        dropped) — Contract 4 in ``docs/ARCHITECTURE.md``.

        The churn event is emitted *before* the teardown: a shard
        worker dying mid-wave makes the index mutation below retry
        through a degraded rebuild, and the emission must not sit
        inside that retried region (exactly-once into the registry —
        pinned by ``tests/test_chaos.py``)."""
        self._emit_churn("fail", iid)
        self.pipeline.drop_prefetch()
        self.factory.on_instance_failed(iid)
        self.policy.on_instance_failed(iid, self.factory.n)

    def mark_drained(self, iid: int):
        """Graceful drain: stop routing new work to ``iid`` but keep its
        KV$ lineage and queue state intact (in-flight work completes)."""
        self._emit_churn("drain", iid)
        self.pipeline.drop_prefetch()
        self.policy.on_instance_failed(iid, self.factory.n)

    def mark_recovered(self, iid: int):
        """A failed/drained instance rejoined (cold: its KV$ and queue
        state were reset at failure time).  When the whole fleet is
        live again the policy drops its mask and the device wave path
        resumes."""
        self._emit_churn("recover", iid)
        self.policy.on_instance_recovered(iid)

    # ---- response piggyback hooks ------------------------------------
    def on_prefill_progress(self, iid: int, n_tokens: int):
        self.factory[iid].on_prefill_progress(n_tokens)

    def on_start_running(self, iid: int, req: Request):
        self.factory[iid].on_start_running(req)

    def on_decode_token(self, iid: int):
        self.factory[iid].on_decode_token()

    def on_finish(self, iid: int, req: Request):
        self.factory[iid].on_finish(req)
        self.policy.on_finish(iid, req)

    # ------------------------------------------------------------------
    def session_pin(self, session_id: int) -> Optional[int]:
        """Session-affinity hint: the instance holding this session's
        KV$ lineage, if the policy tracks pins (None otherwise).  Lets
        drivers and demos surface where a session lives without
        reaching into policy internals — and is the hook a session-
        aware LMetric variant would use to skip the aggregated-index
        walk entirely when the pinned instance holds the whole lineage
        (ROADMAP §Closed-loop next steps)."""
        return self.policy.session_pin(session_id)

    # ------------------------------------------------------------------
    def mean_decision_us(self) -> float:
        if not self.decision_ns:
            return 0.0
        return sum(self.decision_ns) / len(self.decision_ns) / 1e3

    def mean_walk_us(self) -> float:
        """Mean host cost of one aggregated-index walk (per unique
        prompt) — the host half of every KV$-aware decision, accumulated
        by the factory across both the single-request and the wave-input
        paths.  This is the number the flat bitset index + LCP walk
        reuse optimise; ``bench_prefix_index`` tracks it old-vs-new."""
        return self.factory.mean_walk_us()

    def walk_telemetry(self) -> dict:
        """Shard-tagged walk telemetry for the host half of routing:

        * ``mean_walk_us`` — the overall per-unique-prompt walk cost
          (identical to :meth:`mean_walk_us`, fan-out + shared
          lexicographic sort included),
        * ``shards`` — one record per index shard (``shard``, its
          instance range ``lo``/``hi``, ``walks``, ``mean_walk_us``);
          an unsharded factory reports one pseudo-shard over [0, n),
        * ``max_shard_us`` — the slowest shard's mean walk cost: the
          critical path a parallel walk fan-out pays per wave (serial
          fan-out pays the sum over shards instead),
        * ``pipeline`` — per-stage wave timings from the routing
          pipeline (``walk_us`` / ``score_us`` / ``commit_us`` mean
          per-wave cost, wave/speculation counters, and the
          ``overlap_fraction`` of speculative walk time hidden behind
          the score stage — see ``RoutingPipeline.stage_stats``).

        ``bench_router_scale``'s sharded section records exactly this
        structure per (instance count, shard count) point."""
        shards = self.factory.shard_walk_stats()
        return {"mean_walk_us": self.factory.mean_walk_us(),
                "max_shard_us": max(s["mean_walk_us"] for s in shards),
                "shards": shards,
                "pipeline": self.pipeline.stage_stats()}
