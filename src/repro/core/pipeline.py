"""Staged routing pipeline: walk → score → commit, with wave overlap.

``Router.route_batch`` used to be one monolithic method; this module
names its three stages and gives each an explicit boundary so they can
overlap across consecutive waves:

* **walk** — per-unique-prompt aggregated-index hit vectors plus the
  pairwise-LCP matrix (``IndicatorFactory.wave_submit`` /
  ``wave_collect``; sharded factories fan out per shard);
* **score** — the fused device score→argmin→feedback loop
  (``repro.kernels.route_score.route_wave_submit`` / ``_collect``,
  dispatched through ``Policy.plan_submit``);
* **commit** — per-request hook commits under the mid-wave eviction
  guard (``repro.core.router.commit_wave_plan``), the one stage that
  mutates factory state and therefore serializes everything.

Wave pipelining
---------------
While wave ``k``'s score stage runs on device, wave ``k+1``'s walks run
on the shard backend's host workers: right after dispatching the score
stage, the pipeline asks the simulator for the *likely* next arrival
wave (``next_wave_hint`` peeks the event heap) and submits its walk
speculatively.  Speculation is only attempted on backends whose walks
are truly asynchronous (``ShardBackend.async_walks``) — thread and
process fan-out — unless ``overlap`` is forced for testing.

Bit-identity is non-negotiable, and two things threaten it:

1. **The speculative walk misses wave k's commits.**  The walk
   snapshots the index *before* the commit stage inserts wave ``k``'s
   chains.  The factory brackets the speculation with an **insert
   capture** (``begin_insert_capture`` / ``end_insert_capture``): every
   ``(iid, blocks)`` aggregate insert between snapshot and use is
   recorded, and the walk result is patched column-wise with
   ``depth[:, iid] = max(depth[:, iid], LCP(chain, inserted))`` — exact
   because a radix tree's hit depth *is* the max over stored chains of
   the LCP (the same identity the in-wave device credit uses).
   Evictions cannot be patched (a removed leaf may un-deepen a hit), so
   any eviction during the capture invalidates it and the wave walks
   fresh — the same guard ``commit_wave_plan`` applies mid-wave.
2. **The prediction is wrong.**  Closed-loop feedback can push earlier
   arrivals after the hint was taken.  The pipeline validates the
   speculation by request identity (the very same ``Request`` objects,
   in order) and otherwise discards it — waiting the walk out (the
   worker protocol stays in sync) without counting it in telemetry.

Per-stage timings (walk/score/commit, speculation hidden/blocked time)
accumulate here and surface through ``Router.walk_telemetry()['pipeline']``
and ``bench_router_scale``'s pipeline block.
"""
from __future__ import annotations

import time
from typing import Callable, List, Optional, Sequence

import numpy as np

from .indicators import _pairwise_lcp
from .types import Request


class _NullCtx:
    """No-op context manager for the ``obs=None`` stage-span guards."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_CTX = _NullCtx()


class _Speculation:
    """One outstanding speculative next-wave walk."""

    __slots__ = ("wave", "t_submit")

    def __init__(self, wave, t_submit):
        self.wave = wave            # IndicatorFactory._WaveHandle
        self.t_submit = t_submit


class RoutingPipeline:
    """Owns the staged wave path for one :class:`~repro.core.router.
    Router` — stage execution, cross-wave speculation, and per-stage
    telemetry.

    ``next_wave_hint`` is wired by the simulators to a heap peek;
    ``overlap`` is ``None`` (auto: speculate iff the shard backend's
    walks are asynchronous), ``True`` (force — bit-identity tests), or
    ``False`` (disable).
    """

    def __init__(self, router, overlap: Optional[bool] = None):
        self.router = router
        self.overlap = overlap
        self.next_wave_hint: Optional[Callable[[], Optional[list]]] = None
        self._spec: Optional[_Speculation] = None
        # ---- per-stage telemetry (ns totals across waves) -------------
        self.walk_ns = 0
        self.score_ns = 0
        self.commit_ns = 0
        self.waves = 0
        self.prefetches = 0
        self.prefetch_hits = 0
        #: wall time a consumed speculative walk ran off the critical
        #: path (submit → wait start; an upper bound on true overlap)
        self.spec_hidden_ns = 0
        #: wall time the routing path still blocked waiting for it
        self.spec_blocked_ns = 0

    # ------------------------------------------------------------------
    def _overlap_enabled(self) -> bool:
        if self.overlap is not None:
            return self.overlap
        backend = getattr(self.router.factory._agg, "backend", None)
        return backend is not None and backend.async_walks

    def drop_prefetch(self):
        """Discard any outstanding speculation (wave went down a
        non-pipelined path, or the router is closing)."""
        spec, self._spec = self._spec, None
        if spec is None:
            return
        factory = self.router.factory
        try:
            factory.wave_discard(spec.wave)
        finally:
            factory.end_insert_capture()

    # ------------------------------------------------------------------
    def _patch_speculation(self, wave, h, inserted):
        """Fold commits that landed after the speculative snapshot into
        its depth matrix: ``depth[:, iid] = max(..., LCP(chain, ins))``
        — exact (see module docstring) because no eviction fired."""
        if not inserted:
            return
        depth, _, _ = wave
        chains = list(h.chains)
        u = len(chains)
        cross = _pairwise_lcp(chains + [c for _, c in inserted])
        for j, (iid, _) in enumerate(inserted):
            col = cross[:u, u + j][h.uid]       # per-request credit
            np.maximum(depth[:, iid], col, out=depth[:, iid])

    def _walk_stage(self, reqs: Sequence[Request], tracer=None):
        """Produce (depth, lcp, plen): consume a validated speculation
        (patched for post-snapshot inserts) or walk fresh."""
        factory = self.router.factory
        spec, self._spec = self._spec, None
        if spec is not None:
            h = spec.wave
            predicted = (len(h.reqs) == len(reqs)
                         and all(a is b for a, b in zip(h.reqs, reqs)))
            inserted, valid = factory.end_insert_capture()
            if predicted and valid:
                t0 = time.perf_counter_ns()
                self.spec_hidden_ns += t0 - spec.t_submit
                wave = factory.wave_collect(h)
                self.spec_blocked_ns += time.perf_counter_ns() - t0
                self.prefetch_hits += 1
                if tracer is not None:
                    tracer.instant("spec.consume",
                                   args={"k": len(reqs),
                                         "patched": len(inserted)})
                self._patch_speculation(wave, h, inserted)
                return wave
            if tracer is not None:
                tracer.instant("spec.discard",
                               args={"k": len(h.reqs),
                                     "predicted": predicted,
                                     "valid": valid})
            factory.wave_discard(h)
        return factory.wave_collect(factory.wave_submit(reqs))

    def _maybe_prefetch(self, tracer=None):
        """Between score dispatch and collect: speculatively submit the
        predicted next wave's walk (one outstanding at a time)."""
        router = self.router
        if (self._spec is not None or self.next_wave_hint is None
                or not router.policy.batch_needs_kv
                or not self._overlap_enabled()):
            return
        hint = self.next_wave_hint()
        # k <= 1 waves take the scalar path; no wave walk to hide
        if not hint or len(hint) <= 1:
            return
        factory = router.factory
        factory.begin_insert_capture()
        h = factory.wave_submit(tuple(hint))
        self._spec = _Speculation(h, time.perf_counter_ns())
        self.prefetches += 1
        if tracer is not None:
            tracer.instant("spec.submit", args={"k": len(hint)})

    # ------------------------------------------------------------------
    def run_wave(self, reqs: Sequence[Request], now: float) -> List[int]:
        """Route one coalesced arrival wave through walk → score →
        commit; bit-identical to sequential ``route`` calls (the same
        contract the monolithic path had).

        With an obs bundle attached (``Router(..., obs=...)``) the wave
        additionally emits a nested span tree (wave > walk, score >
        score.submit/score.collect, commit > commit.fallback; sampled
        every Nth wave on the virtual clock), speculation
        consume/discard instants, per-stage duration histograms, and the
        ``pipeline.fallback_waves``/``pipeline.fallback_requests``
        counters of the sequential re-route after a mid-wave eviction.
        With the default ``obs=None`` none of this code runs — the stage
        sequence below is byte-for-byte the pre-observability path
        (Contract 5)."""
        from .router import commit_wave_plan
        router = self.router
        policy = router.policy
        factory = router.factory
        obs = router.obs
        tr = obs.tracer if obs is not None else None
        reg = obs.registry if obs is not None else None
        wave_span = None
        if tr is not None:
            tr.wave_tick()
            wave_span = tr.span("wave", args={"wave": self.waves,
                                              "k": len(reqs)})
            wave_span.__enter__()
        t0 = time.perf_counter_ns()
        with (tr.span("walk") if tr is not None else _NULL_CTX):
            if policy.batch_needs_kv:
                wave = self._walk_stage(reqs, tracer=tr)
            else:
                self.drop_prefetch()
                wave = policy.wave_inputs(reqs, factory)
        t1 = time.perf_counter_ns()
        with (tr.span("score") if tr is not None else _NULL_CTX):
            # device-view upload, wave inputs to the device, dispatch
            with (tr.span("score.submit") if tr is not None
                  else _NULL_CTX):
                handle = policy.plan_submit(wave, factory)
            tp0 = time.perf_counter_ns()
            self._maybe_prefetch(tracer=tr)
            tp = time.perf_counter_ns() - tp0  # prefetch is walk work
            with (tr.span("score.collect") if tr is not None
                  else _NULL_CTX):
                sel, _ = policy.plan_collect(handle)
        t2 = time.perf_counter_ns()
        self.walk_ns += (t1 - t0) + tp
        self.score_ns += (t2 - t1) - tp
        per_req_ns = (t2 - t0) // len(reqs)
        prov = obs.provenance if obs is not None else None

        def commit(j, req):
            iid = int(sel[j])
            if prov is not None:
                # pre-commit landscape: earlier wave commits are already
                # applied — exactly the sequential-routing semantics
                prov.record(req, iid, factory, now, policy=policy)
            policy._next_tie()           # one tie value per commit
            router.decision_ns.append(per_req_ns)
            inst = factory[iid]
            hit = inst.kv_hit(req, touch=True)
            req.sched_to = iid
            req.hit_tokens = hit
            req.t_sched = now
            inst.on_route(req, now, hit)
            if router.insert_on_route:
                inst.kv.insert(req.blocks)
            router.routed += 1
            return iid

        def fallback(tail):
            # a commit's insert evicted: the plan's hits no longer hold,
            # so the rest of the wave routes one request at a time
            if reg is not None:
                reg.inc("pipeline.fallback_waves")
                reg.inc("pipeline.fallback_requests", len(tail))
            with (tr.span("commit.fallback", args={"k": len(tail)})
                  if tr is not None else _NULL_CTX):
                return [router.route(r, now) for r in tail]

        with (tr.span("commit") if tr is not None else _NULL_CTX):
            out = commit_wave_plan(factory, reqs, commit, fallback)
        t3 = time.perf_counter_ns()
        self.commit_ns += t3 - t2
        self.waves += 1
        if wave_span is not None:
            wave_span.__exit__(None, None, None)
        if reg is not None:
            reg.observe("pipeline.walk_us", ((t1 - t0) + tp) / 1e3)
            reg.observe("pipeline.score_us", ((t2 - t1) - tp) / 1e3)
            reg.observe("pipeline.commit_us", (t3 - t2) / 1e3)
            reg.observe("pipeline.wave_size", float(len(reqs)))
        # anti-entropy sweep (PR 9): digest-verify the next K shards
        # against KV truth, repairing on mismatch.  Off the routing
        # result path (this wave is already committed) and disabled at
        # the default k=0 — the fault-free instruction sequence above
        # is untouched.
        k = router.anti_entropy_k
        if k:
            factory.anti_entropy_step(k)
        return out

    # ------------------------------------------------------------------
    def stage_stats(self) -> dict:
        """Per-stage pipeline telemetry (``Router.walk_telemetry``'s
        ``pipeline`` block): mean per-wave stage costs in µs, wave and
        speculation counters, and the overlap fraction — the share of a
        consumed speculative walk's wall time that ran off the routing
        critical path (hidden / (hidden + blocked); an upper bound on
        true overlap, since a walk may finish early inside the hidden
        window)."""
        w = max(self.waves, 1)
        denom = self.spec_hidden_ns + self.spec_blocked_ns
        return {
            "waves": self.waves,
            "walk_us": self.walk_ns / w / 1e3,
            "score_us": self.score_ns / w / 1e3,
            "commit_us": self.commit_ns / w / 1e3,
            "prefetches": self.prefetches,
            "prefetch_hits": self.prefetch_hits,
            "overlap_fraction": (self.spec_hidden_ns / denom
                                 if denom else 0.0),
        }
