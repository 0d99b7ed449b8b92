"""Observability tests (``repro.obs``): four contracts.

1. **Registry semantics** — ring-buffer histograms keep exact
   count/sum/max past capacity, snapshots are sorted/JSON-able, merges
   are deterministic (counters sum, gauges max), and the fixed-slot
   worker block folds into per-shard scoped counters idempotently.
2. **Trace round-trip** — spans/instants emit Chrome trace-event
   JSON that survives export → parse → validation (balanced B/E
   nesting, monotonic virtual timestamps, pid/tid mapping with named
   process tracks), is byte-identical across identical runs, and
   respects the every-Nth-wave sampling knob; on the profiler clock the
   spans land, nested and with their args, in a ``jax.profiler`` trace,
   every wave, and the mid-wave eviction fallback is spanned and
   counted exactly.
3. **Contract 5 (disabled-mode identity)** — with ``obs=None`` (the
   default) AND with a fully-enabled bundle, ``route_batch`` decisions
   stay bit-identical to the frozen scalar reference across
   serial/thread/process walk backends: observability may never change
   a routing decision.
4. **Overhead budget** — the fully-enabled bundle (metrics + default-
   sampling trace + provenance) costs ≤5% wall time on a closed-loop
   mixed workload (best-of-k ratio; the bench records the same number).
"""
import collections
import copy
import json
import time

import numpy as np
import pytest

from repro.cluster.closed_loop import ClosedLoopSim
from repro.configs import get_config
from repro.core import (LatencyModel, Router, make_policy,
                        spec_from_config)
from repro.core.scalar_ref import make_scalar_policy
from repro.obs import make_obs
from repro.obs.registry import (N_WORKER_SLOTS, WORKER_SLOTS, Histogram,
                                MetricsRegistry, merge_snapshots)
from repro.obs.trace import (ROUTER_PID, SpanTracer, load_trace,
                             validate_events)
from repro.workloads.sessions import make_mixed_sessions
from repro.workloads.traces import make_hotspot_trace

N_INST = 16


# ---------------------------------------------------------------------------
# 1. registry
# ---------------------------------------------------------------------------
def test_histogram_ring_wraps_with_exact_totals():
    h = Histogram(capacity=8)
    xs = [float(i) for i in range(20)]
    for x in xs:
        h.record(x)
    assert h.count == 20
    assert h.total == sum(xs)
    assert h.max == 19.0
    # percentile window is the retained ring (the last 8 samples)
    assert list(h.samples()) == xs[-8:]
    st = h.stats()
    assert st["count"] == 20 and st["p50"] == pytest.approx(15.5)


def test_snapshot_sorted_and_merge_deterministic():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.inc("z.count", 2)
    a.inc("a.count", 1)
    a.gauge("depth", 3.0)
    a.observe("lat", 1.0)
    a.observe("lat", 3.0)
    b.inc("z.count", 5)
    b.gauge("depth", 2.0)
    b.observe("lat", 7.0)
    sa, sb = a.snapshot(), b.snapshot()
    assert list(sa["counters"]) == sorted(sa["counters"])
    json.dumps(sa)  # JSON-able, no numpy leaks
    m = merge_snapshots([sa, sb])
    assert m["counters"]["z.count"] == 7
    assert m["counters"]["a.count"] == 1
    assert m["gauges"]["depth"] == 3.0
    assert m["hists"]["lat"]["count"] == 3
    assert m["hists"]["lat"]["sum"] == pytest.approx(11.0)
    assert m["hists"]["lat"]["max"] == 7.0
    # deterministic: same inputs, same merged view
    assert m == merge_snapshots([sa, sb])


def test_worker_block_ingest_idempotent():
    reg = MetricsRegistry()
    block = np.arange(2 * N_WORKER_SLOTS,
                      dtype=np.int64).reshape(2, N_WORKER_SLOTS)
    reg.ingest_worker_block(block)
    reg.ingest_worker_block(block)  # counter_set: no double counting
    snap = reg.snapshot()["counters"]
    for j, slot in enumerate(WORKER_SLOTS):
        assert snap[f"shard.0.{slot}"] == block[0, j]
        assert snap[f"shard.1.{slot}"] == block[1, j]
        assert snap[f"shard.{slot}"] == int(block[:, j].sum())


# ---------------------------------------------------------------------------
# 2. trace round-trip
# ---------------------------------------------------------------------------
def _emit_demo(tracer):
    tracer.set_time(1.0)
    tracer.wave_tick()
    with tracer.span("wave", args={"k": 3}):
        with tracer.span("walk"):
            pass
        with tracer.span("score"):
            tracer.instant("spec.submit", args={"k": 2})
        with tracer.span("commit"):
            pass
    tracer.set_time(2.0)
    tracer.instant("churn.fail", args={"iid": 3})


def test_trace_round_trip_schema(tmp_path):
    tr = SpanTracer(sample_every=1)
    _emit_demo(tr)
    path = str(tmp_path / "trace.json")
    tr.export(path)
    events = load_trace(path)  # parses + validates
    # span nesting: wave > walk/score/commit on the router track
    names = [(e["ph"], e["name"]) for e in events
             if e["pid"] == ROUTER_PID and e["ph"] in ("B", "E")]
    assert names == [("B", "wave"), ("B", "walk"), ("E", "walk"),
                     ("B", "score"), ("E", "score"), ("B", "commit"),
                     ("E", "commit"), ("E", "wave")]
    # pid/tid mapping: one named router track carries every event
    meta = {e["pid"]: e["args"]["name"] for e in events
            if e["ph"] == "M" and e["name"] == "process_name"}
    assert meta == {ROUTER_PID: "router"}
    instants = [(e["pid"], e["name"]) for e in events if e["ph"] == "i"]
    assert instants == [(ROUTER_PID, "spec.submit"),
                        (ROUTER_PID, "churn.fail")]
    # virtual clock: timestamps are monotonic and follow set_time
    ts = [e["ts"] for e in events if e["ph"] != "M"]
    assert ts == sorted(ts) and ts[0] >= 1_000_000


def test_trace_validation_rejects_bad_nesting():
    base = {"ts": 1, "pid": 0, "tid": 0}
    meta = {"name": "process_name", "ph": "M", "ts": 0, "pid": 0,
            "tid": 0, "args": {"name": "router"}}
    with pytest.raises(ValueError, match="bad nesting"):
        validate_events([meta,
                         dict(base, name="a", ph="B"),
                         dict(base, name="b", ph="E", ts=2)])
    with pytest.raises(ValueError, match="unclosed"):
        validate_events([meta, dict(base, name="a", ph="B")])
    with pytest.raises(ValueError, match="no process_name"):
        validate_events([dict(base, name="a", ph="i", pid=9)])


def test_sampling_knob_bounds_span_volume():
    tr = SpanTracer(sample_every=4)
    for _ in range(8):
        tr.wave_tick()
        with tr.span("wave"):
            pass
        tr.instant("drop")  # instants ignore sampling
    waves = [e for e in tr.events if e["name"] == "wave"]
    drops = [e for e in tr.events if e["name"] == "drop"]
    assert len(waves) == 2 * 2   # waves 0 and 4, B+E each
    assert len(drops) == 8


def profiled_spans(log_dir, fn):
    """Run ``fn`` under a ``jax.profiler`` trace written to ``log_dir``;
    return its ``repro.*`` host events as (name, start_ns, end_ns,
    args) in start order."""
    import glob
    import jax
    jax.profiler.start_trace(str(log_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    out = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
           for plane in data.planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith("repro.")]
    return sorted(out, key=lambda e: e[1])


def inside(spans, child, parent):
    """Every ``child`` span lies within some ``parent`` span."""
    outer = [(lo, hi) for name, lo, hi, _ in spans if name == parent]
    kids = [(lo, hi) for name, lo, hi, _ in spans if name == child]
    return bool(kids) and all(any(a <= lo and hi <= b for a, b in outer)
                              for lo, hi in kids)


def test_profiler_clock_spans_and_instants(tmp_path):
    """The profiler clock records every wave (no sampling) as
    ``repro.*`` annotations with the span's args, instants as zero-length
    ones, keeps no events of its own and refuses to export."""
    obs = make_obs(metrics=False, trace="profiler", sample_every=4)
    tr = obs.tracer

    def emit():
        for w in range(3):
            assert tr.wave_tick()
            with tr.span("wave", args={"wave": w, "k": 2}):
                with tr.span("walk"):
                    pass
            tr.instant("drop", args={"rid": w})

    spans = profiled_spans(tmp_path / "p", emit)
    waves = [a for name, _, _, a in spans if name == "repro.wave"]
    assert waves == [{"wave": w, "k": 2} for w in range(3)]
    assert inside(spans, "repro.walk", "repro.wave")
    assert [a for name, _, _, a in spans if name == "repro.drop"] == \
        [{"rid": w} for w in range(3)]
    assert tr.events == []
    with pytest.raises(RuntimeError, match="profiler"):
        tr.export(str(tmp_path / "t.json"))
    assert not (tmp_path / "t.json").exists()
    with pytest.raises(ValueError, match="trace"):
        make_obs(trace="wall")


# ---------------------------------------------------------------------------
# 3. disabled-mode bit-identity across backends (Contract 5)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def trace():
    reqs = make_hotspot_trace(qps=12.0, duration=80.0, seed=5,
                              burst_start=20.0, burst_len=40.0)
    assert len(reqs) >= 800
    return reqs[:800]


def _drive(router, reqs, batch=8, use_batch=True):
    """Route in waves with a deterministic drain schedule (the
    ``test_batch_routing`` idiom, compressed).  The frozen scalar
    reference only speaks sequential ``route``, so it drives with
    ``use_batch=False`` and the identical per-wave ``now``."""
    decisions = []
    outstanding = collections.deque()
    reqs = copy.deepcopy(reqs)
    for i in range(0, len(reqs), batch):
        wave = reqs[i:i + batch]
        now = wave[0].arrival
        if use_batch:
            iids = router.route_batch(wave, now)
        else:
            iids = [router.route(r, now) for r in wave]
        decisions.extend(iids)
        for r, iid in zip(wave, iids):
            outstanding.append((iid, r, r.new_tokens))
            router.factory[iid].on_prefill_progress(256)
        for _ in range(len(wave)):
            if len(outstanding) > 2:
                did, dreq, dnew = outstanding.popleft()
                di = router.factory[did]
                di.on_prefill_progress(dnew)
                di.on_start_running(dreq)
                for _ in range(dreq.output_len % 7):
                    di.on_decode_token()
                di.on_finish(dreq)
    return decisions


def _decisions(trace, obs=None, walk_backend=None, n_shards=1,
               maker=make_policy):
    router = Router(maker("lmetric"), N_INST,
                    kv_capacity_tokens=150_000, n_shards=n_shards,
                    walk_backend=walk_backend, obs=obs)
    try:
        return _drive(router, trace,
                      use_batch=maker is not make_scalar_policy)
    finally:
        router.close()


def test_obs_identity_vs_scalar_ref(trace):
    """Disabled AND fully-enabled obs match the frozen scalar reference
    on serial and thread backends."""
    ref = _decisions(trace, maker=make_scalar_policy)
    assert _decisions(trace) == ref
    for backend, shards in ((None, 1), (None, 4), ("thread", 4)):
        obs = make_obs(metrics=True, trace=True, provenance=True,
                       sample_every=2)
        got = _decisions(trace, obs=obs, walk_backend=backend,
                         n_shards=shards)
        assert got == ref, f"obs changed decisions ({backend}, {shards})"
        assert obs.registry.counters["provenance.records"] == len(ref)
        validate_events(obs.tracer.to_json()["traceEvents"])


@pytest.mark.process
def test_obs_identity_process_backend(trace):
    ref = _decisions(trace, maker=make_scalar_policy)
    obs = make_obs(metrics=True, trace=True, provenance=True)
    got = _decisions(trace, obs=obs, walk_backend="process", n_shards=4)
    assert got == ref
    # the shard workers' fixed-slot block made it into the snapshot
    snap = obs.registry.snapshot()["counters"]
    assert "provenance.records" in snap


def test_trace_byte_identical_across_runs(trace):
    """Determinism contract: two identical runs emit byte-identical
    trace JSON (virtual clock + lamport ticks, no wall time)."""
    sub = trace[:200]
    docs = []
    for _ in range(2):
        obs = make_obs(trace=True, sample_every=2)
        _decisions(sub, obs=obs, n_shards=2)
        docs.append(json.dumps(obs.tracer.to_json(), sort_keys=True))
    assert docs[0] == docs[1]


def _fallback_run(reqs, obs, monkeypatch):
    """Route ``reqs`` on a fleet whose caches evict, counting from
    outside every request the commit stage hands to the sequential
    re-route; returns (decisions, fallback requests, fallback waves)."""
    import repro.core.router as router_mod
    orig = router_mod.commit_wave_plan
    seen = [0, 0]

    def counted(factory, wave, commit, fallback):
        def tail(rest):
            seen[0] += len(rest)
            seen[1] += 1
            return fallback(rest)
        return orig(factory, wave, commit, tail)

    monkeypatch.setattr(router_mod, "commit_wave_plan", counted)
    router = Router(make_policy("lmetric"), N_INST,
                    kv_capacity_tokens=20_000, obs=obs)
    try:
        return _drive(router, reqs), seen[0], seen[1]
    finally:
        router.close()
        monkeypatch.setattr(router_mod, "commit_wave_plan", orig)


def test_fallback_counted_and_spanned_on_profiler_clock(trace, tmp_path,
                                                        monkeypatch):
    """With caches that evict mid-wave, the router with a profiler-clock
    bundle decides exactly as with none; the registry's fallback
    counters equal an outside count of the re-routed tail, and each
    fallback is one ``repro.commit.fallback`` span inside a commit,
    carrying the tail's length."""
    base, n_base, _ = _fallback_run(trace, None, monkeypatch)
    assert n_base > 0, "the fleet's caches never evicted mid-wave"
    obs = make_obs(metrics=True, trace="profiler")
    got = []
    spans = profiled_spans(tmp_path / "p", lambda: got.append(
        _fallback_run(trace, obs, monkeypatch)))
    decisions, n_req, n_waves = got[0]
    assert decisions == base
    c = obs.registry.counters
    assert c["pipeline.fallback_requests"] == n_req == n_base
    assert c["pipeline.fallback_waves"] == n_waves
    ks = [a["k"] for name, _, _, a in spans
          if name == "repro.commit.fallback"]
    assert len(ks) == n_waves and sum(ks) == n_req
    assert inside(spans, "repro.commit.fallback", "repro.commit")
    for stage in ("walk", "score", "commit"):
        assert inside(spans, f"repro.{stage}", "repro.wave")
    for part in ("submit", "collect"):
        assert inside(spans, f"repro.score.{part}", "repro.score")
    waves = [a for name, _, _, a in spans if name == "repro.wave"]
    assert [w["wave"] for w in waves] == list(range(len(waves)))


# ---------------------------------------------------------------------------
# 4. enabled-mode overhead budget + compat shims
# ---------------------------------------------------------------------------
def _closed_loop_wall(spec, obs):
    sessions = make_mixed_sessions(
        {"chatbot": 30, "agent": 15, "coder": 15}, seed=5)
    router = Router(make_policy("lmetric"), N_INST,
                    kv_capacity_tokens=150_000, obs=obs)
    sim = ClosedLoopSim(router, spec, LatencyModel(spec))
    t0 = time.perf_counter_ns()
    done = sim.run_sessions(sessions)
    wall = time.perf_counter_ns() - t0
    return wall, [r.sched_to for r in done], sim


@pytest.mark.slow
def test_enabled_overhead_within_budget():
    """Full obs (metrics + default-sampling trace + provenance) costs
    ≤5% closed-loop wall time, best-of-5 interleaved per mode (min is
    the noise-robust statistic), and changes no decision."""
    spec = spec_from_config(get_config("qwen2_7b"), chips=1)
    base, enabled = [], []
    decisions = {}
    for _ in range(5):
        w, d, _ = _closed_loop_wall(spec, None)
        base.append(w)
        decisions.setdefault("off", d)
        w, d, _ = _closed_loop_wall(
            spec, make_obs(metrics=True, trace=True, provenance=True))
        enabled.append(w)
        decisions.setdefault("on", d)
    assert decisions["on"] == decisions["off"]
    ratio = min(enabled) / min(base)
    assert ratio <= 1.05, f"enabled-mode overhead {ratio:.3f}x > 1.05x"


def test_metrics_snapshot_mirrors_legacy_telemetry(trace):
    """The registry re-homes the ad-hoc accumulators exactly: snapshot
    counters equal ``walk_telemetry``/``stage_stats`` sources, and
    repeated snapshots never double-count (counter_set ingestion)."""
    router = Router(make_policy("lmetric"), N_INST,
                    kv_capacity_tokens=150_000, n_shards=2)
    try:
        _drive(router, trace[:200])
        snap = router.metrics_snapshot()["counters"]
        again = router.metrics_snapshot()["counters"]
        assert snap == again
        f = router.factory
        assert snap["index.walks"] == f.walks
        assert snap["index.walk_ns"] == f.walk_ns
        assert snap["pipeline.waves"] == router.pipeline.waves
        assert snap["router.routed"] == router.routed
        # fixed-slot worker block: per-shard rows + totals present and
        # consistent with the legacy pair the backend always kept
        assert snap["shard.walks"] == sum(
            snap[f"shard.{s}.walks"] for s in range(2))
    finally:
        router.close()


@pytest.mark.parametrize("capacity", [2_000, 1 << 62])
def test_metrics_snapshot_counts_eviction_runs(trace, capacity):
    """``index.evict_runs`` counts ``on_evict`` calls, one per run of
    leaves evicted up a chain tail, beside ``index.evictions``, which
    counts the leaves: a capacity-bound router evicts in runs, an
    unbounded one never evicts."""
    router = Router(make_policy("lmetric"), N_INST,
                    kv_capacity_tokens=capacity)
    try:
        _drive(router, trace[:200])
        snap = router.metrics_snapshot()["counters"]
        f = router.factory
        assert snap["index.evictions"] == f.evictions
        assert snap["index.evict_runs"] == f.evict_runs
        if capacity < 1 << 62:
            assert snap["index.evictions"] >= snap["index.evict_runs"] > 0
        else:
            assert snap["index.evictions"] == snap["index.evict_runs"] == 0
    finally:
        router.close()


@pytest.mark.hetero
def test_provenance_round_trip_hetero_fields(trace):
    """On a heterogeneous fleet every provenance record carries the
    chosen instance's model/hardware-class codes, the request's
    requirement, and per-candidate normalized indicators — enough to
    replay the hetero argmin by hand — and the records survive a JSON
    round-trip."""
    from repro.cluster.simulator import make_mixed_fleet
    fleet = make_mixed_fleet()
    obs = make_obs(metrics=True, provenance=True)
    router = Router(make_policy("lmetric"), 16,
                    kv_capacity_tokens=150_000, fleet=fleet, obs=obs)
    sub = copy.deepcopy(trace[:200])
    for i, r in enumerate(sub):
        if i % 4 == 0:
            r.model_requirement = "qwen2_7b"
    try:
        _drive(router, sub)
    finally:
        router.close()
    recs = json.loads(json.dumps(obs.provenance.records))
    assert len(recs) == len(sub)
    by_rid = {r.rid: r for r in sub}
    for rec in recs:
        iid = rec["chosen"]
        assert rec["chosen_model_id"] == int(fleet.model_codes[iid])
        assert rec["chosen_hardware_class"] == \
            int(fleet.class_codes[iid])
        want = by_rid[rec["rid"]].model_requirement
        assert rec["model_requirement"] == want
        if want:   # the capability mask held, and the record proves it
            assert fleet.model_of(iid) == want
        assert rec["top_k"], "hetero records keep the landscape"
        for e in rec["top_k"]:
            assert e["model_id"] == int(fleet.model_codes[e["iid"]])
            assert e["hardware_class"] == int(fleet.class_codes[e["iid"]])
            assert e["norm"] == float(fleet.prefill_norm[e["iid"]])
    assert obs.registry.counters["provenance.records"] == len(sub)


@pytest.mark.hetero
def test_obs_identity_on_mixed_fleet_scenario():
    """Contract 5 under heterogeneity: a fully-enabled obs bundle must
    not change a single decision of the mixed-fleet closed-loop
    scenario vs the disabled default."""
    from repro.cluster.simulator import make_mixed_fleet
    from repro.workloads.sessions import make_mixed_fleet_sessions
    spec = spec_from_config(get_config("qwen3_30b_moe"), chips=1)

    def fates(obs):
        fleet = make_mixed_fleet()
        sessions = make_mixed_fleet_sessions(
            {"chatbot": 20, "coder": 10, "agent": 10}, seed=9)
        router = Router(make_policy("lmetric"), fleet.n,
                        kv_capacity_tokens=150_000, fleet=fleet, obs=obs)
        sim = ClosedLoopSim(router, spec, LatencyModel(spec))
        try:
            done = sim.run_sessions(sessions)
            return [(r.rid, r.sched_to, r.hit_tokens,
                     round(r.t_finish, 9)) for r in done]
        finally:
            router.close()

    base = fates(None)
    assert base, "scenario produced no completions"
    full = fates(make_obs(metrics=True, trace=True, provenance=True,
                          sample_every=2))
    assert full == base


def test_provenance_failure_detector():
    """Affinity capture fires iff the chosen instance's load exceeds
    alpha x the live median while a lighter candidate exists."""
    from repro.obs.provenance import ProvenanceRecorder
    reg = MetricsRegistry()
    p = ProvenanceRecorder(registry=reg, alpha=2.0)
    bs = np.array([1, 1, 1, 9], dtype=np.int64)
    live = np.arange(4)
    assert p._failure_condition(3, bs, None, live) is True
    assert p._failure_condition(0, bs, None, live) is False
    # degenerate fleets never flag
    assert p._failure_condition(0, bs[:1], None, live[:1]) is False
    assert p.failure_conditions == 1
