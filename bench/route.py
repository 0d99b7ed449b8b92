"""Routing cells: one ``Router`` in front of a fleet, driven wave by wave.

Set-up generates the mix's trace, compiles the wave program on a
throwaway router, then drives the measured router through the mix's
first ``warm_requests`` arrivals, past the point where the instances'
caches fill, so the window starts in the fleet's steady state.  The
window routes the arrivals that follow in waves of ``wave`` consecutive
arrivals, back to back, for ``--seconds`` (it starts no wave past them):
before each wave the service model's completions that are due by the
wave's trace time go through the router's hooks, then ``route_batch``
routes the wave at that time.

End-to-end: ``decisions_per_s`` (window requests over the window's wall
seconds, hooks included) and ``wave_p99_ms`` (p99 of each
``route_batch`` call's wall time).  Afterwards the router is freed and
the plain reference follows the run's decisions (``reference.route_ref``).
"""
from __future__ import annotations

import copy
import time

import numpy as np

import devtrace as tr
from common import pct
from generate import ServiceModel, route_trace
from reference import route_ref


def _make_router(config: dict, mix: dict):
    from repro.core import Router, make_policy
    return Router(make_policy(config["policy"]), config["n_instances"],
                  kv_capacity_tokens=config["kv_capacity_tokens"],
                  block_size=mix["block_tokens"])


def _drive(router, reqs, lo, hi, wave, svc, decisions, hits, walls,
           traced=False, window=None, deadline=None) -> int:
    """Route ``reqs[lo:hi]`` wave by wave through ``router``, starting no
    wave at or past ``deadline`` (``time.perf_counter()``); returns the
    end of what was routed."""
    for a in range(lo, hi, wave):
        if deadline is not None and time.perf_counter() >= deadline:
            return a
        w = reqs[a:a + wave]
        now = w[-1].arrival
        with tr.annotate(traced, "hooks"):
            for kind, iid, j in svc.due(now):
                if kind == "first":
                    router.on_prefill_progress(iid, reqs[j].prompt_len
                                               - int(hits[j]))
                    router.on_start_running(iid, reqs[j])
                else:
                    router.on_finish(iid, reqs[j])
        with tr.annotate(traced, "route_batch"):
            t0 = time.perf_counter()
            iids = router.route_batch(w, now)
            walls.append(time.perf_counter() - t0)
        for j, (r, iid) in enumerate(zip(w, iids), a):
            decisions[j] = iid
            hits[j] = r.hit_tokens
            svc.push(now, iid, j, r.prompt_len - r.hit_tokens, r.output_len)
        if window is not None:
            window.poll()
    return hi


def run(config: dict, mix: dict, seed: int, seconds: float, env,
        controls=()) -> dict:
    """One run of a routing cell; ``controls`` (names in
    ``route_ref.CONTROLS``) also reads each control at the sampled
    requests (``bench/readings.py`` does, a benchmark run does not)."""
    window, compiles = env.window, env.compiles
    n = config["n_instances"]
    wave = int(mix["wave"])
    n_warm = int(mix["warm_requests"])
    # the trace holds more than the window can route: a wave is 64
    # consecutive arrivals, and the window starts none past its seconds
    n_max = max(1, round(seconds * mix["window_max_requests_per_second"]
                         / wave)) * wave
    reqs = route_trace(mix, seed, n_warm + n_max)

    # compile the wave program (one full wave) on a throwaway router
    warm = _make_router(config, mix)
    warm.route_batch(copy.deepcopy(reqs[:wave]), reqs[wave - 1].arrival)
    warm.close()
    del warm

    router = _make_router(config, mix)
    svc = ServiceModel(mix, n)
    decisions = np.full(len(reqs), -1, np.int64)
    hits = np.zeros(len(reqs), np.int64)
    _drive(router, reqs, 0, n_warm, wave, svc, decisions, hits, [])
    pipe = router.pipeline
    before = (pipe.walk_ns, pipe.score_ns, pipe.commit_ns, pipe.waves)
    setup_s = time.perf_counter() - env.t_start

    walls: list = []
    c0 = compiles()
    if window is not None:
        window.start()
    t0 = time.perf_counter()
    end = _drive(router, reqs, n_warm, n_warm + n_max, wave, svc, decisions,
                 hits, walls, traced=window is not None, window=window,
                 deadline=t0 + seconds)
    window_s = time.perf_counter() - t0
    if end == n_warm + n_max:
        raise RuntimeError("the window routed every request of its trace: "
                           "raise the mix's window_max_requests_per_second")
    n_win = end - n_warm
    del reqs[end:]
    if window is not None:
        window.stop()
    in_window = compiles() - c0
    memory_peak = env.memory_peak()
    after = (pipe.walk_ns, pipe.score_ns, pipe.commit_ns, pipe.waves)
    stage = dict(zip(("walk_ns", "score_ns", "commit_ns", "waves"),
                     (b - a for a, b in zip(before, after))))
    evictions = int(router.factory.evictions)
    now = reqs[n_warm + n_win - 1].arrival
    running = svc.pending() / 2 / n
    backlog = float(np.maximum(svc.free_at - now, 0.0).mean())
    win_hits = hits[n_warm:].sum() / sum(r.prompt_len for r in reqs[n_warm:])
    router.close()
    del router, pipe

    t_ref = time.perf_counter()
    samples = route_ref.sample_indices(seed, n_warm, n_warm + n_win,
                                       int(mix["check_samples"]))
    ref = route_ref.replay(reqs, decisions[:end], hits[:end], n,
                           mix["block_tokens"],
                           config["kv_capacity_tokens"], ServiceModel(mix, n),
                           wave, samples, controls)
    ref_s = time.perf_counter() - t_ref
    unrouted = int((decisions[n_warm:end] < 0).sum())
    # every check passes when its value is at most its limit
    checks = {
        "decision_mismatches": (ref["mismatches"], 0),
        "hit_mismatches": (ref["hit_mismatches"], 0),
        "invalid_decisions": (ref["bad_decisions"] + unrouted, 0),
        "unchecked_samples": (len(samples) - ref["checked"], 0),
        "compiles_in_window": (in_window, 0),
    }
    info = (f"window {window_s:.3f} s wall, {n_win} requests in "
            f"{len(walls)} waves after {n_warm} warm-up requests; "
            f"{evictions} evictions in all; at the close {running:.2f} "
            f"requests per instance in service, prefill backlog "
            f"{backlog:.2f} s per instance; window hit share "
            f"{win_hits:.4f}; reference {ref_s:.1f} s, "
            f"largest best score {ref['max_best_score']:.0f}")
    return {
        "setup_s": setup_s,
        "e2e": {"decisions_per_s": n_win / window_s,
                "wave_p99_ms": pct(walls, 99) * 1e3},
        "ctx": {"stage": stage, "walls": walls, "window_s": window_s,
                "n": n, "wave": wave},
        "attempted": n_win, "failed": unrouted,
        "memory_peak_bytes": memory_peak,
        "checks": checks, "info": info, "controls": ref["controls"],
    }
