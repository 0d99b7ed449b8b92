#!/usr/bin/env python3
"""A cell's traced window with the program's own spans and counters.

    python3 bench/spans.py --workload route.fleet4096.coder --seconds 45 \
        --seeds 101 102 --obs 1 0

Runs the cell as ``bench/run.py --trace 1`` does, once per seed and
``--obs`` value, in one process.  With ``--obs 1`` the measured router or
cluster is built with ``repro.obs.make_obs(metrics=True,
trace="profiler")``: the program's ``repro.*`` spans land on the device
trace's clock, and its registry counters are read as deltas over the
profiler window.  ``--obs 0`` attaches nothing, as a benchmark run does;
the pair gives what the spans cost.

Prints one JSON line per run: ``correct`` and the checks, the cell's
per-layer metrics (its readers under ``bench/metrics/``), the program
metrics below, each ``repro.*`` span's seconds and count in the window,
the counters' window deltas, and the ten longest idle gaps, each named by
the innermost ``bench.*`` or ``repro.*`` span over it.

Program metrics, ``None`` where the program offers no profiler clock:

* ``fallback_share.route``: requests that the commit stage re-routed one
  at a time after a mid-wave eviction (``pipeline.fallback_requests``)
  over the requests routed in waves (the sum of the
  ``pipeline.wave_size`` samples), in %;
* ``fallback_us.route``: time in ``repro.commit.fallback`` per
  ``repro.wave``, in us;
* ``admit_ms.serve`` and ``fragment_save_ms.serve``: the mean
  ``repro.engine.admit`` and ``repro.engine.fragment_save``, in ms;
* ``fragment_copy_mb.serve``: fragment bytes copied device to host and
  back (``engine.fragment_d2h_bytes`` + ``engine.fragment_h2d_bytes``)
  per prefill step of the window, in MB;
* ``hit_prediction_share.serve``: prompt tokens the engines took from
  their stores (``engine.reused_tokens``) over the hits the router
  predicted for the same requests (``engine.predicted_hit_tokens``), in %.

Not part of a benchmark run; exits non-zero without a TPU.
"""
import contextlib
import functools
import gc
import glob
import json
import os
import shutil
import sys
from types import SimpleNamespace as NS

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import devtrace  # noqa: E402
import run as harness  # noqa: E402
from common import (BENCH, ROOT, config_for, find, load_module,  # noqa: E402
                    load_spec, peaks_for, traffic_for)

#: name prefix of the program's profiler-clock spans
PREFIX = "repro."


def reduce_planes(planes) -> dict:
    """``devtrace.reduce_planes`` over the benchmark's and the program's
    host spans: the idle gaps are named by the innermost ``bench.*`` or
    ``repro.*`` span over them, and ``spans`` maps each ``repro.*`` name
    to its [seconds, count]."""
    spans: dict = {}
    view = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            view.append(plane)
            continue
        events = []
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(devtrace.HOST_PREFIX):
                    events.append(e)
                elif e.name.startswith(PREFIX):
                    s = spans.setdefault(e.name, [0.0, 0])
                    s[0] += e.duration_ns * 1e-9
                    s[1] += 1
                    events.append(NS(name=devtrace.HOST_PREFIX + e.name,
                                     start_ns=e.start_ns,
                                     duration_ns=e.duration_ns))
        view.append(NS(name=plane.name, lines=[NS(name="spans",
                                                  events=events)]))
    out = devtrace.reduce_planes(view)
    out["spans"] = spans
    return out


def reduce(log_dir: str, window_s: float) -> dict:
    """Read the trace under ``log_dir``, reduce it, and delete it."""
    import jax
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    out = reduce_planes(jax.profiler.ProfileData.from_file(files[-1])
                        .planes)
    out["window_s"] = window_s
    shutil.rmtree(log_dir, ignore_errors=True)
    return out


def _counts(registry) -> dict:
    out = dict(registry.counters)
    h = registry.hists.get("pipeline.wave_size")
    out["pipeline.wave_size.sum"] = int(h.total) if h is not None else 0
    return out


class ObsWindow(devtrace.Window):
    """The profiler window; with a registry, also its counters' deltas
    from the window's start to its stop (``counts``)."""

    def __init__(self, log_dir: str, seconds: float, registry=None):
        super().__init__(log_dir, seconds)
        self.registry = registry
        self.counts: dict = {}
        self._before: dict = {}

    def start(self):
        if self.registry is not None:
            self._before = _counts(self.registry)
        super().start()

    def stop(self):
        if self.active:
            super().stop()
            if self.registry is not None:
                after = _counts(self.registry)
                self.counts = {k: v - self._before.get(k, 0)
                               for k, v in after.items()}


@contextlib.contextmanager
def attached(obs):
    """While open, the drivers' ``Router`` and ``EngineCluster`` are
    built with ``obs`` (both drivers import them at call time)."""
    if obs is None:
        yield
        return
    import repro.core
    import repro.serving.engine as engine
    saved = repro.core.Router, engine.EngineCluster
    repro.core.Router = functools.partial(saved[0], obs=obs)
    engine.EngineCluster = functools.partial(saved[1], obs=obs)
    try:
        yield
    finally:
        repro.core.Router, engine.EngineCluster = saved


def profiler_obs():
    """The program's bundle on the profiler clock, or ``None`` where the
    program has no such clock."""
    import repro.obs
    if "profiler" not in getattr(repro.obs, "TRACE_CLOCKS", ()):
        return None
    return repro.obs.make_obs(metrics=True, trace="profiler")


# ---- program metrics ------------------------------------------------------
def _mean_ms(ctx, name):
    s = ctx["trace"]["spans"].get(name)
    return 1e3 * s[0] / s[1] if s and s[1] else None


def fallback_share_route(ctx):
    c = ctx["counts"]
    if not c.get("pipeline.wave_size.sum"):
        return None
    return (100.0 * c.get("pipeline.fallback_requests", 0)
            / c["pipeline.wave_size.sum"])


def fallback_us_route(ctx):
    spans = ctx["trace"]["spans"]
    if "repro.wave" not in spans:
        return None
    seconds = spans.get("repro.commit.fallback", [0.0, 0])[0]
    return 1e6 * seconds / spans["repro.wave"][1]


def admit_ms_serve(ctx):
    return _mean_ms(ctx, "repro.engine.admit")


def fragment_save_ms_serve(ctx):
    return _mean_ms(ctx, "repro.engine.fragment_save")


def fragment_copy_mb_serve(ctx):
    c = ctx["counts"]
    prefills = sum(1 for s in ctx["steps"]
                   if s["kind"] == "prefill" and s["traced"])
    if not c or not prefills:
        return None
    return (c.get("engine.fragment_d2h_bytes", 0)
            + c.get("engine.fragment_h2d_bytes", 0)) / prefills / 1e6


def hit_prediction_share_serve(ctx):
    c = ctx["counts"]
    if not c.get("engine.predicted_hit_tokens"):
        return None
    return (100.0 * c.get("engine.reused_tokens", 0)
            / c["engine.predicted_hit_tokens"])


PROGRAM = {
    "route": {"fallback_share.route": fallback_share_route,
              "fallback_us.route": fallback_us_route},
    "serve": {"admit_ms.serve": admit_ms_serve,
              "fragment_save_ms.serve": fragment_save_ms_serve,
              "fragment_copy_mb.serve": fragment_copy_mb_serve,
              "hit_prediction_share.serve": hit_prediction_share_serve},
}


def run_traced(spec: dict, cell: dict, config: dict, mix: dict, seed: int,
               seconds: float, devices, peaks: dict, obs: bool = True):
    """One traced run of ``cell``, with the program's profiler-clock
    bundle attached where ``obs`` and the program has one; returns (the
    result line, the readers' context)."""
    bundle = profiler_obs() if obs else None
    window = ObsWindow(str(ROOT / ".bench_trace" / f"spans.{cell['name']}"),
                       float(mix["trace_seconds"]),
                       bundle.registry if bundle is not None else None)
    env = harness.Env(devices[:cell["chips"]], window, harness.T_START)
    driver = load_module(BENCH / f"{mix['driver']}.py", mix["driver"])
    with attached(bundle):
        res = driver.run(config, mix, seed, seconds, env)
    gc.collect()
    summary = reduce(window.log_dir, window.window_s)
    ctx = dict(res["ctx"], trace=summary, peaks=peaks, config=config,
               mix=mix, counts=window.counts)
    checks = {k: {"value": v, "limit": lim}
              for k, (v, lim) in res["checks"].items()}
    line = {
        "seed": seed, "obs": window.registry is not None,
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "metrics": {k: v["value"] for k, v in
                    harness._per_layer(spec, cell["name"], ctx).items()},
        "program": {k: f(ctx) for k, f in PROGRAM[mix["driver"]].items()},
        "spans": summary["spans"], "counts": window.counts,
        "gaps": summary["gaps"],
        "device": {"busy_s": summary["busy_s"],
                   "window_s": summary["window_s"],
                   "memory_peak_bytes": res["memory_peak_bytes"]},
        "checks": checks, "info": res["info"]}
    return line, ctx


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--obs", type=int, nargs="+", choices=(0, 1),
                    default=[1])
    args = ap.parse_args()
    spec = load_spec()
    cell = find(spec["workloads"], args.workload)
    config = config_for(spec, cell["config"])
    mix = traffic_for(cell["traffic"])
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("spans: JAX found no TPU; nothing run", file=sys.stderr)
        return 1
    peaks = peaks_for(devs[0].device_kind)
    for seed in args.seeds:
        for obs in args.obs:
            line, _ = run_traced(spec, cell, config, mix, seed, args.seconds,
                                 devs, peaks, obs=bool(obs))
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
