#!/usr/bin/env python3
"""On-chip benchmark of the LMetric router and its served cluster.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on.  The
cell names a configuration (``bench/configs/<name>.json``, found through
the spec's ``configs``) and a traffic mix (``bench/traffic/<name>.json``);
the mix's ``driver`` picks ``bench/<driver>.py``, which sets up, measures
for about ``--seconds`` and checks the window's outputs against the plain
reference under ``bench/reference/``.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs
the same window under a profiler (its first ``trace_seconds``) and
reports the per-layer metrics, each read by ``bench/metrics/<name>.py``.

Exits non-zero, printing no result, unless JAX's first device is a TPU
and there are as many as the cell's ``chips``.  The last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, ``breakdown`` with ``--trace 1``, and
``checks`` last: each number compared with its limit, a check passing
when the number is at most its limit); the checks are also the last
lines of standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
# the TPU runtime would log under /tmp, a path shared between runs
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from common import (BENCH, ROOT, config_for, find, load_module,  # noqa: E402
                    load_spec, peaks_for, traffic_for)

#: the event JAX records once per XLA backend compile
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Env:
    """What a driver needs from the harness."""

    def __init__(self, devices, window, t_start):
        import jax
        self.devices = devices
        self.window = window
        self.t_start = t_start
        self._compiles = 0

        def listener(event, duration, **kw):
            if event == BACKEND_COMPILE_EVENT:
                self._compiles += 1

        jax.monitoring.register_event_duration_secs_listener(listener)

    def compiles(self) -> int:
        return self._compiles

    def memory_peak(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        return int(max(peaks))


def _per_layer(spec: dict, cell: str, ctx: dict) -> dict:
    out = {}
    for m in spec["per_layer"]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             "metric_" + m["name"].replace(".", "_"))
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _end_to_end(spec: dict, cell: str, values: dict) -> dict:
    out = {}
    for m in spec["end_to_end"]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        out[m["name"]] = {"value": float(values[m["name"]]),
                          "unit": m["unit"]}
    return out


def run_cell(spec: dict, cell: dict, config: dict, mix: dict, seed: int,
             seconds: float, trace: bool, devices, peaks: dict) -> dict:
    """Run one cell on ``devices`` and return its result line (the
    harness's look for a chip is in ``main``)."""
    window = None
    if trace:
        from devtrace import Window
        window = Window(str(ROOT / ".bench_trace" / cell["name"]),
                        float(mix["trace_seconds"]))
    env = Env(devices[:cell["chips"]], window, T_START)
    driver = load_module(BENCH / f"{mix['driver']}.py", mix["driver"])
    res = driver.run(config, mix, seed, seconds, env)
    gc.collect()

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    checks = {k: {"value": v, "limit": lim}
              for k, (v, lim) in res["checks"].items()}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": res["attempted"], "failed": res["failed"]}
    if trace:
        from devtrace import reduce
        summary = reduce(window.log_dir, window.window_s)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        ctx = dict(res["ctx"], trace=summary, peaks=peaks, config=config,
                   mix=mix)
        out["metrics"] = _per_layer(spec, cell["name"], ctx)
        out["device"] = device
        out["breakdown"] = {"device_ops": summary["ops"],
                            "idle_gaps": summary["gaps"]}
    else:
        values = dict(res["e2e"], setup_s=res["setup_s"])
        out["metrics"] = _end_to_end(spec, cell["name"], values)
        out["device"] = device
    out["checks"] = checks
    out["info"] = res["info"]
    out["setup_s"] = res["setup_s"]
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the system under test (src/repro) is not here",
              file=sys.stderr)
        return 2
    spec = load_spec()
    cell = find(spec["workloads"], args.workload)
    config = config_for(spec, cell["config"])
    mix = traffic_for(cell["traffic"])
    sys.path.insert(0, str(ROOT / "src"))

    from repro.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: JAX found no TPU ({devs[0].platform}); nothing run",
              file=sys.stderr)
        return 1
    if len(devs) < cell["chips"]:
        print(f"bench: {cell['name']} needs {cell['chips']} chips, JAX "
              f"found {len(devs)}", file=sys.stderr)
        return 1
    out = run_cell(spec, cell, config, mix, args.seed, args.seconds,
                   bool(args.trace), devs, peaks_for(devs[0].device_kind))
    info, setup_s = out.pop("info"), out.pop("setup_s")
    print(f"bench {cell['name']} seed {args.seed}: {info}; setup "
          f"{setup_s:.3f} s; compile cache {cache_dir}", flush=True)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
