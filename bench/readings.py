#!/usr/bin/env python3
"""Readings that set a cell's correctness limits: on several seeds in one
process, the program's own compared numbers and its controls' (the plain
reference with one step changed: for the routing cells ``float32`` scores
and the ``first_min`` tie rule, for the served cells ``fp8`` weights).

    python3 bench/readings.py --workload route.fleet4096.coder \
        --seconds 10 --seeds 101 102 103

Prints one JSON line per seed.  A limit lies above every program reading
and below every control reading (PERF.md gives them).  Not part of a
benchmark run; exits non-zero without a TPU.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as harness  # noqa: E402
from common import (BENCH, ROOT, config_for, find, load_module,  # noqa: E402
                    load_spec, traffic_for)

CONTROLS = {"route": {"controls": ("float32", "first_min")},
            "serve": {"control": "fp8"}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    spec = load_spec()
    cell = find(spec["workloads"], args.workload)
    config = config_for(spec, cell["config"])
    mix = traffic_for(cell["traffic"])
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("readings: JAX found no TPU; nothing run", file=sys.stderr)
        return 1
    driver = load_module(BENCH / f"{mix['driver']}.py", mix["driver"])
    for seed in args.seeds:
        env = harness.Env(devs[:cell["chips"]], None, harness.T_START)
        res = driver.run(config, mix, seed, args.seconds, env,
                         **CONTROLS[mix["driver"]])
        print(json.dumps({"seed": seed, "program": {
            k: v for k, (v, _) in res["checks"].items()},
            "controls": res["controls"], "info": res["info"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
