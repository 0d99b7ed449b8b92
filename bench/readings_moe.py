#!/usr/bin/env python3
"""``bench/readings.py`` for the MoE served cells: on several seeds in one
process, the program's compared numbers, then each control's: the cell
served with a broken expert layer in the program's place
(``serve_moe.CONTROLS``: fp8 expert weights, top-7 routing, the capacity
dispatch of a prompt), compared with the same reference.

    python3 bench/readings_moe.py --workload serve.a3b-x4.chat \
        --seconds 10 --seeds 101 102 103 [--controls top7 ...]

Prints one JSON line per seed and program (``"control"`` null for the
program).  A limit lies above every program reading and below some
reading of every control.  Not part of a benchmark run; exits non-zero
without a TPU.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as harness  # noqa: E402
from common import (BENCH, ROOT, config_for, find, load_module,  # noqa: E402
                    load_spec, traffic_for)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="*", default=None,
                    help="default: every one of serve_moe.CONTROLS")
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
    controls = (driver.CONTROLS if args.controls is None
                else tuple(args.controls))
    for seed in args.seeds:
        for control in (None,) + controls:
            env = harness.Env(devs[:cell["chips"]], None, harness.T_START)
            res = driver.run(config, mix, seed, args.seconds, env,
                             control=control)
            print(json.dumps({"seed": seed, "control": control, "checks": {
                k: v for k, (v, _) in res["checks"].items()},
                "info": res["info"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
