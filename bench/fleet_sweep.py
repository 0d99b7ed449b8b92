#!/usr/bin/env python3
"""Find the knee of a routing cell's fleet: drive the cell's router with
its mix at several trace rates and print, every ``--every`` requests, the
fleet's load under the mix's service model.

    JAX_PLATFORMS=cpu python3 bench/fleet_sweep.py \
        --workload route.fleet4096.coder --seed 12345 \
        --requests 81920 --rates 400 500

Each line gives the requests per instance in service, the mean prefill
backlog per instance (seconds of queued prefill on the trace clock), the
evictions and the share of prompt tokens hit in the last ``--every``
requests.  Below the knee the backlog settles once the caches are full;
past it the backlog grows and the hit share falls as the caches thrash.
The cell's mix takes about four fifths of the knee.  These are counts on
the trace clock, the same on any device; routing speed is not measured.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

from common import ROOT, config_for, find, load_spec, traffic_for  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=12345)
    ap.add_argument("--requests", type=int, default=81920)
    ap.add_argument("--every", type=int, default=8192)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    spec = load_spec()
    cell = find(spec["workloads"], args.workload)
    config = config_for(spec, cell["config"])
    sys.path.insert(0, str(ROOT / "src"))
    import route
    from generate import ServiceModel, route_trace

    n = config["n_instances"]
    for rate in args.rates:
        mix = dict(traffic_for(cell["traffic"]), rate=rate)
        reqs = route_trace(mix, args.seed, args.requests)
        router = route._make_router(config, mix)
        svc = ServiceModel(mix, n)
        dec = np.full(len(reqs), -1, np.int64)
        hits = np.zeros(len(reqs), np.int64)
        ev0 = 0
        for lo in range(0, args.requests, args.every):
            hi = min(args.requests, lo + args.every)
            route._drive(router, reqs, lo, hi, int(mix["wave"]), svc, dec,
                         hits, [])
            now = reqs[hi - 1].arrival
            ev = int(router.factory.evictions)
            prompt = sum(r.prompt_len for r in reqs[lo:hi])
            print(json.dumps({
                "rate": rate, "requests": hi, "trace_s": now,
                "in_service_per_instance": svc.pending() / 2 / n,
                "prefill_backlog_s": float(
                    np.maximum(svc.free_at - now, 0.0).mean()),
                "evictions": ev - ev0,
                "hit_share": float(hits[lo:hi].sum() / prompt)}),
                flush=True)
            ev0 = ev
        router.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
