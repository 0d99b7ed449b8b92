#!/usr/bin/env python3
"""Find the knee of a served cell: serve the first ``--requests`` arrivals
of its mix at several fixed rates in one process, each on a fresh cluster
to the last request, and print per rate the rate completed, TTFT and TPOT
percentiles on the cluster's clock, and whether the queue grew.

    python3 bench/sweep.py --workload serve.x4.agent --seed 1 \
        --requests 120 --rates 4 5 6 7 8

A rate the cluster sustains completes requests as fast as they arrive and
keeps TTFT of the last third of arrivals near that of the first third;
past the knee TTFT climbs through the run and the rate completed stays
flat.  A cell below the knee takes about four fifths of it, a cell above
it a rate well past it.  Not part of a benchmark run; exits non-zero
without a TPU.
"""
import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from common import ROOT, config_for, find, load_spec, pct, traffic_for  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--requests", type=int, default=120)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    spec = load_spec()
    cell = find(spec["workloads"], args.workload)
    config = config_for(spec, cell["config"])
    mix = traffic_for(cell["traffic"])
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: JAX found no TPU; nothing run", file=sys.stderr)
        return 1
    import serve
    from generate import served_arrivals
    from repro.models import Model

    model = Model(serve.model_config(config))
    params = serve.make_weights(model, args.seed, config["initializer_range"])
    warm = serve._cluster(model, params, config)
    warm.run(serve._warm_arrivals(mix, args.seed, config["vocab_size"]))
    serve.warm_prefills(model, warm, serve.prefill_steps(
        served_arrivals(mix, args.seed, args.requests, config["vocab_size"]),
        mix, config["engine"]))
    del warm
    gc.collect()
    for rate in args.rates:
        arrivals = served_arrivals(dict(mix, rate=rate), args.seed,
                                   args.requests, config["vocab_size"])
        cluster = serve._cluster(model, params, config)
        t0 = time.perf_counter()
        done = cluster.run(arrivals)
        wall = time.perf_counter() - t0
        done.sort(key=lambda r: r.arrival)
        third = max(1, len(done) // 3)
        ttft = [r.ttft for r in done]
        tpot = [r.tpot for r in done if r.output_len > 1]
        span = max(r.t_finish for r in done)
        print(json.dumps({
            "rate": rate, "requests": len(done), "wall_s": wall,
            "virtual_span_s": span,
            "completed_per_virtual_s": len(done) / span,
            "output_tokens_per_wall_s": sum(r.output_len for r in done) / wall,
            "ttft_p50_ms": pct(ttft, 50) * 1e3,
            "ttft_p90_ms": pct(ttft, 90) * 1e3,
            "tpot_p90_ms": pct(tpot, 90) * 1e3,
            "ttft_first_third_ms": pct(ttft[:third], 50) * 1e3,
            "ttft_last_third_ms": pct(ttft[-third:], 50) * 1e3}),
            flush=True)
        del cluster, done
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
