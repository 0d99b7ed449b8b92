#!/usr/bin/env python3
"""Chip smoke run: the router's main path on a TPU, end to end.

    python3 chip_smoke.py             # one chip: routing + served phase
    python3 chip_smoke.py --chips 4   # four chips: the served phase with
                                      # engine i on device i, then with
                                      # all four engines on device 0

Routing phase (one chip): the first 2000 requests of the hotspot trace
over a 4096-instance fleet, in waves of 64.  One router plans every wave
on the device (``Router.route_batch``); a twin router routes the same
traffic one request at a time on the host (``Router.route``), with the
completion hooks driven identically.  For ``lmetric`` and ``vllm`` the
assignments must be identical and every wave's plan must come back from
the TPU.

Served phase: an ``EngineCluster`` of four full-width qwen3_4b engines
(random weights from ``--seed``) behind the lmetric router serves a dozen
requests that share 96-token app prefixes.  Every request must get its
``output_len`` tokens and the engines' prefix store must hit.  On one
chip, the first-token logits of a served prompt must also be finite and
agree with a one-shot prefill of the same prompt.  On four chips, tokens
must agree between the two placements for every request that reused the
same number of cached tokens in both.

Exits non-zero, printing no result, unless JAX's first device is a TPU.
Every failed check raises.  The last line of standard output is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
import argparse
import collections
import copy
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.join(ROOT, "examples"))

ARCH = "qwen3_4b"
N_INSTANCES = 4096
N_REQUESTS = 2000
WAVE = 64
N_SERVED = 12
#: one shared prompt length (96-token app prefix + 32-token suffix):
#: cold prompts prefill as two 64-token chunks, warm ones as one
#: 32-token chunk, so the engines compile few prefill shapes
SUFFIX_LEN = 32
ENGINE_KW = dict(block_size=16, max_batch=4, max_len=256, chunk_tokens=64)
#: largest |engine - one-shot| first-token logit gap, relative to the
#: one-shot logits' largest magnitude
LOGIT_RTOL = 0.05


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# routing phase
# ---------------------------------------------------------------------------
def _drive(router, reqs, wave, batched):
    """Route ``reqs`` in waves of ``wave``; returns (decisions, seconds
    spent in the routing calls).  The completion hooks are a pure
    function of the request index, so two routers stay state-for-state
    equal as long as their decisions do."""
    decisions, outstanding, routing_s = [], collections.deque(), 0.0
    for i in range(0, len(reqs), wave):
        w = reqs[i:i + wave]
        now = w[0].arrival
        t0 = time.perf_counter()
        iids = (router.route_batch(w, now) if batched
                else [router.route(r, now) for r in w])
        routing_s += time.perf_counter() - t0
        decisions.extend(iids)
        for r, iid in zip(w, iids):
            outstanding.append((iid, r, r.new_tokens))
            router.factory[iid].on_prefill_progress(256)
        for _ in range(len(w)):
            if len(outstanding) > 2:
                did, dreq, dnew = outstanding.popleft()
                inst = router.factory[did]
                inst.on_prefill_progress(dnew)
                inst.on_start_running(dreq)
                for _ in range(dreq.output_len % 7):
                    inst.on_decode_token()
                inst.on_finish(dreq)
    return decisions, routing_s


def _watch_plans(policy):
    """Record, per device-planned wave, the platforms its plan output
    lives on."""
    seen = []
    submit = policy.plan_submit

    def plan_submit(wave, factory):
        handle = submit(wave, factory)
        seen.append({d.platform for d in handle[0].devices()})
        return handle

    policy.plan_submit = plan_submit
    return seen


def routing_phase(platform="tpu", n_inst=N_INSTANCES, n_reqs=N_REQUESTS,
                  wave=WAVE):
    from repro.core import Router, make_policy
    from repro.workloads.traces import make_hotspot_trace

    trace = make_hotspot_trace(qps=14.0, duration=160.0, seed=5,
                               burst_start=40.0, burst_len=70.0)[:n_reqs]
    _check(len(trace) == n_reqs, f"trace has {len(trace)} requests")
    n_waves = -(-n_reqs // wave)
    mk = functools.partial(Router, n_instances=n_inst,
                           kv_capacity_tokens=150_000)
    for name in ("lmetric", "vllm"):
        # compile both wave shapes (full and last) outside the timing
        warm = mk(make_policy(name))
        warm.route_batch(copy.deepcopy(trace[:wave]), 0.0)
        if n_reqs % wave:
            warm.route_batch(copy.deepcopy(trace[:n_reqs % wave]), 0.0)
        pol = make_policy(name)
        seen = _watch_plans(pol)
        dev_router = mk(pol)
        got, t_dev = _drive(dev_router, copy.deepcopy(trace), wave, True)
        want, t_host = _drive(mk(make_policy(name)), copy.deepcopy(trace),
                              wave, False)
        if got != want:
            first = next(i for i, (a, b) in enumerate(zip(got, want))
                         if a != b)
            raise AssertionError(f"{name}: route_batch diverges from host "
                                 f"route at request {first}")
        _check(len(seen) == n_waves,
               f"{name}: {len(seen)} of {n_waves} waves planned on device")
        _check(all(s == {platform} for s in seen),
               f"{name}: wave plans on {seen}")
        print(f"routing {name}: n={n_inst} waves of {wave}: "
              f"{len(got)}/{len(want)} assignments identical, "
              f"{len(seen)} waves planned on {platform}, "
              f"{dev_router.factory.evictions} evictions", flush=True)
        print(f"routing {name} smoke reading (not a metric): route_batch "
              f"{len(got) / t_dev:.1f} decisions/s, host route "
              f"{len(want) / t_host:.1f} decisions/s", flush=True)


# ---------------------------------------------------------------------------
# served phase
# ---------------------------------------------------------------------------
_COMPILES = [0]
#: the event JAX records once per XLA backend compile
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _count_compiles():
    import jax

    def listener(event, duration, **kw):
        if event == BACKEND_COMPILE_EVENT:
            _COMPILES[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)


def build_model(arch, seed):
    import jax
    from repro.configs import get_config
    from repro.models import Model

    cfg = get_config(arch)
    model = Model(cfg)
    params = model.init(jax.random.key(seed))
    n = sum(x.size for x in jax.tree.leaves(params))
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"model {cfg.name}: {n / 1e9:.3f} B params, "
          f"{nbytes / 2**30:.2f} GiB, {cfg.n_layers} layers, "
          f"d_model {cfg.d_model}", flush=True)
    return model, params


def served_phase(model, params, seed, devices=None, label="served"):
    """Serve ``N_SERVED`` requests on four engines; returns the cluster.
    ``devices``: engine i on ``devices[i % len(devices)]``.  A first
    pass on a throwaway cluster compiles every program this placement
    runs, so the measured pass's step walls hold no compile."""
    import jax
    from repro.cluster.metrics import summarize
    from repro.core import LMetricPolicy
    from repro.serving.engine import EngineCluster
    from serve_cluster import build_workload

    arrivals = build_workload(N_SERVED, seed=seed, suffix_len=SUFFIX_LEN)

    def serve():
        cluster = EngineCluster(4, model, params, LMetricPolicy(),
                                devices=devices, **ENGINE_KW)
        return cluster, cluster.run(arrivals)

    c0 = _COMPILES[0]
    serve()
    c1 = _COMPILES[0]
    t0 = time.perf_counter()
    cluster, done = serve()
    wall = time.perf_counter() - t0
    _check(len(done) == len(arrivals),
           f"{label}: {len(done)} of {len(arrivals)} requests answered")
    for r in done:
        seq = cluster.served[r.rid]
        _check(len(seq.out_tokens) == r.output_len,
               f"{label}: rid {r.rid} got {len(seq.out_tokens)} of "
               f"{r.output_len} tokens")
    reused = sum(s.reused for s in cluster.served.values())
    _check(reused > 0 and any(r.hit_tokens > 0 for r in done),
           f"{label}: the prefix store never hit")
    s = summarize(done)
    placement = sorted({str(d) for e in cluster.engines
                        for d in jax.tree.leaves(e.cache)[0].devices()})
    print(f"{label}: {len(done)} requests answered on {placement}; "
          f"prefix store reused {reused} tokens over "
          f"{sum(1 for q in cluster.served.values() if q.reused)} requests",
          flush=True)
    print(f"{label} smoke reading (not a metric): TTFT mean "
          f"{s['ttft_mean'] * 1e3:.2f} ms, TPOT mean "
          f"{s['tpot_mean'] * 1e3:.2f} ms (virtual clock of measured step "
          f"walls); backend compiles: {c1 - c0} in the warm-up pass, "
          f"{_COMPILES[0] - c1} in the measured pass; measured pass wall "
          f"{wall:.1f} s", flush=True)
    return cluster


def check_logits(model, cluster, rtol=LOGIT_RTOL):
    """First-token logits of one served prompt through engine 0's own
    chunked prefill, against a one-shot prefill of the same prompt."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.serving.engine import prefill_slot

    eng = cluster.engines[0]
    toks = next(s.tokens for s in cluster.served.values())
    cache = model.init_cache(eng.max_batch, eng.max_len)
    for lo in range(0, len(toks), eng.chunk):
        chunk = jnp.asarray(toks[lo:lo + eng.chunk][None], jnp.int32)
        pos = jnp.arange(lo, lo + chunk.shape[1], dtype=jnp.int32)[None]
        logits, cache = prefill_slot(model, eng.params, cache, chunk, pos,
                                     jnp.asarray(lo, jnp.int32), 0)
    got = np.asarray(logits[0], np.float32)
    ref, _ = jax.jit(functools.partial(model.prefill, last_only=True))(
        eng.params, jnp.asarray(toks[None], jnp.int32))
    ref = np.asarray(ref[0, -1], np.float32)
    _check(got.shape == ref.shape == (model.cfg.vocab_size,),
           f"logit shapes {got.shape} {ref.shape}")
    _check(np.isfinite(got).all() and np.isfinite(ref).all(),
           "non-finite logits")
    rel = float(np.abs(got - ref).max() / np.abs(ref).max())
    print(f"logits: engine chunked prefill vs one-shot prefill: max gap "
          f"{rel:.2e} of max |logit|, argmax {int(got.argmax())} vs "
          f"{int(ref.argmax())}", flush=True)
    _check(rel <= rtol, f"logit gap {rel:.3e} > {rtol}")


def compare_placements(spread, pooled):
    """Tokens must agree for every request that reused the same number
    of cached tokens under both placements."""
    same = [rid for rid, s in spread.served.items()
            if pooled.served[rid].reused == s.reused]
    for rid in same:
        _check(spread.served[rid].out_tokens == pooled.served[rid].out_tokens,
               f"rid {rid}: tokens differ between placements")
    _check(len(same) > 0, "no request comparable between placements")
    print(f"placements agree: {len(same)}/{len(spread.served)} requests "
          f"reused equally and generated identical tokens", flush=True)


# ---------------------------------------------------------------------------
def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU ({dev['platform']}); "
              "nothing run", file=sys.stderr)
        return 1
    print(f"device: {dev['platform']} {dev['kind']} x{dev['count']}; "
          f"compile cache {cache_dir}", flush=True)
    _check(dev["count"] >= args.chips,
           f"--chips {args.chips} but {dev['count']} devices")
    _count_compiles()

    if args.chips == 1:
        routing_phase()
        model, params = build_model(ARCH, args.seed)
        cluster = served_phase(model, params, args.seed)
        check_logits(model, cluster)
    else:
        model, params = build_model(ARCH, args.seed)
        spread = served_phase(model, params, args.seed,
                              devices=devs[:4], label="served 4 devices")
        pooled = served_phase(model, params, args.seed, devices=devs[:1],
                              label="served 1 device")
        compare_placements(spread, pooled)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
