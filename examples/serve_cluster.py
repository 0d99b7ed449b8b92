"""End-to-end driver: serve a REAL JAX model with batched requests.

Spins up 4 in-process serving instances of a reduced qwen3-family model
(real parameters, real KV cache, real chunked prefill with prefix-cache
compute skip), routes ~40 requests with LMETRIC vs the vLLM baseline, and
reports TTFT/TPOT/hit-rate from the virtual-time cluster.

  PYTHONPATH=src python examples/serve_cluster.py [--n 40] [--policy both]

``--closed-loop`` swaps the pre-stamped workload for coding-agent
sessions driven end-to-end through the real engines: each agent's next
prompt embeds its previous turn (so the prefix store sees genuinely
growing shared context), and the next turn is only submitted after the
previous one finishes — the closed-loop feedback of
``repro.cluster.closed_loop``, but with real JAX compute underneath.

  PYTHONPATH=src python examples/serve_cluster.py --closed-loop [--n 6]
"""
import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.cluster.metrics import fmt_row, summarize
from repro.compile_cache import use_compile_cache
from repro.configs import get_config
from repro.core import JSQPolicy, LMetricPolicy, SessionAffinityPolicy
from repro.models import Model
from repro.serving.engine import EngineCluster
from repro.workloads.sessions import (SESSIONS, SLO, Session,
                                      blocks_to_tokens, make_sessions)


def build_workload(n, seed=0, suffix_len=None):
    """Multi-app workload: 3 'applications' with shared 96-token system
    prompts.  Suffixes are 8-31 tokens, or all ``suffix_len`` long (a
    fixed prompt length keeps the engines' prefill shapes few)."""
    rng = np.random.RandomState(seed)
    apps = [rng.randint(4, 500, size=96) for _ in range(3)]
    arrivals, t = [], 0.0
    for i in range(n):
        t += float(rng.exponential(0.05))
        app = rng.randint(3)
        n_sfx = rng.randint(8, 32)      # drawn either way: one stream per seed
        suffix = rng.randint(4, 500, size=suffix_len or n_sfx)
        toks = np.concatenate([apps[app], suffix]).astype(np.int32)
        arrivals.append((t, toks, int(rng.randint(4, 12))))
    return arrivals


def build_closed_loop_sessions(n, seed=0):
    """Tiny coding-agent sessions sized for the smoke engine: ~3-block
    prompts of 16-token blocks growing turn over turn, output lengths
    the 256-token cache can hold."""
    # lenient SLO: smoke-model walltimes are seconds/turn on CPU, and
    # the demo should show the feedback loop, not mass abandonment
    spec = dataclasses.replace(
        SESSIONS["coder"], app_prefix_blocks=2, n_apps=2,
        first_input_blocks=2, turn_input_blocks=1, turns_mean=3.0,
        output_tokens_mean=8, output_tokens_cv=0.3,
        think_time_mean=0.05, block_tokens=16,
        slo=SLO(ttft=30.0, tpot=2.0))
    base = make_sessions("coder", n, seed=seed, start_rate=10.0)
    return [Session(s.sid, spec, s.start_t, seed, s.app) for s in base]


def to_arrival(req):
    toks = blocks_to_tokens(req.blocks, tokens_per_block=16)
    return (req.arrival, toks, req.output_len, req.session_id)


def run_closed_loop(model, params, n_sessions, policy_cls, name):
    sessions = build_closed_loop_sessions(n_sessions)
    by_sid = {s.sid: s for s in sessions}
    cluster = EngineCluster(4, model, params, policy_cls(),
                            block_size=16, max_batch=4, max_len=256,
                            chunk_tokens=64)

    def feedback(req, now):
        return [to_arrival(r)
                for r in by_sid[req.session_id].on_complete(req, now)]

    t0 = time.time()
    arrivals = [to_arrival(r) for s in sessions for r in s.start()]
    done = cluster.run(arrivals, feedback=feedback)
    s = summarize(done)
    print(fmt_row(name, s) + f"  wall={time.time() - t0:.1f}s")
    finished = sum(1 for s in sessions if s.completed or s.abandoned)
    line = (f"  {finished}/{len(sessions)} sessions done, "
            f"{len(done)} turns served")
    pins = {s.sid: p for s in sessions
            if (p := cluster.router.session_pin(s.sid)) is not None}
    if pins:
        line += f"; session->instance pins: {pins}"
    print(line)
    return done


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=40)
    ap.add_argument("--arch", default="qwen3_4b-smoke")
    ap.add_argument("--policy", default="both",
                    choices=["lmetric", "vllm", "affinity", "both"])
    ap.add_argument("--closed-loop", action="store_true",
                    help="drive coding-agent sessions with completion->"
                         "next-turn feedback through the real engines")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"serving {cfg.name}: {n_params / 1e6:.1f}M params, "
          f"4 instances\n")

    policies = {"lmetric": LMetricPolicy, "vllm": JSQPolicy,
                "affinity": SessionAffinityPolicy}
    names = [args.policy] if args.policy != "both" \
        else ["lmetric", "vllm"]
    if args.closed_loop:
        n = min(args.n, 12)
        for name in names:
            run_closed_loop(model, params, n, policies[name], name)
        print("\n(closed loop: turn t+1 submitted only after turn t "
              "finished; prompts embed prior output blocks)")
        return
    for name in names:
        t0 = time.time()
        cluster = EngineCluster(4, model, params, policies[name](),
                                block_size=16, max_batch=4, max_len=256,
                                chunk_tokens=64)
        done = cluster.run(build_workload(args.n))
        s = summarize(done)
        print(fmt_row(name, s) + f"  wall={time.time() - t0:.1f}s "
              f"sched={cluster.router.mean_decision_us():.0f}µs")
    print("\n(virtual-time: TTFT/TPOT are measured JAX step walltimes "
          "composed per instance)")


if __name__ == "__main__":
    main()
