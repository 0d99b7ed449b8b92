"""Served cells: ``EngineCluster`` engines behind the router, on one chip,
offered more than they serve.

Set-up builds the program's model from the configuration, makes the
weights on the device from the seed in one jitted call, draws the mix's
arrivals, serves a short warm-up pair on a throwaway cluster and runs its
prefill program at every step length the arrivals can ask for, so that
every program and every shape the mix uses is compiled (then frees it).
Then one ``EngineCluster.run`` serves the arrivals on a fresh cluster.
Its first ``warm_requests`` arrivals are set-up too: they fill the
engines' slots, queues and prefix stores.  The window opens when the
router takes the next arrival and closes at the first engine step that
would start ``--seconds`` later (the run ends there).  The mix's rate lies
above the knee, so the queues grow all through the window and the
engines never wait for work.

End-to-end: ``output_tokens_per_s``, every token the engines produced in
the window (a decode step's batch, a finished prefill's first token) over
the window's wall seconds.  The cluster steps its engines one at a time
on a virtual clock; TTFT and TPOT on that clock swing above the knee and
are only printed.  Afterwards the cluster is freed and the plain float32
reference (``reference.qwen3_ref``) runs over a sample of the requests
that finished in the window, drawn from the seed with the longest among
them: the widest gap by which a served token's logit lies below the
reference's best logit must stay under the mix's ``gap_limit``.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

import devtrace as tr
from common import jax_seed, pct, rng
from generate import served_arrivals
from reference import qwen3_ref


def model_config(config: dict):
    """The program's ``ModelConfig`` for a Qwen3-style dense decoder."""
    from repro.models.config import ModelConfig, dense_pattern
    L = config["num_hidden_layers"]
    return ModelConfig(
        name=config["name"], arch_type="dense", n_layers=L,
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        block_pattern=dense_pattern(L), head_dim=config["head_dim"],
        qk_norm=True, rope_theta=float(config["rope_theta"]),
        tie_embeddings=config["tie_word_embeddings"],
        norm_eps=config["rms_norm_eps"], dtype=config["torch_dtype"],
        source=config["source"])


def make_weights(model, seed: int, std: float):
    """Every weight of the model in one jitted call on the device: norm
    scales 1, every matrix normal with standard deviation ``std``, in
    the served dtype."""
    import jax
    import jax.numpy as jnp
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, s) in enumerate(leaves):
            if jax.tree_util.keystr(path).endswith("['scale']"):
                out.append(jnp.ones(s.shape, s.dtype))
            else:
                out.append((jax.random.normal(jax.random.fold_in(key, i),
                                              s.shape, jnp.float32)
                            * std).astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    params = jax.jit(build)(jax.random.key(jax_seed(seed, 3)))
    jax.block_until_ready(params)
    return params


def _cluster(model, params, config: dict):
    from repro.core import make_policy
    from repro.serving.engine import EngineCluster
    e = config["engine"]
    return EngineCluster(config["engines"], model, params,
                         make_policy(config["policy"]),
                         block_size=e["block_size"], devices=None,
                         max_batch=e["max_batch"], max_len=e["max_len"],
                         chunk_tokens=e["chunk_tokens"],
                         prefix_capacity=e["prefix_capacity"])


def prefill_steps(arrivals, mix: dict, engine: dict):
    """Every prefill step (start, length) that ``arrivals`` can ask of an
    engine: each prompt from position 0 (no stored prefix) and, where the
    mix shares prefixes, from the end of the shared prefix (a stored app
    prefix reused), in steps of at most ``chunk_tokens``."""
    chunk, max_len = int(engine["chunk_tokens"]), int(engine["max_len"])
    starts = [0] + ([int(mix["prefix_tokens"])] if mix["prefix_shared"]
                    else [])
    steps = set()
    for _, toks, _ in arrivals:
        end = min(len(toks), max_len)
        for pos in starts:
            pos = min(pos, end - 1)
            while pos < end:
                n = min(chunk, end - pos)
                steps.add((pos, n))
                pos += n
    return sorted(steps)


def warm_prefills(model, cluster, steps):
    """Run the engine's prefill step once for every (start, length) in
    ``steps``, with its inputs built as the engine builds them, on
    ``cluster``'s first engine (its cache is left as it was): every
    program a window's prefill uses is compiled."""
    import jax.numpy as jnp
    from repro.serving.engine import prefill_slot
    eng = cluster.engines[0]
    for pos, n in steps:
        toks = jnp.asarray(np.zeros((1, n), np.int32), jnp.int32)
        positions = jnp.arange(pos, pos + n, dtype=jnp.int32)[None]
        logits, _ = prefill_slot(model, eng.params, eng.cache, toks,
                                 positions, jnp.asarray(pos, jnp.int32), 0)
        logits.block_until_ready()


def _warm_arrivals(mix: dict, seed: int, vocab: int):
    """Two requests in the mix's shape, the second long after the first
    (so that, where the mix shares prefixes, it hits the first one's
    stored fragment): every program and shape of the window compiles."""
    g = rng(seed, 4)
    bt, prefix = int(mix["block_tokens"]), int(mix["prefix_tokens"])
    head = g.integers(0, vocab, size=prefix, dtype=np.int32)
    out = []
    for t in (0.0, 1e4):
        if not mix["prefix_shared"]:
            head = g.integers(0, vocab, size=prefix, dtype=np.int32)
        body = g.integers(0, vocab, size=bt, dtype=np.int32)
        out.append((t, np.concatenate([head, body]), 3))
    return out


class WindowClosed(Exception):
    """Raised from a watched engine step once the window's time is up:
    it ends ``EngineCluster.run`` at a step boundary."""


class _Watch:
    """Watch a cluster from outside: the window opens when the router
    takes arrival ``n_warm`` (the first of the window) and closes at the
    first engine step that would start ``seconds`` later.  Records every
    engine step inside the window (kind, wall, what it computed) and the
    sequences that finished in it."""

    def __init__(self, cluster, n_warm: int, seconds: float, env):
        self.env, self.n_warm, self.seconds = env, n_warm, seconds
        self.window = env.window
        self.t0 = self.t_close = None
        self.c0 = self.in_window = 0
        self.routed = 0
        self.steps: list = []
        self.first: list = []        # sequences whose prefill ended inside
        self.finished: list = []     # sequences that finished inside
        route = cluster.router.route

        def routed(req, now, _route=route):
            if self.t0 is None and req.rid >= n_warm:
                self._open()
            if self.t0 is not None:
                self.routed += 1
            return _route(req, now)

        cluster.router.route = routed
        for eng in cluster.engines:
            eng.step = self._wrap(eng, eng.step)

    def _open(self):
        self.c0 = self.env.compiles()
        if self.window is not None:
            self.window.start()
        self.t0 = time.perf_counter()

    def close(self):
        if self.t_close is None:
            self.t_close = time.perf_counter()
            if self.window is not None:
                self.window.stop()
            self.in_window = self.env.compiles() - self.c0

    @property
    def open(self) -> bool:
        return self.t0 is not None and self.t_close is None

    def _wrap(self, eng, orig):
        traced = self.window is not None

        def step():
            if not self.open:
                return orig()
            if time.perf_counter() - self.t0 >= self.seconds:
                self.close()
                raise WindowClosed
            head = eng.waiting[0] if eng.waiting else None
            cached = [int(eng._pos[s]) for s in eng.running]
            inside = traced and self.window.active
            with tr.annotate(traced, "engine_step"):
                t0 = time.perf_counter()
                ev = orig()
                wall = time.perf_counter() - t0
            rec = {"kind": ev["kind"], "wall": wall,
                   "traced": inside and self.window.active,
                   "out_tokens": ev["decode_bs"] + len(ev["first"])}
            if ev["kind"] == "prefill":
                n = ev["prefill_tokens"]
                rec.update(tokens=n, start=head.pos - n)
            elif ev["kind"] == "decode":
                rec.update(cached=cached)
            self.steps.append(rec)
            self.first.extend(ev["first"])
            self.finished.extend(ev["finished"])
            if traced:
                self.window.poll()
            return ev
        return step


def _sample(finished, seed: int, tokens: int):
    """The longest finished request, then others drawn from the seed,
    until the sample holds ``tokens`` served tokens."""
    order = sorted(finished, key=lambda s: -(len(s.tokens)
                                             + len(s.out_tokens)))
    if not order:
        return []
    pick = [order[0]]
    rest = order[1:]
    for i in rng(seed, 5).permutation(len(rest)):
        if sum(len(s.out_tokens) for s in pick) >= tokens:
            break
        pick.append(rest[int(i)])
    return pick


def run(config: dict, mix: dict, seed: int, seconds: float, env,
        control=None) -> dict:
    """One run of a served cell; ``control`` (``"fp8"``) also reads the
    control's gap on the sampled requests (``bench/readings.py`` does, a
    benchmark run does not)."""
    from repro.models import Model

    model = Model(model_config(config))
    params = make_weights(model, seed, config["initializer_range"])
    vocab = config["vocab_size"]
    n_warm = int(mix["warm_requests"])
    # each engine's clock advances by its own steps only, so the cluster's
    # clock runs at most 1/engines of the wall: these arrivals outlast the
    # window with a quarter to spare
    n_win = math.ceil(float(mix["rate"]) * seconds * 1.25 / config["engines"])
    arrivals = served_arrivals(mix, seed, n_warm + n_win, vocab)

    warm = _cluster(model, params, config)
    warm.run(_warm_arrivals(mix, seed, vocab))
    warm_prefills(model, warm, prefill_steps(arrivals, mix, config["engine"]))
    del warm
    gc.collect()
    cluster = _cluster(model, params, config)
    watch = _Watch(cluster, n_warm, seconds, env)
    try:
        cluster.run(arrivals)
    except WindowClosed:
        pass
    if watch.t0 is None or watch.t_close is None:
        raise RuntimeError("the arrivals ran out before the window closed: "
                           "the mix's rate is below what the cluster serves")
    setup_s = watch.t0 - env.t_start
    window_s = watch.t_close - watch.t0
    memory_peak = env.memory_peak()
    queued = sum(len(e.waiting) for e in cluster.engines)

    done = watch.finished
    short = sum(1 for s in done if len(s.out_tokens) != s.req.output_len)
    out_tokens = sum(r["out_tokens"] for r in watch.steps)
    reused = sum(s.reused for s in watch.first)
    prompt_tokens = sum(len(s.tokens) for s in watch.first)
    pick = [(s.tokens, list(s.out_tokens)) for s in
            _sample(done, seed, int(mix["check_tokens"]))]
    ttft = [s.req.ttft for s in done] or [float("nan")]
    del cluster, done
    gc.collect()

    t_ref = time.perf_counter()
    length = config["engine"]["max_len"]
    gaps = [qwen3_ref.served_gap(params, config, p, o, length, control)
            for p, o in pick]
    ref_s = time.perf_counter() - t_ref
    gap = max((g["gap"] for g in gaps), default=0.0)
    checks = {
        "logit_gap": (gap, float(mix["gap_limit"])),
        "unchecked_window": (int(not pick), 0),
        "short_requests": (short, 0),
        "compiles_in_window": (watch.in_window, 0),
    }
    del params
    e2e = {"output_tokens_per_s": out_tokens / window_s}
    info = (f"window {window_s:.3f} s wall after {n_warm} warm-up "
            f"requests: {watch.routed} routed, {len(watch.finished)} "
            f"finished, {queued} still queued at the close, {out_tokens} "
            f"output tokens, {len(watch.steps)} engine steps; prefix reuse "
            f"{reused}/{prompt_tokens} prompt tokens; TTFT of the finished "
            f"p50 {pct(ttft, 50) * 1e3:.1f} ms, p90 {pct(ttft, 90) * 1e3:.1f}"
            f" ms (queueing above the knee); reference over {len(pick)} "
            f"requests ({sum(g['tokens'] for g in gaps)} tokens) in "
            f"{ref_s:.1f} s; device peak {memory_peak} B")
    return {
        "setup_s": setup_s,
        "e2e": e2e,
        "ctx": {"steps": watch.steps, "window_s": window_s,
                "reused": reused, "prompt_tokens": prompt_tokens},
        "attempted": watch.routed, "failed": short,
        "memory_peak_bytes": memory_peak,
        "checks": checks, "info": info,
        "controls": ({control: max((g["control_gap"] for g in gaps),
                                   default=0.0)} if control else {}),
    }
