"""Served cells of a Qwen3-MoE model: ``EngineCluster`` engines behind the
router, on one chip, each engine holding one chip's share of the experts,
offered more than they serve.

Set-up, window and end-to-end metric are those of ``bench/serve.py``,
whose helpers this driver runs: the model's weights made on the device
from the seed, every prefill length the arrivals can ask for compiled on
a throwaway cluster, the first ``warm_requests`` arrivals served before
the window opens, ``output_tokens_per_s`` over the window's wall seconds.

The model is the program's ``ModelConfig`` with the configuration's
expert share (``first_expert_held``, ``n_experts_held``): the router
scores all ``num_experts``, and each layer computes its held experts'
part (``repro.models.layers.moe_dropless``).

With ``--trace 1`` the cluster is built with the program's observability
bundle on the profiler clock (``spans.profiler_obs``), and each step of
the window records the deltas of the engines' MoE counters
(``engine.moe_routed_local``, ``engine.moe_layer_steps``,
``engine.moe_experts_touched``, ``engine.moe_max_expert_tokens``) that the
readers under ``bench/metrics/`` use.  ``--trace 0`` attaches nothing.

``correct`` compares two things with the plain float32 reference
(``reference.qwen3_moe_ref``, given the same share), after the window,
with the cluster freed:

* the served tokens: the reference runs over a sample of the sequences
  the window served, those that finished in it and those still decoding
  at its close (a request decodes for tens of seconds here, so a window
  that a profiler stalls may finish none), the longest among them and
  others drawn from the seed.  The widest gap by which a served token's
  logit lies below the reference's best logit must stay under the mix's
  ``gap_limit``.  The reference picks its own top-k from its own float32
  router logits, so a near-tied k-th choice may fall the other way in the
  program's bf16 activations; the share of (position, layer) routings
  within 1e-3 of such a tie is printed.
* the expert layer of the timed programs: each engine call leaves its
  expert layers' inputs and outputs at some token rows in the cache
  (``moe_tap``: a decode step's every slot, a prefill's rows spread over
  its chunk).  A seeded sample of ``check_steps`` of the window's decode
  steps and as many of its prefills keep theirs on the device; the
  reference's expert layer runs on each tapped input and is compared
  with the program's output, per (row, layer), relative to its norm.  The
  median error must stay under ``expert_limit`` (``expert_err``), and
  the share of pairs that err by over half (a choice gained or lost, a
  row or a layer gone wrong) under ``lost_limit`` (``expert_lost``).  A
  chip holds one eighth of each token's expert work, so a fault there
  hardly moves the served tokens; these checks see it.

``CONTROLS`` put a broken expert layer in the program's place (``run``'s
``control``): expert weights rounded through float8, routing to one
expert fewer, or the capacity dispatch on a prompt that the served
programs used before.  ``bench/readings_moe.py`` reads them.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import math
import time

import numpy as np

from common import pct, rng
from generate import served_arrivals
from reference import qwen3_moe_ref
from serve import (WindowClosed, _cluster, _sample, _warm_arrivals, _Watch,
                   make_weights, prefill_steps, warm_prefills)
from spans import attached, profiler_obs

#: a broken expert layer put in the program's place (``run``'s ``control``)
CONTROLS = ("fp8_experts", "top7", "capacity")

#: the engines' MoE counters, read as deltas over each step of the window
MOE_COUNTERS = {"routed": "engine.moe_routed_local",
                "layer_steps": "engine.moe_layer_steps",
                "touched": "engine.moe_experts_touched",
                "max_tokens": "engine.moe_max_expert_tokens"}


def model_config(config: dict):
    """The program's ``ModelConfig`` for a Qwen3-MoE decoder holding the
    configuration's expert share.  Every layer is sparse
    (``decoder_sparse_step`` 1, no ``mlp_only_layers``), so the dense
    width ``intermediate_size`` is never used and ``d_ff`` is the expert
    width."""
    from repro.models.config import ModelConfig, dense_pattern
    if not (config["decoder_sparse_step"] == 1
            and not config["mlp_only_layers"] and config["norm_topk_prob"]):
        raise ValueError(f"{config['name']}: only every layer sparse with "
                         "renormalised top-k weights is served")
    L = config["num_hidden_layers"]
    F = config["moe_intermediate_size"]
    return ModelConfig(
        name=config["name"], arch_type="moe", n_layers=L,
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], d_ff=F,
        vocab_size=config["vocab_size"], block_pattern=dense_pattern(L),
        head_dim=config["head_dim"], n_experts=config["num_experts"],
        top_k=config["num_experts_per_tok"], moe_d_ff=F,
        expert_first=config["first_expert_held"],
        experts_held=config["n_experts_held"], qk_norm=True,
        rope_theta=float(config["rope_theta"]),
        tie_embeddings=config["tie_word_embeddings"],
        norm_eps=config["rms_norm_eps"], dtype=config["torch_dtype"],
        source=config["source"])


def _fp8_experts(params):
    """``params`` with every expert matrix rounded through float8 e4m3,
    one scale per column of the reduced dimension (layer by layer, in
    place: the stacks are donated)."""
    import jax
    from reference.qwen3_ref import _mat

    @functools.partial(jax.jit, donate_argnums=0)
    def fp8(stack):
        def one(layer, s):
            w = _mat(s[layer], "fp8", 1).astype(s.dtype)
            return jax.lax.dynamic_update_slice_in_dim(s, w[None], layer, 0)
        return jax.lax.fori_loop(0, stack.shape[0], one, stack)

    ffn = params["units"][0]["ffn"]
    for k in ("wi", "wg", "wo"):
        ffn[k]["w"] = fp8(ffn[k]["w"])
    return params


@contextlib.contextmanager
def _capacity(cfg):
    """The served programs, traced inside, drop each held expert's choices
    past ``ceil(T * top_k / n_experts * 1.25)`` (at least ``top_k``) of a
    call's T token rows, in row order: the capacity dispatch of a prompt
    (a decode step's rows never reach it)."""
    from repro.models import layers
    orig = layers.held_experts_op

    def dropping(x, comb, counts, *rest, **kw):
        import jax.numpy as jnp
        cap = max(math.ceil(x.shape[0] * cfg.top_k / cfg.n_experts
                            * cfg.capacity_factor), cfg.top_k)
        rank = jnp.cumsum(comb > 0, axis=0)
        return orig(x, jnp.where(rank <= cap, comb, 0.0), counts, *rest,
                    **kw)

    layers.held_experts_op = dropping
    try:
        yield
    finally:
        layers.held_experts_op = orig


class _Taps:
    """A seeded sample of the window's engine calls' ``moe_tap``, left on
    the device: ``per_kind`` of its decode steps and as many of its
    prefills, each call as likely as any other (reservoir sampling)."""

    def __init__(self, cluster, watch, per_kind: int, seed: int):
        self.per_kind, self.rng = per_kind, rng(seed, 6)
        self.kept = {"prefill": [], "decode": []}
        self.seen = {"prefill": 0, "decode": 0}
        for eng in cluster.engines:
            def step(inner=eng.step, eng=eng):
                n = len(watch.steps)
                ev = inner()
                if len(watch.steps) > n and ev["kind"] in self.kept:
                    self._offer(ev["kind"], eng.cache["moe_tap"])
                return ev
            eng.step = step

    def _offer(self, kind: str, tap):
        kept, i = self.kept[kind], self.seen[kind]
        self.seen[kind] += 1
        if i < self.per_kind:
            kept.append(tap)
        else:
            j = int(self.rng.integers(0, i + 1))
            if j < self.per_kind:
                kept[j] = tap

    def host(self):
        """The kept taps on the host: {kind: (h, y)}, each (MoE layers,
        rows of every kept call, d), or None where none was kept."""
        out = {}
        for kind, kept in self.kept.items():
            out[kind] = (tuple(np.concatenate([np.asarray(t[k]) for t in kept],
                                              axis=1) for k in ("h", "y"))
                         if kept else None)
        return out


def _count_steps(cluster, watch, registry):
    """Give each step record of ``watch`` its MoE counters' deltas."""
    for eng in cluster.engines:
        def step(inner=eng.step):
            n = len(watch.steps)
            before = dict(registry.counters)
            ev = inner()
            if len(watch.steps) > n:
                watch.steps[-1]["moe"] = {
                    k: registry.counters.get(c, 0) - before.get(c, 0)
                    for k, c in MOE_COUNTERS.items()}
            return ev
        eng.step = step


def run(config: dict, mix: dict, seed: int, seconds: float, env,
        control=None) -> dict:
    """One run of a served MoE cell; ``control`` (one of ``CONTROLS``)
    serves with a broken expert layer in the program's place
    (``bench/readings_moe.py`` does, a benchmark run does not)."""
    from repro.models import Model

    assert control is None or control in CONTROLS, control
    mc = model_config(config)
    if control == "top7":
        mc = dataclasses.replace(mc, top_k=mc.top_k - 1)
    model = Model(mc)
    params = make_weights(model, seed, config["initializer_range"])
    if control == "fp8_experts":
        params = _fp8_experts(params)
    vocab = config["vocab_size"]
    n_warm = int(mix["warm_requests"])
    # each engine's clock advances by its own steps only, so the cluster's
    # clock runs at most 1/engines of the wall: these arrivals outlast the
    # window with a quarter to spare
    n_win = math.ceil(float(mix["rate"]) * seconds * 1.25 / config["engines"])
    arrivals = served_arrivals(mix, seed, n_warm + n_win, vocab)

    with (_capacity(mc) if control == "capacity"
          else contextlib.nullcontext()):
        warm = _cluster(model, params, config)
        warm.run(_warm_arrivals(mix, seed, vocab))
        warm_prefills(model, warm,
                      prefill_steps(arrivals, mix, config["engine"]))
        del warm
        gc.collect()
        obs = profiler_obs() if env.window is not None else None
        with attached(obs):
            cluster = _cluster(model, params, config)
        watch = _Watch(cluster, n_warm, seconds, env)
        if obs is not None:
            _count_steps(cluster, watch, obs.registry)
        taps = _Taps(cluster, watch, int(mix["check_steps"]), seed)
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
    running = [s for e in cluster.engines for s in e.running.values()]

    done = watch.finished
    short = sum(1 for s in done if len(s.out_tokens) != s.req.output_len)
    out_tokens = sum(r["out_tokens"] for r in watch.steps)
    reused = sum(s.reused for s in watch.first)
    prompt_tokens = sum(len(s.tokens) for s in watch.first)
    pick = [(s.tokens, list(s.out_tokens)) for s in
            _sample(done + running, seed, int(mix["check_tokens"]))]
    ttft = [s.req.ttft for s in done] or [float("nan")]
    decode_s = sum(r["wall"] for r in watch.steps if r["kind"] == "decode")
    tapped = taps.host()
    del cluster, done, running, taps
    gc.collect()
    if control == "fp8_experts":     # the reference reads them unrounded
        del params
        gc.collect()
        params = make_weights(model, seed, config["initializer_range"])

    t_ref = time.perf_counter()
    length = config["engine"]["max_len"]
    gaps = [qwen3_moe_ref.served_gap(params, config, p, o, length)
            for p, o in pick]
    gap = max((g["gap"] for g in gaps), default=0.0)
    errs = {}
    for kind, t in tapped.items():
        if t is not None:
            e = qwen3_moe_ref.expert_err(params, config, *t).ravel()
            errs[kind] = e[~np.isnan(e)]      # pairs with a held choice
    ref_s = time.perf_counter() - t_ref
    err = np.concatenate(list(errs.values()) or [np.zeros(0)])

    def lost(e):
        return float(np.mean(e > 0.5)) if e.size else 0.0

    positions = sum(g["positions"] for g in gaps)
    near = (sum(g["near_ties"] * g["positions"] for g in gaps)
            / max(positions, 1))
    checks = {
        "logit_gap": (gap, float(mix["gap_limit"])),
        "expert_err": (float(np.median(err)) if err.size else 0.0,
                       float(mix["expert_limit"])),
        "expert_lost": (lost(err), float(mix["lost_limit"])),
        "unchecked_window": (int(not pick or not err.size), 0),
        "short_requests": (short, 0),
        "compiles_in_window": (watch.in_window, 0),
    }
    del params
    e2e = {"output_tokens_per_s": out_tokens / window_s}
    tap_info = ", ".join(
        f"{kind} {e.size} (median {np.median(e) if e.size else 0:.5f},"
        f" over half {100 * lost(e):.4f}%)" for kind, e in errs.items())
    info = (f"window {window_s:.3f} s wall after {n_warm} warm-up "
            f"requests: {watch.routed} routed, {len(watch.finished)} "
            f"finished, {queued} still queued at the close, {out_tokens} "
            f"output tokens, {len(watch.steps)} engine steps "
            f"({decode_s:.3f} s in decode); prefix reuse "
            f"{reused}/{prompt_tokens} prompt tokens; TTFT of the finished "
            f"p50 {pct(ttft, 50) * 1e3:.1f} ms, p90 {pct(ttft, 90) * 1e3:.1f}"
            f" ms (queueing above the knee); reference over {len(pick)} "
            f"sequences ({sum(g['tokens'] for g in gaps)} tokens) and the "
            f"tapped (row, layer) pairs, {tap_info}, in {ref_s:.1f} s; "
            f"routings within 1e-3 of a tie at the "
            f"{config['num_experts_per_tok']}th expert {100 * near:.4f}%; "
            f"device peak {memory_peak} B")
    return {
        "setup_s": setup_s,
        "e2e": e2e,
        "ctx": {"steps": watch.steps, "window_s": window_s,
                "reused": reused, "prompt_tokens": prompt_tokens},
        "attempted": watch.routed, "failed": short,
        "memory_peak_bytes": memory_peak,
        "checks": checks, "info": info,
        "near_ties": near,
    }
