"""The one traffic generator: every mix under ``bench/traffic/`` is a JSON
file of parameters that this module reads.

Two kinds of mix, named by the file's ``"kind"``:

* ``"route_sessions"`` -- an open-loop trace of multi-turn sessions for
  the routing cells.  Prompts are chains of abstract block ids (one id per
  ``block_tokens`` tokens): an app prefix shared by every session of the
  app, then per-session history that grows turn by turn (each turn adds
  fresh input blocks and the previous answer's blocks).  The structure is
  ``repro.workloads.traces.make_trace``'s, copied here so that the
  yardstick cannot move with the program.
* ``"served_open_loop"`` -- token-level arrivals for the served cells:
  ``(time, prompt_tokens, output_len)`` tuples on the cluster's virtual
  clock.  Every prompt is whole ``block_tokens`` blocks: an app prefix of
  ``prefix_tokens`` shared by the app's requests, then fresh tokens.

Sizes and arrival times are drawn from the mix's fixed ``shape_seed``, so
every ``--seed`` gets the same set of sizes and arrivals; the seed only
permutes which request or session takes which slot (a served request
only within its block of ``order_block`` slots), and draws the token
contents.  That keeps the work of a run the same from seed to seed.

The routing cells' service model (``ServiceModel``) lives here too: it
turns each routed request into its completion events on the trace clock.
"""
from __future__ import annotations

import heapq
import math
from typing import List, Tuple

import numpy as np

from common import rng


def _zipf_p(n: int, a: float) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1) ** a
    return p / p.sum()


def _gamma_gaps(g, n: int, rate: float, cv: float) -> np.ndarray:
    """``n`` inter-arrival gaps of a gamma renewal process with mean
    ``1/rate`` and coefficient of variation ``cv``."""
    shape = 1.0 / (cv * cv)
    return g.gamma(shape, 1.0 / (shape * rate), size=n)


def _out_lens(g, n: int, mean: float, cv: float) -> np.ndarray:
    # make_trace's lognormal: median at the mean, sigma = cv * 0.7
    return np.maximum(2, g.lognormal(math.log(mean), cv * 0.7,
                                     size=n).astype(np.int64))


# ---------------------------------------------------------------------------
# routing cells: session traces of abstract blocks
# ---------------------------------------------------------------------------
def route_trace(mix: dict, seed: int, n_requests: int):
    """The first ``n_requests`` arrivals of the mix's session trace, as
    ``repro.core.types.Request`` objects sorted by arrival (rid = index).

    Sessions start up to ``lead_seconds`` before the trace does, and only
    their turns from time 0 on are kept, so the arrival rate is the mix's
    ``rate`` from the first arrival on (no ramp while sessions pile up).
    """
    from repro.core.types import Request

    shape = np.random.default_rng(mix["shape_seed"])
    rate = float(mix["rate"])
    turns_mean = float(mix["turns_mean"])
    lead = float(mix.get("lead_seconds", 0.0))
    sess_rate = rate / max(turns_mean, 1.0)
    # enough session starts that their turns cover n_requests arrivals
    # (later turns of the last sessions fall past the cut)
    n_sess = int(n_requests / max(turns_mean, 1.0) * 1.6 + lead * sess_rate) + 64
    starts = np.cumsum(_gamma_gaps(shape, n_sess, sess_rate,
                                   mix["arrival_cv"])) - lead
    app_p = _zipf_p(mix["n_apps"], mix["zipf_a"])
    apps = shape.choice(mix["n_apps"], size=n_sess, p=app_p)
    if turns_mean <= 1.0:
        turns = np.ones(n_sess, np.int64)
    else:
        turns = np.maximum(1, shape.poisson(turns_mean, size=n_sess))
    n_turns = int(turns.sum())
    first = np.maximum(1, shape.poisson(mix["first_input_blocks"],
                                        size=n_sess))
    later = np.maximum(1, shape.poisson(mix.get("turn_input_blocks", 1),
                                        size=n_turns))
    outs = _out_lens(shape, n_turns, mix["output_tokens_mean"],
                     mix["output_tokens_cv"])
    think = (np.maximum(0.5, shape.exponential(mix.get("think_time_mean", 1.0),
                                               size=n_turns))
             + outs * mix.get("gen_seconds_per_token", 0.02))

    # the seed decides which session takes which start slot
    order = rng(seed, 1).permutation(n_sess)
    bt = int(mix["block_tokens"])
    next_id = 1
    prefixes = []
    for _ in range(mix["n_apps"]):
        nb = int(mix["app_prefix_blocks"])
        prefixes.append(list(range(next_id, next_id + nb)))
        next_id += nb
    turn_lo = np.concatenate([[0], np.cumsum(turns)[:-1]])
    rows: List[Tuple[float, tuple, int]] = []
    for slot in range(n_sess):
        s = int(order[slot])
        t = float(starts[slot])
        history = list(prefixes[int(apps[s])])
        for j in range(int(turns[s])):
            ti = int(turn_lo[s]) + j
            nb = int(first[s]) if j == 0 else int(later[ti])
            history.extend(range(next_id, next_id + nb))
            next_id += nb
            out = int(outs[ti])
            if t >= 0.0:
                rows.append((t, tuple(history), out))
            na = max(1, out // bt)           # the answer joins the context
            history.extend(range(next_id, next_id + na))
            next_id += na
            t += float(think[ti])
    rows.sort(key=lambda r: r[0])
    if len(rows) < n_requests:
        raise ValueError(f"mix yields {len(rows)} < {n_requests} requests")
    return [Request(rid=i, arrival=t, blocks=b, prompt_len=len(b) * bt,
                    output_len=o)
            for i, (t, b, o) in enumerate(rows[:n_requests])]


class ServiceModel:
    """What the fleet does with a routed request, on the trace clock.

    Each instance prefills its requests one after another at
    ``prefill_tokens_per_s`` (only the tokens its cache misses), emits the
    first token when the request's prefill ends, then decodes
    ``output_len`` tokens at ``tpot_s`` each.  ``push`` schedules those two
    events; ``due(now)`` yields the events at or before ``now`` in time
    order (ties in push order), as ``(kind, iid, request_index)`` with
    kind ``"first"`` or ``"finish"``.
    """

    def __init__(self, mix: dict, n_instances: int):
        svc = mix["service"]
        self.prefill_rate = float(svc["prefill_tokens_per_s"])
        self.tpot = float(svc["tpot_s"])
        self.free_at = np.zeros(n_instances)
        self._heap: list = []
        self._seq = 0

    def push(self, now: float, iid: int, j: int, new_tokens: int,
             output_len: int):
        t_first = max(now, self.free_at[iid]) + new_tokens / self.prefill_rate
        self.free_at[iid] = t_first
        t_fin = t_first + output_len * self.tpot
        heapq.heappush(self._heap, (t_first, self._seq, "first", iid, j))
        heapq.heappush(self._heap, (t_fin, self._seq + 1, "finish", iid, j))
        self._seq += 2

    def due(self, now: float):
        h = self._heap
        while h and h[0][0] <= now:
            _, _, kind, iid, j = heapq.heappop(h)
            yield kind, iid, j

    def pending(self) -> int:
        return len(self._heap)


# ---------------------------------------------------------------------------
# served cells: token arrivals on the cluster's virtual clock
# ---------------------------------------------------------------------------
def served_arrivals(mix: dict, seed: int, n: int, vocab: int):
    """The first ``n`` arrivals of the mix, at ``rate`` requests per
    virtual second.  Returns a list of ``(time, prompt_tokens int32
    array, output_len)``."""
    shape = np.random.default_rng(mix["shape_seed"])
    rate = float(mix["rate"])
    times = np.cumsum(_gamma_gaps(shape, n, rate, mix["arrival_cv"]))
    times -= times[0]
    bt = int(mix["block_tokens"])
    prefix = int(mix["prefix_tokens"])
    fresh = np.maximum(1, shape.poisson(mix["fresh_tokens_mean"] / bt,
                                        size=n)) * bt
    outs = _out_lens(shape, n, mix["output_tokens_mean"],
                     mix["output_tokens_cv"])
    n_apps = int(mix["n_apps"])
    apps = shape.choice(n_apps, size=n, p=_zipf_p(n_apps, mix["zipf_a"]))
    limit = int(mix["max_total_tokens"])
    fresh = np.minimum(fresh, (limit - prefix - 2) // bt * bt)
    outs = np.minimum(outs, limit - prefix - fresh)

    tok = rng(seed, 2)
    # which request takes which slot: a request moves only within its
    # block of ``order_block`` consecutive slots, so every prefix of the
    # arrivals holds nearly the same work under every seed (a window
    # above the knee serves only the earliest arrivals)
    block = int(mix["order_block"])
    perm = np.concatenate([lo + tok.permutation(min(block, n - lo))
                           for lo in range(0, n, block)])
    prefixes = tok.integers(0, vocab, size=(n_apps, prefix), dtype=np.int32)
    shared = bool(mix["prefix_shared"])
    out = []
    for slot in range(n):
        i = int(perm[slot])
        head = (prefixes[int(apps[i])] if shared else
                tok.integers(0, vocab, size=prefix, dtype=np.int32))
        body = tok.integers(0, vocab, size=int(fresh[i]), dtype=np.int32)
        out.append((float(times[slot]), np.concatenate([head, body]),
                    int(outs[i])))
    return out
