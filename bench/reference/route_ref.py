"""Plain reference for the routing cells: LMetric over per-instance radix
trees, one request at a time.

Written from the paper's rule (LMetric, arXiv:2603.15202, Fig. 17b) and
the router's stated contract, without importing the program:

* each instance keeps a radix tree of the block chains routed to it; a
  request's hit on an instance is the depth of its longest cached prefix
  times the block size, capped at the prompt length;
* the tree evicts least-recently-used leaves once it holds more than
  ``capacity_tokens`` (a node's last use is the last insert through it);
* the score of instance i is ``(P_i + 1) * (BS_i + 1)`` with
  ``P_i = queued_prefill_tokens_i + prompt_len - hit_i`` and
  ``BS_i = running_i + queued_i``, in float64;
* the decision is the lowest score; scores within 1e-9 of it tie, and
  the ties are taken round-robin in ascending instance order by a
  counter that advances once per decision.

``Replay`` follows a run's own decisions (teacher forcing): it applies
each decision to its own state, checks the hit the program reported for
every request, and at sampled requests works out the whole decision
itself and compares.  So each sampled decision is checked against the
same history the program saw, and the cost stays linear in the run.
"""
from __future__ import annotations

import heapq
from typing import Dict, Iterable, Sequence

import numpy as np

EPS = 1e-9


class _Node:
    __slots__ = ("children", "parent", "key", "last_use")

    def __init__(self, parent, key):
        self.children: Dict[int, "_Node"] = {}
        self.parent = parent
        self.key = key
        self.last_use = 0


class Tree:
    """One instance's prefix cache."""

    def __init__(self, block_tokens: int, capacity_tokens: int):
        self.bt = block_tokens
        self.capacity = capacity_tokens
        self.root = _Node(None, None)
        self.n_blocks = 0
        self.leaves = set()

    def depth(self, blocks: Sequence[int]) -> int:
        node, d = self.root, 0
        for b in blocks:
            node = node.children.get(b)
            if node is None:
                break
            d += 1
        return d

    def insert(self, blocks: Sequence[int], tick: int):
        node = self.root
        for b in blocks:
            child = node.children.get(b)
            if child is None:
                child = _Node(node, b)
                node.children[b] = child
                self.leaves.discard(node)
                self.leaves.add(child)
                self.n_blocks += 1
            child.last_use = tick
            node = child
        if self.n_blocks * self.bt > self.capacity:
            self._evict()

    def _evict(self):
        heap = [(n.last_use, id(n), n) for n in self.leaves]
        heapq.heapify(heap)
        while self.n_blocks * self.bt > self.capacity and heap:
            _, _, leaf = heapq.heappop(heap)
            parent = leaf.parent
            del parent.children[leaf.key]
            self.leaves.discard(leaf)
            self.n_blocks -= 1
            if parent is not self.root and not parent.children:
                self.leaves.add(parent)
                heapq.heappush(heap, (parent.last_use, id(parent), parent))


class LMetricRef:
    """The fleet's state as the reference sees it."""

    def __init__(self, n: int, block_tokens: int, capacity_tokens: int):
        self.n = n
        self.bt = block_tokens
        self.trees = [Tree(block_tokens, capacity_tokens) for _ in range(n)]
        self.running = np.zeros(n, np.int64)
        self.queued = np.zeros(n, np.int64)
        self.queued_prefill = np.zeros(n, np.int64)
        self.decisions = 0               # the round-robin tie counter
        self.tick = 0

    def hit(self, iid: int, blocks, prompt_len: int) -> int:
        return min(self.trees[iid].depth(blocks) * self.bt, prompt_len)

    def scores(self, blocks, prompt_len: int, dtype=np.float64):
        hits = np.array([self.hit(i, blocks, prompt_len)
                         for i in range(self.n)], np.int64)
        a = (self.queued_prefill + (prompt_len - hits)).astype(dtype) \
            + dtype(1.0)
        b = (self.running + self.queued).astype(dtype) + dtype(1.0)
        return a * b

    def pick(self, s: np.ndarray, round_robin: bool = True) -> int:
        best = s.min()
        ties = np.flatnonzero(s <= best + s.dtype.type(EPS))
        return int(ties[self.decisions % len(ties)] if round_robin
                   else ties[0])

    def decide(self, blocks, prompt_len: int, dtype=np.float64,
               round_robin: bool = True) -> int:
        return self.pick(self.scores(blocks, prompt_len, dtype), round_robin)

    # ---- what happens to the fleet --------------------------------------
    def commit(self, iid: int, blocks, prompt_len: int) -> int:
        hit = self.hit(iid, blocks, prompt_len)
        self.queued[iid] += 1
        self.queued_prefill[iid] += prompt_len - hit
        self.tick += 1
        self.trees[iid].insert(blocks, self.tick)
        self.decisions += 1
        return hit

    def first_token(self, iid: int, new_tokens: int):
        self.queued_prefill[iid] = max(self.queued_prefill[iid] - new_tokens,
                                       0)
        self.queued[iid] = max(self.queued[iid] - 1, 0)
        self.running[iid] += 1

    def finish(self, iid: int):
        self.running[iid] = max(self.running[iid] - 1, 0)


#: controls: the reference with one step of its rule changed
CONTROLS = {
    "float32": dict(dtype=np.float32),
    "first_min": dict(round_robin=False),
}


def replay(reqs, decisions: Sequence[int], hits: Sequence[int], n: int,
           block_tokens: int, capacity_tokens: int, service, wave: int,
           samples: Iterable[int], controls: Sequence[str] = ()) -> dict:
    """Follow a run's decisions through the reference.

    ``reqs`` carry the inputs (``blocks``, ``prompt_len``, ``output_len``,
    ``arrival``) in routing order; ``decisions``/``hits`` are what the
    program returned for each.  ``service`` is a fresh
    ``generate.ServiceModel`` of the run's mix.  Returns counts: sampled
    decisions checked and mismatched, hits mismatched (every request),
    and for each named control its sampled mismatches against the
    reference's own decision."""
    ref = LMetricRef(n, block_tokens, capacity_tokens)
    samples = set(samples)
    out = {"checked": 0, "mismatches": 0, "hit_mismatches": 0,
           "bad_decisions": 0, "max_best_score": 0.0}
    ctl = {c: 0 for c in controls}
    new = np.zeros(len(reqs), np.int64)
    for lo in range(0, len(reqs), wave):
        w = reqs[lo:lo + wave]
        now = w[-1].arrival
        for kind, iid, j in service.due(now):
            if kind == "first":
                ref.first_token(iid, int(new[j]))
            else:
                ref.finish(iid)
        for j in range(lo, lo + len(w)):
            r = reqs[j]
            iid = int(decisions[j])
            if not 0 <= iid < n:
                out["bad_decisions"] += 1
                continue
            if j in samples:
                s = ref.scores(r.blocks, r.prompt_len)
                want = ref.pick(s)
                out["checked"] += 1
                out["mismatches"] += int(want != iid)
                out["max_best_score"] = max(out["max_best_score"],
                                            float(s.min()))
                for c in controls:
                    ctl[c] += int(ref.decide(r.blocks, r.prompt_len,
                                             **CONTROLS[c]) != want)
            hit = ref.commit(iid, r.blocks, r.prompt_len)
            out["hit_mismatches"] += int(hit != int(hits[j]))
            new[j] = r.prompt_len - hit
            service.push(now, iid, j, int(new[j]), r.output_len)
    out["controls"] = ctl
    return out


def sample_indices(seed: int, lo: int, hi: int, k: int) -> np.ndarray:
    """``k`` request indices in ``[lo, hi)`` drawn from the seed."""
    rng = np.random.default_rng([seed, 0x5EED])
    return np.sort(rng.choice(np.arange(lo, hi), size=min(k, hi - lo),
                              replace=False))
