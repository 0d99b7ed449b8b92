"""Indicator factory (paper §3, Fig. 4) — structure-of-arrays core.

The factory exposes the *direct system indicators* of Fig. 2:

  R-BS   running batch size
  Q-BS   queued batch size
  BS     R-BS + Q-BS
  P_tokens   queued new-prefill tokens (decremented as prefill proceeds)
  #Tokens    total context tokens resident on the instance
  KV$        per-instance prefix-cache index (radix tree)

Array contract
--------------
All scalar indicators live in contiguous ``numpy`` int64 arrays on the
factory itself — one slot per instance, updated **in place** by the
instance hooks:

  ``factory.r_bs``                    shape (n,)   running batch sizes
  ``factory.q_bs``                    shape (n,)   queued batch sizes
  ``factory.queued_prefill_tokens``   shape (n,)   queued new-prefill tokens
  ``factory.total_tokens``            shape (n,)   resident context tokens
  ``factory.bs_vector()``             shape (n,)   R-BS + Q-BS (fresh array)
  ``factory.hits_for(req)``           shape (n,)   per-instance KV$ hit tokens

Policies score by vectorized expressions over these arrays (LMetric's
``(p_token + 1) * (bs + 1)`` is two fused array ops); nothing in the
scoring path walks per-instance Python objects.  The arrays are the
substrate the jitted wave loop (``repro.kernels.route_score``) scores
for batch routing.

``InstanceState`` remains the mutation interface — it is a *view* over
one column of the factory's arrays (attribute reads/writes hit the
arrays directly), so the existing update hooks, the cluster simulator,
the in-process JAX engine, and tests that poke ``f[i].r_bs = 5`` all
keep working unchanged.

Vectorized KV$ hits
-------------------
``hits_for`` is backed by an aggregated prefix index shared across the
factory: a *flat* structure-of-arrays radix tree whose per-node
instance membership is one row of a ``(capacity, ceil(n/64))`` uint64
bitset matrix (bit i set ⇔ instance i's own tree contains that block
chain) — see ``AggregatedPrefixIndex`` for the layout and the
walk-reuse invariant.  A single walk down the prompt yields every
instance's hit depth; per-instance LRU clocks and capacity eviction
stay in the per-instance trees, which keep the aggregate coherent
through the ``RadixKVIndex`` on_insert/on_evict callbacks.
``exact_only`` factories (recurrent-state semantics) fall back to the
per-instance scalar walk, which the aggregate cannot model.  The
factory accumulates host walk telemetry (``walk_ns`` / ``walks``) so
benchmarks can report the per-walk cost the flat index optimises
(``Router.mean_walk_us``).

Past ~4k instances the factory shards the aggregate by instance-id
range (``n_shards > 1`` builds a
``repro.core.sharded_index.ShardedPrefixIndex`` — S independent flat
indexes whose per-shard hit vectors concatenate into the same
full-width arrays, bit-identical to the unsharded index); per-shard
walk telemetry surfaces through ``shard_walk_stats`` /
``Router.walk_telemetry``.

Device mirror & dirty-flag sync contract
----------------------------------------
Batch routing (``Router.route_batch``) scores whole arrival waves on
device.  The factory therefore keeps a **device mirror** of the four
scalar indicator arrays (partitioned by the same instance-id ranges as
the prefix index, one dirty flag per shard):

* ``device_view()`` returns ``(r_bs, q_bs, queued_prefill_tokens,
  total_tokens)`` as jax arrays (int64 — created under
  ``repro.kernels.route_score.x64()``), re-uploading **only the shards
  whose dirty flag is set** and caching the rest (with one shard —
  the default — that degenerates to the original whole-array
  behaviour).
* Every built-in mutation path — the ``InstanceState`` update hooks and
  its property setters — stays an in-place numpy write and flips the
  owning shard's flag via ``mark_dirty(iid)``.  Code that writes
  ``factory.r_bs[...]`` (or the siblings) directly MUST call
  ``factory.mark_dirty()`` (all shards, conservative) or
  ``factory.mark_dirty(iid)`` (just the touched shard) afterwards;
  that is the entire synchronization contract, and it is what every
  future on-device scheduling feature builds on.
* The mirror is read-only: device code never writes indicators back.
  Decisions return to the host and are committed through the same
  hooks, so the numpy arrays remain the single source of truth.

``docs/ARCHITECTURE.md`` states this contract (and the subset
invariant below) as the two load-bearing invariants of the routing
stack — read it before building on either.

``evictions`` counts per-instance KV$ leaf evictions (and full clears).
The batched routing plan models intra-wave cache growth exactly but
cannot model mid-wave *eviction*; ``Router.route_batch`` snapshots this
counter and falls back to sequential host routing the moment it moves —
this is also when ``route_batch`` falls back entirely: ``exact_only``
factories (no aggregated index), policies without a device kind
(simulator-based llm-d/PolyServe, Dynamo's normalised blend, Preble's
windowed fallback), an attached hotspot detector, the "cost" load
indicator, or a router with ``insert_on_route=False`` (the intra-wave
LCP credit models inserts that would never happen) all take the
documented host path instead.

Wave inputs (``wave_inputs``) are the host-side half of the batch path:
one aggregated-index walk per *unique* prompt in the wave (duplicates
share a row) plus the pairwise longest-common-prefix matrix that lets
the device credit intra-wave inserts.

Preble window bookkeeping
-------------------------
Per-instance routed-request windows (Preble's 3-minute fallback) live in
fixed-size numpy ring buffers on the factory (``_log_t``/``_log_p`` with
per-instance start/length cursors, doubling on overflow).  The
``InstanceState.routed_log`` list API and ``trim_log`` keep their exact
pre-ring semantics (drop the *leading* run older than the window), so
the frozen scalar reference reads them unchanged; ``window_stats``
exposes the vectorized trim+sum+count the Preble fallback scores with.

Updates are piggybacked on instance responses in a real deployment; the
cluster simulator and the in-process JAX engine call the same hooks.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .radix import RadixKVIndex
from .types import Request

_WORD_BITS = 64
#: bitset word dtype pinned to little-endian so the ``view(np.uint8)``
#: decode in the scatters is platform-independent (the frozen bigint
#: reference uses explicit little-endian ``int.to_bytes``); on LE hosts
#: this is bit-for-bit the native uint64
_WORD = np.dtype("<u8")


def shard_bounds(n_instances: int, n_shards: int) -> List[Tuple[int, int]]:
    """Contiguous instance-id ranges ``[lo, hi)``, one per shard, sizes
    within one of each other.  The single definition both the sharded
    prefix index and the factory's device-mirror partition use, so hit
    vectors and indicator slices always cut at the same boundaries."""
    return [((s * n_instances) // n_shards,
             ((s + 1) * n_instances) // n_shards)
            for s in range(n_shards)]


def shard_owner(n_instances: int, n_shards: int) -> np.ndarray:
    """``owner[i]`` = shard covering instance ``i`` under
    :func:`shard_bounds` — built here once so the sharded index's
    mutation routing and the factory's ``mark_dirty(iid)`` mirror
    partition can never disagree about ownership."""
    owner = np.empty(n_instances, dtype=np.int64)
    for s, (lo, hi) in enumerate(shard_bounds(n_instances, n_shards)):
        owner[lo:hi] = s
    return owner


# ---------------------------------------------------------------------------
# Anti-entropy digests (PR 9).  A shard's content digest is the
# commutative sum (mod 2^64) of one mixed hash per *membership bit* —
# pair (node chain-hash, local instance id) — over every live non-root
# node, plus the live node count and total bit count.  Each node's
# chain-hash is a pure function of its root→node block-key path
# (splitmix64 chaining), so three independent computations of the same
# logical state agree exactly: the incremental accumulator maintained
# by add/remove, a rescan of the bitset rows, and a replay of the
# canonical ``RadixKVIndex.chains()`` truth (``digest_from_chains``).
# Commutativity makes the incremental update O(changed bits) per
# mutation — the same asymptotics as the mutation itself.

_M64 = (1 << 64) - 1
#: arbitrary odd constant seeding the root's chain-hash
_ROOT_H = 0x27220A95FE1EADB5
#: odd multiplier for the per-bit digest term — a single multiply over
#: two already-mixed inputs keeps mutation-path upkeep to a few int ops
#: per changed bit (detection only needs commutative sums not to cancel,
#: not a full finalizer)
_PHI = 0x9E3779B97F4A7C15


def _mix64(x: int) -> int:
    """splitmix64 finalizer over arbitrary Python ints (numpy scalars
    coerced — a bare ``int64 & _M64`` would overflow)."""
    x = int(x) & _M64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _M64
    return x ^ (x >> 31)


def _chain_step(h: int, key: int) -> int:
    """One chain-hash round (root→node path hash): a single multiply +
    xorshift over the already-finalized parent hash — runs once per
    node *allocation* on the KV-insert path, so it must stay cheap.
    Kept in lockstep with the inlined copy in
    ``AggregatedPrefixIndex._alloc``."""
    x = ((h ^ key) * 0xBF58476D1CE4E5B9) & _M64
    return x ^ (x >> 31)


_IHASH_CACHE: Dict[int, int] = {}


def _ihash(iid: int) -> int:
    """Per-(local) instance-id hash, memoized — ids are small and dense
    so the cache stays bounded by the widest shard ever built."""
    h = _IHASH_CACHE.get(iid)
    if h is None:
        h = _mix64((iid + 1) * 0x9E3779B97F4A7C15)
        _IHASH_CACHE[iid] = h
    return h


def digest_from_chains(pairs) -> Tuple[int, int, int]:
    """Digest of the index a from-scratch rebuild over ``pairs`` —
    iterable of ``(local_iid, block_chain)`` from the per-instance
    ``RadixKVIndex.chains()`` truth — would produce.  Same triple as
    ``AggregatedPrefixIndex.digest``: (bit-sum, live nodes, total bits)."""
    acc, bits, nodes = 0, set(), set()
    for li, chain in pairs:
        h = _ROOT_H
        ih = _ihash(li)
        for b in chain:
            h = _chain_step(h, b)
            nodes.add(h)
            k = (h, li)
            if k not in bits:
                bits.add(k)
                acc = (acc + ((h ^ ih) * _PHI & _M64)) & _M64
    return (acc, len(nodes), len(bits))


class AggregatedPrefixIndex:
    """Flat, array-backed cross-instance prefix index.

    Nodes live in contiguous structure-of-arrays storage — a node is an
    integer row id, child lookup is one hash probe in the node's
    ``block_key -> child_row_id`` dict (``_kids[row]``, single-int
    hashing on the walk's hot path), and freed rows are recycled
    through a free list — so the index has no per-node Python objects
    and no arbitrary-precision mask arithmetic.

    Bitset layout
    -------------
    Per-node instance membership is one row of the ``(capacity,
    ceil(n/64))`` uint64 matrix ``_masks``: bit ``i`` of row ``nid``
    (little-endian within and across words) is set iff instance ``i``'s
    own radix tree contains the block chain ending at node ``nid``.
    Mask AND/ANDNOT and the ``match_depths`` scatter are vectorized
    numpy word ops, ``remove_instance`` is a single column clear — this
    removes the ~4k-instance ceiling of the old bigint masks (kept
    verbatim in ``repro.core._prefix_ref`` as the differential
    reference).

    The walk-reuse invariant
    ------------------------
    Because every per-instance chain is prefix-closed, a child's mask is
    always a **subset** of its parent's (``add`` marks whole chains;
    ``remove_leaf`` only ever clears a node that is a leaf *for that
    instance*, so no descendant still carries the bit).  Two
    consequences the fast paths lean on:

    * the live instance set at depth ``d`` of a walk is exactly the
      mask of the node at depth ``d`` (no running intersection), and
      mask *narrowing* is detected by comparing cached popcounts — one
      scalar read per step instead of an O(n/64) word op;
    * a walk's state at depth ``d`` — (node id, live set) — is a pure
      function of the first ``d`` blocks, so ``match_depths_many`` can
      sort a wave's chains lexicographically and resume each walk from
      the shared-prefix frontier of its predecessor (frame stack +
      narrowing-segment stack), paying one deep walk per *lineage*
      instead of one per chain.

    Callers must therefore only mutate through the ``RadixKVIndex``
    callback protocol (or preserve prefix-closure themselves); the
    invariant is what ``tests/test_prefix_index.py`` pins against the
    bigint reference.
    """

    __slots__ = ("n", "words", "_full", "_masks", "_pop", "_parent",
                 "_live", "_key", "_kids", "_free", "_top",
                 "_chash", "_dig", "_bits", "_dig_on")

    def __init__(self, n_instances: int, capacity: int = 256):
        self.n = n_instances
        self.words = (n_instances + _WORD_BITS - 1) // _WORD_BITS
        full = np.zeros(self.words, dtype=_WORD)
        nfull, rem = divmod(n_instances, _WORD_BITS)
        full[:nfull] = np.uint64(0xFFFFFFFFFFFFFFFF)
        if rem:
            full[nfull] = np.uint64((1 << rem) - 1)
        self._full = full
        cap = max(int(capacity), 2)
        # masks are the one vectorized structure; the scalar per-node
        # metadata lives in plain Python lists — the walk reads one pop
        # per step, and a list index is ~3x cheaper than a numpy scalar
        # read on that hot path
        self._masks = np.zeros((cap, self.words), dtype=_WORD)
        self._pop: List[int] = [0] * cap
        self._parent: List[int] = [-1] * cap
        self._live: List[bool] = [False] * cap
        self._key: List = [None] * cap
        # per-node child dict (block key -> child row id), indexed by
        # row id — hash-addressed lookup with single-int hashing on the
        # walk's hot path; None marks a freed row
        self._kids: List[Optional[Dict[int, int]]] = [None] * cap
        self._free: List[int] = []
        # per-node chain-hash (pure function of the root→node key path)
        # plus the incremental anti-entropy accumulator: sum of
        # mixed (chain-hash, iid) pairs over every membership bit.
        # Both are LAZY — zero mutation-path upkeep until the first
        # digest read reconstructs them (``_enable_digest``), then
        # maintained incrementally
        self._chash: List[int] = [0] * cap
        self._chash[0] = _ROOT_H
        self._dig = 0
        self._bits = 0
        self._dig_on = False
        # row 0 is the root, pinned to the full instance set so the
        # popcount narrowing check works from the very first block
        self._top = 1
        self._masks[0] = full
        self._pop[0] = n_instances
        self._live[0] = True
        self._kids[0] = {}

    @property
    def n_nodes(self) -> int:
        """Live nodes, excluding the root."""
        return sum(self._live) - 1

    # ---- storage ------------------------------------------------------
    def _grow(self):
        cap = self._masks.shape[0]
        masks = np.zeros((2 * cap, self.words), dtype=_WORD)
        masks[:cap] = self._masks
        self._masks = masks
        self._pop.extend([0] * cap)
        self._parent.extend([-1] * cap)
        self._live.extend([False] * cap)
        self._key.extend([None] * cap)
        self._kids.extend([None] * cap)
        self._chash.extend([0] * cap)

    def _alloc(self, parent: int, key) -> int:
        if self._free:
            nid = self._free.pop()
        else:
            nid = self._top
            if nid == self._masks.shape[0]:
                self._grow()
            self._top += 1
        self._masks[nid] = 0
        self._pop[nid] = 0
        self._parent[nid] = parent
        self._live[nid] = True
        self._key[nid] = key
        self._kids[nid] = {}
        if self._dig_on:
            # inlined ``_chain_step`` (keep in lockstep) — on the
            # KV-insert path once per node allocation
            x = ((self._chash[parent] ^ key) * 0xBF58476D1CE4E5B9) & _M64
            self._chash[nid] = x ^ (x >> 31)
        return nid

    def _free_node(self, nid: int) -> int:
        """Recycle a dead node; returns its parent id."""
        parent = self._parent[nid]
        del self._kids[parent][self._key[nid]]
        self._live[nid] = False
        self._parent[nid] = -1
        self._key[nid] = None
        self._kids[nid] = None
        self._free.append(nid)
        return parent

    # ---- mutation (RadixKVIndex callback protocol) --------------------
    def add(self, iid: int, blocks: Sequence[int]):
        """Mark the whole chain as present on instance ``iid``."""
        if not blocks:
            return
        kids = self._kids
        cur_kids = kids[0]
        node = 0
        path: List[int] = []
        append = path.append
        for b in blocks:
            child = cur_kids.get(b)
            if child is None:
                child = self._alloc(node, b)
                cur_kids[b] = child
            append(child)
            node = child
            cur_kids = kids[child]
        w = iid >> 6
        mbit = 1 << (iid & 63)
        mitem = self._masks.item       # bound after _alloc may have grown
        # subset invariant: the nodes already holding the bit form a
        # prefix of the path — binary-search the boundary instead of
        # reading every node's mask
        lo, hi = 0, len(path)
        while lo < hi:
            mid = (lo + hi) // 2
            if mitem(path[mid], w) & mbit:
                lo = mid + 1
            else:
                hi = mid
        if lo < len(path):
            fresh = path[lo:]
            ids = np.fromiter(fresh, np.int64, len(fresh))
            self._masks[ids, w] |= np.uint64(mbit)
            pop = self._pop
            if self._dig_on:
                chash, ih = self._chash, _ihash(iid)
                dig = self._dig
                for nid in fresh:
                    pop[nid] += 1
                    dig += (chash[nid] ^ ih) * _PHI & _M64
                self._dig = dig & _M64
                self._bits += len(fresh)
            else:
                for nid in fresh:
                    pop[nid] += 1

    def remove_leaf(self, iid: int, path: Sequence[int]):
        """Instance ``iid`` evicted the leaf at ``path`` (root→leaf keys).

        Only the final node loses the bit — ancestors are still cached
        (radix eviction removes leaves only, so chains stay prefix-closed
        and the subset invariant holds).
        """
        self.remove_tail(iid, path, 1)

    def remove_tail(self, iid: int, path: Sequence[int], m: int):
        """Instance ``iid`` evicted the deepest ``m`` blocks of ``path``
        bottom-up (one ``RadixKVIndex.on_evict`` run): the same work as
        ``remove_leaf`` on ``path[:len(path) - j]`` for ``j`` in
        ``range(m)``, in that order, with one descent instead of ``m``.

        A node a ``remove_leaf`` descent would not reach (missing from
        the index, or pruned by the previous step) is skipped, as that
        call would return early.
        """
        kids = self._kids
        node = depth = 0
        for b in path:
            child = kids[node].get(b)
            if child is None:
                break
            node = child
            depth += 1
        stop = max(len(path) - m, 0)      # depth of the deepest survivor
        if depth <= stop:
            return
        w = iid >> 6
        mbit = 1 << (iid & 63)
        masks, pop, parent = self._masks, self._pop, self._parent
        ih = _ihash(iid) if self._dig_on else 0
        while depth > stop:
            v = masks.item(node, w)
            if v & mbit:
                masks[node, w] = np.uint64(v & ~mbit)
                pop[node] -= 1
                if self._dig_on:
                    self._dig = (self._dig - ((self._chash[node] ^ ih)
                                              * _PHI & _M64)) & _M64
                    self._bits -= 1
            if pop[node] or kids[node]:
                node = parent[node]
                depth -= 1
            else:
                # prune the freed tail: no instance holds it, nothing
                # hangs off; the run's nodes it frees are skipped
                while node and not pop[node] and not kids[node]:
                    node = self._free_node(node)
                    depth -= 1

    def remove_instance(self, iid: int):
        """Instance ``iid`` cleared its whole cache: one vectorized
        column clear over every live row, then a cascade prune of the
        rows the clear killed."""
        w = iid >> 6
        bit = np.uint64(1 << (iid & 63))
        top = self._top
        col = self._masks[:top, w]
        pop, kids, live = self._pop, self._kids, self._live
        # row 0 (the pinned full root) is excluded; freed rows keep
        # stale masks until recycled, so filter by liveness
        hits = [nid for nid in np.flatnonzero((col & bit) != 0).tolist()
                if nid and live[nid]]
        if not hits:
            return
        col[np.fromiter(hits, np.int64, len(hits))] &= ~bit
        stack = []
        if self._dig_on:
            chash, ih, dig = self._chash, _ihash(iid), self._dig
            for nid in hits:
                pop[nid] -= 1
                dig -= (chash[nid] ^ ih) * _PHI & _M64
                if not pop[nid] and not kids[nid]:
                    stack.append(nid)
            self._dig = dig & _M64
            self._bits -= len(hits)
        else:
            for nid in hits:
                pop[nid] -= 1
                if not pop[nid] and not kids[nid]:
                    stack.append(nid)
        while stack:
            nid = stack.pop()
            if not live[nid] or pop[nid] or kids[nid]:
                continue
            parent = self._free_node(nid)
            if parent and not pop[parent] and not kids[parent]:
                stack.append(parent)

    # ---- anti-entropy (PR 9) ------------------------------------------
    def _enable_digest(self):
        """Deferred digest bring-up: chain hashes and the accumulator
        are reconstructed from the live tree on the first digest read,
        then maintained incrementally.  Mutations before that read pay
        zero digest upkeep — the Contract 5 discipline applied to
        anti-entropy: an index that is never verified must execute the
        exact pre-digest instruction sequence."""
        chash, kids = self._chash, self._kids
        stack = [0]
        while stack:
            nid = stack.pop()
            h = chash[nid]
            for key, child in kids[nid].items():
                x = ((h ^ key) * 0xBF58476D1CE4E5B9) & _M64
                chash[child] = x ^ (x >> 31)
                stack.append(child)
        self._dig_on = True
        dig, _, bits = self.rescan_digest()
        self._dig, self._bits = dig, bits

    @property
    def digest(self) -> Tuple[int, int, int]:
        """Incrementally-maintained content digest: ``(bit-sum mod 2^64,
        live non-root nodes, total membership bits)``.  Matches
        :meth:`rescan_digest` iff no mask word was corrupted *after the
        first digest read* (upkeep starts lazily — ``_enable_digest``),
        and :func:`digest_from_chains` over the KV truth iff no mutation
        was ever dropped or misapplied, before or after."""
        if not self._dig_on:
            self._enable_digest()
        return (self._dig, self.n_nodes, self._bits)

    def rescan_digest(self) -> Tuple[int, int, int]:
        """Recompute the digest triple from the live bitset rows (not
        the incremental accumulator) — a mismatch against ``digest``
        means a mask bit changed without going through add/remove."""
        if not self._dig_on:
            self._enable_digest()
        acc, bits, nodes = 0, 0, 0
        masks, chash = self._masks, self._chash
        for nid in range(1, self._top):
            if not self._live[nid]:
                continue
            nodes += 1
            row = masks[nid]
            if not row.any():
                continue
            idxs = np.flatnonzero(np.unpackbits(
                row.view(np.uint8), bitorder="little",
                count=self.n)).tolist()
            h = chash[nid]
            for i in idxs:
                acc += (h ^ _ihash(i)) * _PHI & _M64
            bits += len(idxs)
        return (acc & _M64, nodes, bits)

    def reset(self):
        """Drop every node (root stays pinned full) without reallocating
        the mask matrix — the in-place half of ``repair``: callers
        re-``add`` the canonical chains afterwards."""
        cap = self._masks.shape[0]
        self._masks[:] = 0
        self._pop = [0] * cap
        self._parent = [-1] * cap
        self._live = [False] * cap
        self._key = [None] * cap
        self._kids = [None] * cap
        self._free = []
        self._chash = [0] * cap
        self._chash[0] = _ROOT_H
        self._dig = 0
        self._bits = 0
        self._top = 1
        self._masks[0] = self._full
        self._pop[0] = self.n
        self._live[0] = True
        self._kids[0] = {}

    def corrupt_bit(self, seed: int) -> Optional[Tuple[int, int]]:
        """Fault-injection hook: deterministically flip one membership
        bit in a live non-root row *without* updating the pop cache or
        the digest accumulator — exactly the silent corruption the
        anti-entropy sweep exists to catch.  Returns ``(nid, iid)`` or
        None if the index is empty."""
        live = [nid for nid in range(1, self._top)
                if self._live[nid] and self._pop[nid]]
        if not live or not self.n:
            return None
        r = _mix64(seed ^ 0xB17F11B5)
        nid = live[r % len(live)]
        iid = (r >> 17) % self.n
        w = iid >> 6
        v = int(self._masks.item(nid, w)) ^ (1 << (iid & 63))
        self._masks[nid, w] = np.uint64(v)
        return (nid, iid)

    # ---- queries ------------------------------------------------------
    def _scatter(self, words: np.ndarray, depth: int, out: np.ndarray):
        bits = np.unpackbits(words.view(np.uint8), bitorder="little",
                             count=self.n)
        out[bits.astype(bool)] = depth

    def match_depths(self, blocks: Sequence[int],
                     out: Optional[np.ndarray] = None) -> np.ndarray:
        """Per-instance cached-prefix depth (in blocks) for ``blocks``."""
        if out is None:
            out = np.zeros(self.n, dtype=np.int64)
        else:
            out[:] = 0
        kids = self._kids
        pop = self._pop
        masks = self._masks
        node = 0
        cur_kids = kids[0]
        cur = self.n                 # popcount of the live set (= node's)
        d = 0
        segs: List[Tuple[np.ndarray, int]] = []
        alive = True
        for b in blocks:
            child = cur_kids.get(b)
            if child is None:
                break
            pc = pop[child]
            if pc != cur:            # subset invariant: strict narrowing
                if d:
                    segs.append((masks[node] & ~masks[child], d))
                if not pc:
                    alive = False
                    break
                cur = pc
            node = child
            cur_kids = kids[child]
            d += 1
        for words, dep in segs:
            self._scatter(words, dep, out)
        if alive and d:
            self._scatter(masks[node], d, out)
        return out

    def match_depths_many(self, chains: Sequence[Sequence[int]],
                          order: Optional[Sequence[int]] = None,
                          adj: Optional[np.ndarray] = None,
                          out: Optional[np.ndarray] = None) -> np.ndarray:
        """``match_depths`` for a whole wave of chains at once, with
        LCP-chained walk reuse.

        Chains are walked in lexicographic order; each walk resumes from
        the shared-prefix frontier of its predecessor (frame stack of
        node ids plus the stack of narrowing segments emitted along the
        current path), so a wave of requests sharing long lineages pays
        one deep walk instead of k.  Pass precomputed ``(order, adj)``
        from :func:`_sorted_lcp` to share the sort with the pairwise-LCP
        matrix; segment scatters batch into one ``unpackbits`` exactly
        like the per-chain version.  ``out`` (shape ``(k, n)``, zeroed
        here) lets the sharded index scatter each shard's result
        straight into its column slice of the full-width matrix instead
        of allocating and copying a per-shard temporary.
        """
        k = len(chains)
        if out is None:
            out = np.zeros((k, self.n), dtype=np.int64)
        else:
            out[:] = 0
        if k == 0:
            return out
        if order is None:
            order, adj = _sorted_lcp(chains)
        kids = self._kids
        pop = self._pop
        masks = self._masks
        rows: List[int] = []
        seg_words: List[np.ndarray] = []
        seg_depths: List[int] = []
        nodes = [0]      # frame stack: nodes[d] = node after d blocks
        # (descend_depth, lost_words, matched_depth) along current path
        loss: List[Tuple[int, np.ndarray, int]] = []
        for t, r in enumerate(order):
            blocks = chains[r]
            p = int(adj[t]) if t else 0
            if p > len(nodes) - 1:
                p = len(nodes) - 1
            del nodes[p + 1:]
            while loss and loss[-1][0] > p:
                loss.pop()
            node = nodes[p]
            cur_kids = kids[node]
            cur = pop[node]
            d = p
            empty = False
            for b in blocks[d:]:
                child = cur_kids.get(b)
                if child is None:
                    break
                pc = pop[child]
                if pc != cur:
                    if d:
                        loss.append(
                            (d + 1, masks[node] & ~masks[child], d))
                    if not pc:
                        empty = True
                        break
                    cur = pc
                node = child
                cur_kids = kids[child]
                nodes.append(child)
                d += 1
            for _, words, md in loss:
                rows.append(r)
                seg_words.append(words)
                seg_depths.append(md)
            if not empty and d:
                rows.append(r)
                seg_words.append(masks[node])
                seg_depths.append(d)
        if rows:
            buf = np.empty((len(seg_words), self.words), dtype=_WORD)
            for i, wds in enumerate(seg_words):
                buf[i] = wds
            bits = np.unpackbits(buf.view(np.uint8), axis=1,
                                 bitorder="little",
                                 count=self.n).astype(bool)
            # a handful of segments per chain: masked row assignment
            # (disjoint masks) beats ufunc.at and broadcast-multiply
            # reductions by ~10x
            for i, r in enumerate(rows):
                out[r][bits[i]] = seg_depths[i]
        return out


def _lcp_block(chains: Sequence[Sequence[int]], out: np.ndarray,
               idxs: Sequence[int], max_elems: int = 4_000_000):
    """Brute-force pairwise LCP of ``chains[idxs]`` scattered into
    ``out``: pad to (g, L), compare all pairs, count the leading run of
    equal positions, row-tiled to bound the (rows, g, L) temporary.

    O(g²·L) — superseded by the sorted running-minimum reconstruction in
    :func:`_pairwise_lcp`, and kept as its differential reference
    (``tests/test_batch_routing.py::test_lcp_tiling_matches_untiled``).
    """
    g = len(idxs)
    lens = np.fromiter((len(chains[i]) for i in idxs), np.int64, g)
    L = int(lens.max())
    B = np.zeros((g, L), dtype=np.int64)
    for row, i in enumerate(idxs):
        B[row, : len(chains[i])] = chains[i]
    has = np.arange(L)[None, :] < lens[:, None]
    idxs = np.asarray(idxs)
    step = max(1, max_elems // max(g * L, 1))
    for r0 in range(0, g, step):
        r1 = min(r0 + step, g)
        eq = (B[r0:r1, None, :] == B[None, :, :]) \
            & has[r0:r1, None, :] & has[None, :, :]
        out[np.ix_(idxs[r0:r1], idxs)] = np.cumprod(
            eq, axis=2, dtype=np.int8).sum(axis=2, dtype=np.int64)


def _lcp_pair(a: Sequence[int], b: Sequence[int]) -> int:
    """LCP of two chains by galloping + binary search over C-level
    tuple-slice equality — O(lcp·log) pointer compares, no per-element
    Python arithmetic (chains carry ~2^60 block ids, so element-wise
    Python loops and numpy int conversion both cost more than slice
    compares)."""
    m = min(len(a), len(b))
    if m == 0 or a[0] != b[0]:
        return 0
    lo, k = 1, 2                        # a[:lo] == b[:lo] holds
    while k < m and a[:k] == b[:k]:
        lo, k = k, 2 * k
    if k >= m:
        if a[:m] == b[:m]:
            return m
        hi = m
    else:
        hi = k
    while lo + 1 < hi:
        mid = (lo + hi) // 2
        if a[:mid] == b[:mid]:
            lo = mid
        else:
            hi = mid
    return lo


def _sorted_lcp(chains: Sequence[Sequence[int]]
                ) -> Tuple[List[int], np.ndarray]:
    """Lexicographic sort order + adjacent-LCP array for a wave.

    ``order[t]`` indexes chains in sorted order; ``adj[t]`` is the LCP
    (in blocks) of sorted chains ``t-1`` and ``t`` (``adj[0] = 0``).
    Sorting makes each chain's LCP with its predecessor maximal over all
    earlier chains — the property both the walk reuse and the pairwise
    running-minimum reconstruction rely on.
    """
    u = len(chains)
    order = sorted(range(u), key=chains.__getitem__)
    adj = np.zeros(u, dtype=np.int64)
    for t in range(1, u):
        adj[t] = _lcp_pair(chains[order[t - 1]], chains[order[t]])
    return order, adj


def _pairwise_lcp(chains: Sequence[Sequence[int]],
                  order: Optional[Sequence[int]] = None,
                  adj: Optional[np.ndarray] = None) -> np.ndarray:
    """Pairwise longest-common-prefix (in blocks) of block-id chains.

    Reconstructed from the sorted adjacent-LCP array: for sorted chains,
    ``LCP(t, t') = min(adj[t+1..t'])``, so the matrix is u running-
    minimum sweeps (O(u²) total) instead of the old padded all-pairs
    compare (O(u²·L)).  Pass the ``(order, adj)`` pair from
    :func:`_sorted_lcp` to share the sort with ``match_depths_many``.
    """
    u = len(chains)
    if u == 0:
        return np.zeros((0, 0), dtype=np.int64)
    if order is None:
        order, adj = _sorted_lcp(chains)
    M = np.zeros((u, u), dtype=np.int64)
    for t in range(u - 1):
        M[t, t + 1:] = np.minimum.accumulate(adj[t + 1:])
    M += M.T
    lens = np.fromiter((len(chains[i]) for i in order), np.int64, u)
    np.fill_diagonal(M, lens)
    rank = np.empty(u, dtype=np.int64)
    rank[np.fromiter(order, np.int64, u)] = np.arange(u)
    return M[np.ix_(rank, rank)]


class InstanceState:
    """Per-instance view over one column of the factory's arrays.

    Scalar indicator attributes (``r_bs`` …) read and write the shared
    numpy arrays in place, so per-instance hooks and direct attribute
    pokes stay coherent with the vectorized scoring path.
    """

    __slots__ = ("iid", "_f", "kv")

    def __init__(self, iid: int, factory: "IndicatorFactory",
                 kv: RadixKVIndex):
        self.iid = iid
        self._f = factory
        self.kv = kv

    # ---- indicator reads/writes (array-backed) ---------------------------
    @property
    def r_bs(self) -> int:
        return int(self._f.r_bs[self.iid])

    @r_bs.setter
    def r_bs(self, v: int):
        self._f.r_bs[self.iid] = v
        self._f.mark_dirty(self.iid)

    @property
    def q_bs(self) -> int:
        return int(self._f.q_bs[self.iid])

    @q_bs.setter
    def q_bs(self, v: int):
        self._f.q_bs[self.iid] = v
        self._f.mark_dirty(self.iid)

    @property
    def queued_prefill_tokens(self) -> int:
        return int(self._f.queued_prefill_tokens[self.iid])

    @queued_prefill_tokens.setter
    def queued_prefill_tokens(self, v: int):
        self._f.queued_prefill_tokens[self.iid] = v
        self._f.mark_dirty(self.iid)

    @property
    def total_tokens(self) -> int:
        return int(self._f.total_tokens[self.iid])

    @total_tokens.setter
    def total_tokens(self, v: int):
        self._f.total_tokens[self.iid] = v
        self._f.mark_dirty(self.iid)

    @property
    def routed_log(self) -> List:
        """(time, p_tokens) of windowed routed requests, oldest first.

        Reconstructed from the factory ring buffer; list semantics (and
        the frozen scalar reference that iterates it) are unchanged.
        """
        return self._f.routed_window(self.iid)

    @property
    def bs(self) -> int:
        return self.r_bs + self.q_bs

    def kv_hit(self, req: Request, touch: bool = False) -> int:
        return self.kv.match(req.blocks, req.prompt_len, touch=touch)

    def p_token(self, req: Request, hit: Optional[int] = None) -> int:
        """Paper Fig. 17(b): queued new-prefill tokens if routed here."""
        if hit is None:
            hit = self.kv_hit(req)
        return self.queued_prefill_tokens + (req.prompt_len - hit)

    # ---- update hooks (called by router / engine / simulator) ------------
    def on_route(self, req: Request, now: float, hit: int):
        f, i = self._f, self.iid
        f.q_bs[i] += 1
        f.queued_prefill_tokens[i] += req.prompt_len - hit
        f.total_tokens[i] += req.prompt_len
        f.mark_dirty(i)
        f.log_routed(i, now, req.prompt_len - hit)

    def on_prefill_progress(self, n_tokens: int):
        f, i = self._f, self.iid
        left = f.queued_prefill_tokens[i] - n_tokens
        f.queued_prefill_tokens[i] = left if left > 0 else 0
        f.mark_dirty(i)

    def on_retract(self, req: Request, prefill_left: int):
        """Reverse ``on_route`` for a cancelled queued-or-prefilling
        request (deadline blown): the unburnt prefill leaves the queue
        and the prompt leaves the resident-token count.  The KV$ entry
        routing inserted stays — the LRU evicts it like any cold
        lineage."""
        f, i = self._f, self.iid
        if f.q_bs[i] > 0:
            f.q_bs[i] -= 1
        left = f.queued_prefill_tokens[i] - prefill_left
        f.queued_prefill_tokens[i] = left if left > 0 else 0
        left = f.total_tokens[i] - req.prompt_len
        f.total_tokens[i] = left if left > 0 else 0
        f.mark_dirty(i)

    def on_start_running(self, req: Request):
        f, i = self._f, self.iid
        if f.q_bs[i] > 0:
            f.q_bs[i] -= 1
        f.r_bs[i] += 1
        f.mark_dirty(i)

    def on_decode_token(self):
        f = self._f
        f.total_tokens[self.iid] += 1
        f.mark_dirty(self.iid)

    def on_finish(self, req: Request):
        f, i = self._f, self.iid
        if f.r_bs[i] > 0:
            f.r_bs[i] -= 1
        left = f.total_tokens[i] - req.prompt_len - req.output_len
        f.total_tokens[i] = left if left > 0 else 0
        f.mark_dirty(i)

    def trim_log(self, now: float, window: float):
        self._f.trim_routed(self.iid, now - window)


class _WaveHandle:
    """In-flight wave walk: everything ``wave_collect`` needs to finish
    the host half of a batch-routing wave — the (possibly asynchronous)
    aggregated-index walk plus the shared sort the pairwise-LCP
    reconstruction reuses.  Produced by ``wave_submit``; the routing
    pipeline holds one of these across a speculative prefetch."""

    __slots__ = ("reqs", "uid", "chains", "order", "adj", "depth_u",
                 "handle", "submit_ns")

    def __init__(self, reqs, uid, chains, order, adj, depth_u, handle,
                 submit_ns):
        self.reqs = reqs
        self.uid = uid
        self.chains = chains
        self.order = order
        self.adj = adj
        self.depth_u = depth_u
        self.handle = handle
        self.submit_ns = submit_ns


class IndicatorFactory:
    _LOG_CAP0 = 256   # initial per-instance routed-window ring capacity

    def __init__(self, n_instances: int, kv_capacity_tokens: int = 1 << 62,
                 block_size: int = 64, exact_only: bool = False,
                 n_shards: int = 1, parallel_walks: bool = False,
                 walk_backend: Optional[str] = None,
                 shard_timeout_s: Optional[float] = None,
                 fleet=None):
        self.n = n_instances
        self.block_size = block_size
        self.exact_only = exact_only
        self.walk_backend = walk_backend
        self.parallel_walks = parallel_walks
        self.shard_timeout_s = shard_timeout_s
        # --- heterogeneous fleet columns (PR 10) -------------------------
        # model_id / hardware_class ride in the SoA like every other
        # indicator (same shard_bounds partition as the device mirror
        # and the sharded prefix index).  They are written once at init
        # and never mutated, so the per-shard dirty protocol has nothing
        # to re-upload for them — device_hetero_view caches one upload.
        # prefill_norm is the per-instance marginal prefill cost; it is
        # None iff no fleet was given OR the fleet's costs are constant
        # (FleetSpec.norm_or_none) — the collapse that keeps homogeneous
        # configurations on the exact legacy instruction sequence.
        self.fleet = fleet
        if fleet is not None:
            if fleet.n != n_instances:
                raise ValueError(f"fleet describes {fleet.n} instances, "
                                 f"factory has {n_instances}")
            self.model_id = fleet.model_codes.copy()
            self.hardware_class = fleet.class_codes.copy()
            self.prefill_norm = fleet.norm_or_none()
        else:
            self.model_id = np.zeros(n_instances, dtype=np.int64)
            self.hardware_class = np.zeros(n_instances, dtype=np.int64)
            self.prefill_norm = None
        self._dev_hetero = None
        self._feasible_cache = {}
        # degraded-mode telemetry: walk-backend deaths survived by
        # rebuilding the index from the per-instance radix trees
        self.degraded_rebuilds = 0
        # exactly-once rebuild event hook (observability): invoked once
        # per degraded_rebuilds increment, never re-fired for the same
        # rebuild even when the triggering walk/mutation is retried —
        # the counter and the event move together (Router wires this to
        # the obs registry/tracer when observability is attached)
        self.on_degraded_rebuild = None
        # anti-entropy telemetry (PR 9): scoped repairs performed,
        # digest mismatches seen, the sweep cursor, and per-repair wall
        # cost; on_shard_repair fires exactly once per repair
        self.shard_repairs = 0
        self.verify_mismatches = 0
        self.repair_ns: List[int] = []
        self.on_shard_repair = None
        self._sweep_cursor = 0
        self._fault_injector = None
        self.on_backend_event = None
        # shard count for the aggregated index AND the device-mirror
        # partition (same shard_bounds cut); 1 = the unsharded flat index
        self.n_shards = max(1, min(int(n_shards), n_instances))
        # --- the array contract (see module docstring) -------------------
        self.r_bs = np.zeros(n_instances, dtype=np.int64)
        self.q_bs = np.zeros(n_instances, dtype=np.int64)
        self.queued_prefill_tokens = np.zeros(n_instances, dtype=np.int64)
        self.total_tokens = np.zeros(n_instances, dtype=np.int64)
        self._hit_depths = np.zeros(n_instances, dtype=np.int64)
        # device mirror (see docstring): per-shard dirty flags, only
        # touched shards re-upload; _dev caches the concatenated tuple
        self._mirror_bounds = shard_bounds(n_instances, self.n_shards)
        self._mirror_owner = shard_owner(n_instances, self.n_shards)
        self._dirty = np.ones(self.n_shards, dtype=bool)
        self._dev_shards = [None] * self.n_shards
        self._dev = None
        # mid-wave plan invalidation signal for Router.route_batch;
        # evict_runs counts on_evict calls (evictions / evict_runs is
        # the leaves per run)
        self.evictions = 0
        self.evict_runs = 0
        # host-walk telemetry: aggregated-index walk time / walk count
        # (per unique prompt), surfaced by Router.mean_walk_us
        self.walk_ns = 0
        self.walks = 0
        # Preble routed-window ring buffers (time, p_tokens), per instance
        cap = self._LOG_CAP0
        self._log_t = np.zeros((n_instances, cap), dtype=np.float64)
        self._log_p = np.zeros((n_instances, cap), dtype=np.int64)
        self._log_start = np.zeros(n_instances, dtype=np.int64)
        self._log_len = np.zeros(n_instances, dtype=np.int64)
        # speculative-walk insert capture (see begin_insert_capture)
        self._capture = None
        self._capture_ev0 = 0
        # exact_only hit semantics (deepest snapshot boundary) cannot be
        # read off chain membership alone -> scalar per-instance fallback
        if exact_only:
            self._agg = None
        elif self.n_shards == 1 and walk_backend is None:
            self._agg = AggregatedPrefixIndex(n_instances)
        else:
            # an explicit walk backend always builds the sharded index
            # (even at one shard) so backend sweeps compare like with
            # like; decisions are bit-identical either way
            from .sharded_index import ShardedPrefixIndex
            self._agg = ShardedPrefixIndex(n_instances, self.n_shards,
                                           parallel=parallel_walks,
                                           backend=walk_backend,
                                           timeout_s=shard_timeout_s)
        self.instances = []
        for i in range(n_instances):
            kv = RadixKVIndex(block_size=block_size,
                              capacity_tokens=kv_capacity_tokens,
                              exact_only=exact_only)
            if self._agg is not None:
                kv.on_insert = (lambda blocks, _i=i:
                                self._on_insert(_i, blocks))
                kv.on_evict = (lambda path, m, _i=i:
                               self._on_evict(_i, path, m))
                kv.on_clear = (lambda _i=i: self._on_clear(_i))
            self.instances.append(InstanceState(i, self, kv))
        self._wire_agg()

    def _wire_agg(self):
        """Arm the aggregated index's self-healing hooks: the factory
        is the canonical chains provider (supervised worker recovery
        rebuilds only from it), and any attached fault injector carries
        over to replacement backends."""
        agg = self._agg
        if agg is None:
            return
        sp = getattr(agg, "set_chains_provider", None)
        if sp is not None:
            sp(self._shard_chains)
        if self._fault_injector is not None:
            af = getattr(agg, "attach_faults", None)
            if af is not None:
                af(self._fault_injector)
        if self.on_backend_event is not None:
            backend = getattr(agg, "backend", None)
            if backend is not None:
                backend.on_event = self.on_backend_event

    def attach_backend_events(self, cb):
        """Wire ``cb(kind, shard, info)`` to the shard backend's
        recovery events (restart / timeout / escalation / repair);
        survives degraded rebuilds.  None disarms."""
        self.on_backend_event = cb
        agg = self._agg
        backend = getattr(agg, "backend", None) if agg is not None \
            else None
        if backend is not None:
            backend.on_event = cb

    def _mutate_recover(self, e, op, *args):
        """A routed mutation failed: scoped repair when the error names
        a shard, full rebuild otherwise, then re-apply the mutation —
        all three index mutations are idempotent, so re-applying after
        a repair that already replayed it is a no-op."""
        shard = getattr(e, "shard", None)
        self._rebuild_index(shard=shard)
        if shard is not None:
            try:
                getattr(self._agg, op)(*args)
            except (RuntimeError, OSError):
                self._rebuild_index()

    def _on_insert(self, iid: int, blocks):
        try:
            self._agg.add(iid, blocks)
        except (RuntimeError, OSError) as e:
            self._mutate_recover(e, "add", iid, blocks)
            # the rebuild/repair replays the tree, this insert included
        if self._capture is not None:
            self._capture.append((iid, blocks))

    def _on_evict(self, iid: int, path, m: int):
        self.evictions += m
        self.evict_runs += 1
        try:
            self._agg.remove_tail(iid, path, m)
        except (RuntimeError, OSError) as e:
            self._mutate_recover(e, "remove_tail", iid, path, m)

    def _on_clear(self, iid: int):
        self.evictions += 1
        try:
            self._agg.remove_instance(iid)
        except (RuntimeError, OSError) as e:
            self._mutate_recover(e, "remove_instance", iid)

    # ---- lifecycle -------------------------------------------------------
    def close(self):
        """Tear down the aggregated index's execution backend (thread
        pools, process workers + their shared-memory segments).  Serial
        factories are unaffected; any factory is safe to close twice.
        ``with IndicatorFactory(...) as f:`` closes on exit."""
        agg = self._agg
        if agg is not None and hasattr(agg, "close"):
            agg.close()

    def __enter__(self) -> "IndicatorFactory":
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # ---- speculative-walk insert capture ---------------------------------
    def begin_insert_capture(self):
        """Start recording ``(iid, blocks)`` aggregate inserts.

        The routing pipeline brackets a speculative next-wave walk with
        begin/end: every chain inserted between the walk's snapshot and
        its use is captured, and the walk result is patched with the
        exact cross-wave LCP credit (tree hit depth is the max over
        stored chains of the LCP, so ``max(old_depth, lcp(chain,
        inserted))`` is the depth a fresh walk would return).  An
        eviction or clear invalidates the capture — leaf removal cannot
        be credited — and the pipeline falls back to a fresh walk,
        mirroring ``Router.route_batch``'s mid-wave eviction guard.
        """
        self._capture = []
        self._capture_ev0 = self.evictions

    def end_insert_capture(self):
        """Stop recording; returns ``(inserts, valid)`` where ``valid``
        is False if any eviction/clear fired during the capture."""
        cap, self._capture = self._capture, None
        if cap is None:
            return [], False
        return cap, self.evictions == self._capture_ev0

    # ---- instance churn (Contract 4, factory half) -----------------------
    def on_instance_failed(self, iid: int):
        """An instance died with its KV$: zero its indicator columns
        (dirtying the device mirror shard), forget its routed window,
        and clear its radix tree — the ``on_clear`` callback removes
        the aggregated-index column through the shard backend's
        owner-routed mutation and bumps the eviction counter, which
        also invalidates any in-flight speculative insert capture."""
        self.r_bs[iid] = 0
        self.q_bs[iid] = 0
        self.queued_prefill_tokens[iid] = 0
        self.total_tokens[iid] = 0
        self._log_start[iid] = 0
        self._log_len[iid] = 0
        self.mark_dirty(iid)
        self.instances[iid].kv.clear()

    # ---- degraded mode (walk-backend death) ------------------------------
    def _rebuild_index(self, shard: Optional[int] = None):
        """A walk backend died mid-query.  When the error named a shard
        (``ShardError.shard``) and the surviving backend can repair in
        place, rebuild **only that shard's range** from the per-instance
        radix trees — healthy shards' node arrays are untouched.
        Otherwise the legacy path: tear the broken index down, build a
        replacement (same sharded flavour with fresh workers; a serial
        flat index when the respawn fails too), and repopulate it from
        KV truth.  Either way bumps the eviction counter so any
        in-flight wave plan or speculative capture is invalidated."""
        self.degraded_rebuilds += 1
        cb = self.on_degraded_rebuild
        if cb is not None:
            # fire exactly here — the one place the counter increments —
            # so a worker death that triggers a retried walk (or a
            # mutation error during mark_failed) cannot double-emit;
            # observer faults must never break the rebuild itself
            try:
                cb(self.degraded_rebuilds)
            except Exception:
                pass
        self.evictions += 1
        if shard is not None and self._repair_in_place(shard):
            return
        old, self._agg = self._agg, None
        if old is not None and hasattr(old, "close"):
            try:
                old.close()
            except Exception:
                pass                      # the backend is already broken
        agg = None
        if self.walk_backend is not None or self.n_shards > 1:
            from .sharded_index import ShardedPrefixIndex
            try:
                agg = ShardedPrefixIndex(self.n, self.n_shards,
                                         parallel=self.parallel_walks,
                                         backend=self.walk_backend,
                                         timeout_s=self.shard_timeout_s)
            except Exception:
                agg = None                # respawn failed: go serial
        if agg is None:
            agg = AggregatedPrefixIndex(self.n)
        for inst in self.instances:
            for chain in inst.kv.chains():
                agg.add(inst.iid, chain)
        # the kv callbacks close over self._agg dynamically, so the
        # swap retargets every future insert/evict/clear
        self._agg = agg
        self._wire_agg()

    def _walk_retry(self, e, fn):
        """Bounded degraded-mode retry for a failed walk: scoped repair
        when the error names a shard (``ShardError.shard``), full
        rebuild otherwise, then re-run the walk.  Bounded by shards + 1
        attempts — each repair heals one shard, so a plan injecting
        consecutive crashes on every shard still converges instead of
        looping."""
        for _ in range(self._index_shards() + 1):
            self._rebuild_index(shard=getattr(e, "shard", None))
            try:
                return fn()
            except (RuntimeError, OSError) as e2:
                e = e2
        raise e

    def _repair_in_place(self, s: int) -> bool:
        """Try the scoped repair; False falls back to the full rebuild
        (no ``repair_shard`` on the index, backend already torn down,
        or the repair itself failed)."""
        agg = self._agg
        if agg is None or not hasattr(agg, "repair_shard"):
            return False
        backend = getattr(agg, "backend", None)
        if backend is not None and getattr(backend, "_closed", False):
            return False
        try:
            self.repair_shard(s, _count_rebuild=False)
        except Exception:
            return False
        return True

    # ---- anti-entropy (PR 9) ---------------------------------------------
    def _index_shards(self) -> int:
        """Shard count of the live aggregated index (1 for the flat
        unsharded index, 0 for exact_only factories)."""
        agg = self._agg
        return getattr(agg, "n_shards", 1) if agg is not None else 0

    def _shard_chains(self, s: int) -> List[Tuple[int, list]]:
        """Canonical truth for shard ``s``: every ``(local_iid, chain)``
        in its instance range, read from the per-instance radix trees."""
        lo, hi = shard_bounds(self.n, self._index_shards())[s]
        pairs = []
        for iid in range(lo, hi):
            for chain in self.instances[iid].kv.chains():
                pairs.append((iid - lo, chain))
        return pairs

    def attach_faults(self, injector):
        """Arm deterministic fault injection
        (``repro.core.faults.FaultInjector``) on the aggregated index's
        backend; survives degraded rebuilds.  None disarms."""
        self._fault_injector = injector
        agg = self._agg
        if agg is not None:
            af = getattr(agg, "attach_faults", None)
            if af is not None:
                af(injector)

    def verify_shard(self, s: int) -> bool:
        """True iff shard ``s``'s aggregated index agrees with KV truth:
        the incremental digest, a rescan of the bitset rows, and a
        replay of ``RadixKVIndex.chains()`` all produce the same digest
        triple.  Counts mismatches; never mutates."""
        agg = self._agg
        if agg is None:
            return True
        truth = digest_from_chains(self._shard_chains(s))
        sd = getattr(agg, "shard_digest", None)
        if sd is not None:
            inc, scan = sd(s)
        else:
            inc, scan = agg.digest, agg.rescan_digest()
        ok = tuple(inc) == truth and tuple(scan) == truth
        if not ok:
            self.verify_mismatches += 1
        return ok

    def repair_shard(self, s: int, _count_rebuild: bool = True):
        """Rebuild shard ``s`` — and only shard ``s`` — from canonical
        KV truth, leaving healthy shards' node arrays untouched.  Bumps
        the eviction counter (a repaired shard may answer differently,
        so in-flight plans and speculative captures are invalid) and
        fires ``on_shard_repair`` exactly once."""
        agg = self._agg
        if agg is None:
            return
        t0 = time.perf_counter_ns()
        rp = getattr(agg, "repair_shard", None)
        if rp is not None:
            rp(s, self._shard_chains(s))
        else:
            # flat unsharded index: shard 0 is the whole index
            agg.reset()
            for li, chain in self._shard_chains(0):
                agg.add(li, chain)
        self.repair_ns.append(time.perf_counter_ns() - t0)
        self.shard_repairs += 1
        if _count_rebuild:
            self.evictions += 1
        cb = self.on_shard_repair
        if cb is not None:
            try:
                cb(s, self.shard_repairs)
            except Exception:
                pass

    def anti_entropy_step(self, k: int = 1) -> int:
        """Budgeted background sweep: verify the next ``k`` shards in
        cursor order, repairing any whose digests disagree with KV
        truth.  Returns the number of repairs performed.  O(k · shard
        state) worst case, O(k · occupied rows) typical — callers run
        it once per wave with small ``k``."""
        if self._agg is None or k <= 0:
            return 0
        S = self._index_shards()
        repaired = 0
        for _ in range(min(int(k), S)):
            s = self._sweep_cursor % S
            self._sweep_cursor += 1
            if not self.verify_shard(s):
                self.repair_shard(s)
                repaired += 1
        return repaired

    def __len__(self):
        return self.n

    def __iter__(self):
        return iter(self.instances)

    def __getitem__(self, i) -> InstanceState:
        return self.instances[i]

    # ---- vectorized reads ------------------------------------------------
    def bs_vector(self) -> np.ndarray:
        return self.r_bs + self.q_bs

    def hits_for(self, req: Request) -> np.ndarray:
        """Per-instance KV$ hit tokens (capped at the prompt length)."""
        if self._agg is not None:
            t0 = time.perf_counter_ns()
            try:
                depths = self._agg.match_depths(req.blocks,
                                                out=self._hit_depths)
            except (RuntimeError, OSError) as e:
                # degraded: scoped repair (or full rebuild) + retry
                depths = self._walk_retry(
                    e, lambda: self._agg.match_depths(
                        req.blocks, out=self._hit_depths))
            self.walk_ns += time.perf_counter_ns() - t0
            self.walks += 1
            hits = depths * self.block_size
            np.minimum(hits, req.prompt_len, out=hits)
            return hits
        return np.array([inst.kv_hit(req) for inst in self.instances],
                        dtype=np.int64)

    def p_tokens_for(self, req: Request,
                     hits: Optional[np.ndarray] = None) -> np.ndarray:
        """Vectorized Fig. 17(b) P-token: queued prefill + new tokens."""
        if hits is None:
            hits = self.hits_for(req)
        return self.queued_prefill_tokens + (req.prompt_len - hits)

    def mean_walk_us(self) -> float:
        """Mean host cost of one aggregated-index walk (per unique
        prompt), from the ``walk_ns``/``walks`` telemetry — the single
        definition both ``Router.mean_walk_us`` and the benchmarks
        report.  On a sharded factory a "walk" is the full fan-out
        across every shard (including the shared lexicographic sort);
        ``shard_walk_stats`` breaks the same work down per shard."""
        return self.walk_ns / max(self.walks, 1) / 1e3

    def shard_walk_stats(self) -> List[dict]:
        """Per-shard host-walk telemetry: one record per shard with its
        instance range ``[lo, hi)``, walks served, and mean per-walk
        cost in µs.  An unsharded (or ``exact_only``) factory reports a
        single pseudo-shard covering ``[0, n)`` so consumers never
        branch on the index flavour; the max over shards is the
        critical path a parallel walk fan-out pays per wave
        (``Router.walk_telemetry`` surfaces it)."""
        stats = getattr(self._agg, "shard_stats", None)
        if stats is not None:
            return stats()
        return [{"shard": 0, "lo": 0, "hi": self.n,
                 "walks": int(self.walks),
                 "mean_walk_us": self.mean_walk_us()}]

    # ---- device mirror (dirty-flag sync contract, see docstring) ---------
    def mark_dirty(self, iid: Optional[int] = None):
        """Invalidate the device mirror after an in-place indicator
        write — THE other half of the sync contract (hooks write numpy
        in place, then flip dirty; ``device_view`` re-uploads; device
        code never writes indicators back).  ``mark_dirty(iid)``
        narrows the invalidation to the mirror shard covering instance
        ``iid`` (what every built-in hook passes); a bare
        ``mark_dirty()`` conservatively dirties every shard and is
        always safe for external callers that batch-write slices of
        ``factory.r_bs`` and friends."""
        if iid is None:
            self._dirty[:] = True
        else:
            self._dirty[self._mirror_owner[iid]] = True
        self._dev = None

    def device_view(self):
        """(r_bs, q_bs, queued_prefill_tokens, total_tokens) as int64
        jax arrays (created under ``route_score.x64``),
        re-uploading only the mirror shards whose dirty flag is set
        since the last call.  With one shard (the default) this is one
        cached whole-array upload per mutation epoch, exactly the
        pre-sharding behaviour; with ``n_shards > 1`` untouched shards
        reuse their cached device slices and only the concatenation is
        redone.  The returned arrays are read-only by contract."""
        if self._dev is not None:
            return self._dev
        import jax.numpy as jnp
        from repro.kernels.route_score import x64
        cols = (self.r_bs, self.q_bs, self.queued_prefill_tokens,
                self.total_tokens)
        with x64():  # keep the mirror int64
            for s, (lo, hi) in enumerate(self._mirror_bounds):
                if self._dirty[s] or self._dev_shards[s] is None:
                    self._dev_shards[s] = tuple(jnp.asarray(c[lo:hi])
                                                for c in cols)
                    self._dirty[s] = False
            if self.n_shards == 1:
                self._dev = self._dev_shards[0]
            else:
                self._dev = tuple(
                    jnp.concatenate([self._dev_shards[s][j]
                                     for s in range(self.n_shards)])
                    for j in range(4))
        return self._dev

    # ---- heterogeneous fleet reads (PR 10) -------------------------------
    def feasible_mask(self, requirement: str):
        """Boolean capability mask for a ``model_requirement``, or
        ``None`` when there is nothing to filter (no fleet attached, or
        an empty requirement — every instance qualifies).  Contract 7:
        this is a *pre-score* filter; callers intersect it into the
        policy's candidate set exactly like the alive mask, so a
        ``None`` return keeps the legacy instruction sequence.  Masks
        are cached per requirement string (the fleet is immutable)."""
        if self.fleet is None or not requirement:
            return None
        m = self._feasible_cache.get(requirement)
        if m is None:
            m = self.fleet.feasible_mask(requirement)
            self._feasible_cache[requirement] = m
        return m

    def device_hetero_view(self):
        """(model_id, hardware_class, prefill_norm) as device arrays,
        partitioned by the same ``shard_bounds`` cut as ``device_view``.
        The columns are written once at init and never mutated, so —
        unlike the load indicators — one cached upload serves every
        wave; ``mark_dirty`` has nothing to invalidate here.  The norm
        slot is ``None`` when ``prefill_norm`` collapsed (homogeneous
        fleet), mirroring the host-side contract."""
        if self._dev_hetero is not None:
            return self._dev_hetero
        import jax.numpy as jnp
        from repro.kernels.route_score import x64
        with x64():
            shards = [(jnp.asarray(self.model_id[lo:hi]),
                       jnp.asarray(self.hardware_class[lo:hi]),
                       None if self.prefill_norm is None
                       else jnp.asarray(self.prefill_norm[lo:hi]))
                      for lo, hi in self._mirror_bounds]
            if self.n_shards == 1:
                self._dev_hetero = shards[0]
            else:
                self._dev_hetero = tuple(
                    None if shards[0][j] is None else
                    jnp.concatenate([s[j] for s in shards])
                    for j in range(3))
        return self._dev_hetero

    # ---- wave inputs (host half of the batch routing path) ---------------
    def wave_submit(self, reqs: Sequence[Request]) -> _WaveHandle:
        """Start the walk stage for an arrival wave: dedup to unique
        chains, compute the shared lexicographic sort, and submit one
        LCP-chained aggregated-index walk per unique prompt.  On
        asynchronous backends (thread/process shard fan-out) the walk
        runs while the caller does other work; ``wave_collect`` blocks
        for the result.  Requires the aggregated index."""
        k = len(reqs)
        uid = np.empty(k, dtype=np.int64)
        uniq: Dict[tuple, int] = {}
        for j, r in enumerate(reqs):
            u = uniq.setdefault(r.blocks, len(uniq))
            uid[j] = u
        chains = [None] * len(uniq)
        for blocks, u in uniq.items():
            chains[u] = blocks
        t0 = time.perf_counter_ns()
        order, adj = _sorted_lcp(chains)
        submit = getattr(self._agg, "submit_many", None)
        try:
            if submit is not None:
                depth_u, handle = submit(chains, order=order, adj=adj)
            else:
                depth_u = self._agg.match_depths_many(chains, order=order,
                                                      adj=adj)
                handle = None
        except (RuntimeError, OSError) as e:
            # walk backend died on dispatch: repair (scoped to the
            # failed shard when the error names one) and run this
            # wave's walk on the healed index
            depth_u = self._walk_retry(
                e, lambda: self._agg.match_depths_many(chains,
                                                       order=order,
                                                       adj=adj))
            handle = None
        return _WaveHandle(tuple(reqs), uid, chains, order, adj,
                           depth_u, handle,
                           time.perf_counter_ns() - t0)

    def wave_collect(self, h: _WaveHandle, with_lcp: bool = True):
        """Finish a submitted wave walk: wait for the depth matrix,
        account walk telemetry (submit cost + blocked wait — the host
        time the walk actually held up routing), and derive the
        pairwise-LCP matrix from the shared sort."""
        t0 = time.perf_counter_ns()
        if h.handle is not None:
            try:
                h.handle.wait()
            except (RuntimeError, OSError) as e:
                # a shard worker died mid-query (degraded mode): repair
                # and recompute this wave's walk — the wave proceeds
                # instead of raising
                h.depth_u = self._walk_retry(
                    e, lambda: self._agg.match_depths_many(
                        h.chains, order=h.order, adj=h.adj))
                h.handle = None
        self.walk_ns += h.submit_ns + (time.perf_counter_ns() - t0)
        self.walks += len(h.chains)
        k = len(h.reqs)
        lcp = (_pairwise_lcp(h.chains, order=h.order, adj=h.adj)
               [np.ix_(h.uid, h.uid)] if with_lcp else None)
        plen = np.fromiter((r.prompt_len for r in h.reqs), np.int64, k)
        return h.depth_u[h.uid], lcp, plen

    def wave_discard(self, h: _WaveHandle):
        """Wait out a submitted walk without consuming it (mispredicted
        speculation).  The wait keeps asynchronous backends' protocol
        in sync; nothing is added to walk telemetry — no routed wave
        was served by this walk."""
        if h.handle is not None:
            try:
                h.handle.wait()
            except (RuntimeError, OSError) as e:
                # the speculation is being dropped anyway; just heal
                # the broken shard (or replace the backend) so the
                # next wave has an index
                self._rebuild_index(shard=getattr(e, "shard", None))

    def wave_inputs(self, reqs: Sequence[Request], with_lcp: bool = True):
        """(depth (k,n), lcp (k,k) | None, plen (k,)) for an arrival wave.

        One LCP-chained aggregated-index walk per *unique* prompt (waves
        are bursty — duplicates and shared classes are the common case),
        plus the pairwise block-chain LCP matrix the device loop needs
        to credit intra-wave inserts.  The lexicographic sort feeding
        the walk reuse is computed once and shared with the pairwise-LCP
        reconstruction.  ``wave_submit`` + ``wave_collect`` in one
        breath — the synchronous spelling of the walk stage."""
        return self.wave_collect(self.wave_submit(reqs),
                                 with_lcp=with_lcp)

    # ---- Preble routed-window ring buffers -------------------------------
    #: entries older than this are expendable when a ring fills: every
    #: windowed consumer (Preble's 3-minute fallback) looks back far
    #: less, and horizon-trimming a full row beats doubling the whole
    #: (n, cap) matrix for one hot instance under skewed load
    LOG_HORIZON_S = 3600.0

    def log_routed(self, iid: int, t: float, p_tokens: int):
        if self._log_len[iid] == self._log_t.shape[1]:
            self.trim_routed(iid, t - self.LOG_HORIZON_S)
        if self._log_len[iid] == self._log_t.shape[1]:
            self._grow_log()
        cap = self._log_t.shape[1]
        idx = (self._log_start[iid] + self._log_len[iid]) % cap
        self._log_t[iid, idx] = t
        self._log_p[iid, idx] = p_tokens
        self._log_len[iid] += 1

    def _grow_log(self):
        cap = self._log_t.shape[1]
        nt = np.zeros((self.n, 2 * cap), dtype=np.float64)
        npv = np.zeros((self.n, 2 * cap), dtype=np.int64)
        idx = (self._log_start[:, None] + np.arange(cap)[None, :]) % cap
        rows = np.arange(self.n)[:, None]
        nt[:, :cap] = self._log_t[rows, idx]
        npv[:, :cap] = self._log_p[rows, idx]
        self._log_t, self._log_p = nt, npv
        self._log_start[:] = 0

    def _log_view(self):
        """(times, ptokens, valid) in logical (oldest-first) order."""
        cap = self._log_t.shape[1]
        idx = (self._log_start[:, None] + np.arange(cap)[None, :]) % cap
        rows = np.arange(self.n)[:, None]
        valid = np.arange(cap)[None, :] < self._log_len[:, None]
        return self._log_t[rows, idx], self._log_p[rows, idx], valid

    def trim_routed(self, iid: int, cut: float):
        """Drop the leading run of entries older than ``cut`` (exact
        pre-ring ``trim_log`` semantics: only the front is scanned)."""
        cap = self._log_t.shape[1]
        start, ln = int(self._log_start[iid]), int(self._log_len[iid])
        k = 0
        while k < ln and self._log_t[iid, (start + k) % cap] < cut:
            k += 1
        if k:
            self._log_start[iid] = (start + k) % cap
            self._log_len[iid] = ln - k

    def routed_window(self, iid: int) -> List:
        cap = self._log_t.shape[1]
        start, ln = int(self._log_start[iid]), int(self._log_len[iid])
        idx = (start + np.arange(ln)) % cap
        return [(float(t), int(p)) for t, p in
                zip(self._log_t[iid, idx], self._log_p[iid, idx])]

    def window_stats(self, now: float, window: float,
                     trim: bool = True):
        """Vectorized trim + (sum p_tokens, count) over every instance's
        window — the Preble fallback in one shot instead of n Python
        log walks.  ``trim=False`` computes the same stats without
        advancing the ring cursors (side-effect-free inspection, e.g.
        ``scores_batch``)."""
        cut = now - window
        times, pts, valid = self._log_view()
        drop = np.cumprod(valid & (times < cut), axis=1).sum(axis=1)
        if drop.any():
            if trim:
                cap = self._log_t.shape[1]
                self._log_start[:] = (self._log_start + drop) % cap
                self._log_len[:] = self._log_len - drop
            keep = valid & (np.arange(times.shape[1])[None, :]
                            >= drop[:, None])
        else:
            keep = valid
        return (np.where(keep, pts, 0).sum(axis=1),
                keep.sum(axis=1).astype(np.int64))

    def snapshot(self) -> Dict[str, List]:
        snap = {
            "r_bs": self.r_bs.tolist(),
            "q_bs": self.q_bs.tolist(),
            "bs": self.bs_vector().tolist(),
            "queued_prefill_tokens": self.queued_prefill_tokens.tolist(),
            "total_tokens": self.total_tokens.tolist(),
            "kv_tokens": [i.kv.tokens_stored for i in self.instances],
        }
        if self.fleet is not None:
            snap["model_id"] = self.model_id.tolist()
            snap["hardware_class"] = self.hardware_class.tolist()
        return snap
