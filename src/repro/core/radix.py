"""Block-granular prefix (radix) tree — the per-instance KV$ index.

Real engines cache KV at page/block granularity and hash whole blocks
(vLLM prefix caching, SGLang radix attention).  We key the tree on
*block ids*: a prompt is a sequence of block ids, each representing
``block_size`` tokens.  The workload layer synthesises prompts directly
as block-id sequences (compact); the real JAX engine derives block ids
from actual token arrays via ``tokens_to_blocks`` (rolling chain hash, so
identical blocks under different prefixes get distinct ids — prefix
semantics preserved).

Eviction is LRU over leaf blocks under a token-capacity budget, matching
finite per-instance KV$ space.  The tree keeps its leaf set as it
changes, so an eviction heapifies the leaves without walking the tree.
``exact_only`` supports the recurrent families (DESIGN.md
§Arch-applicability): a recurrent-state snapshot is reusable only on an
exact full-prefix boundary, so partial prefix credit is disallowed.

Coherence callbacks: ``on_insert(blocks)`` fires after every ``insert``
and ``on_evict(path, m)`` once per *run* of evictions — a chain tail
evicted bottom-up, each leaf's parent the next leaf popped.  ``path`` is
the full root→leaf key chain of the run's deepest leaf and ``m`` the
number of blocks the run took off its end, so the run's leaves are
``path[:len(path) - j]`` for ``j`` in ``range(m)``, deepest first; the
callback fires once the run has left the tree.  ``IndicatorFactory``
uses them to keep its aggregated cross-instance prefix index in sync, so
any caller may mutate ``inst.kv`` directly without desynchronising
vectorized hit lookups.
"""
from __future__ import annotations

import heapq
import itertools
from typing import Dict, List, Optional, Sequence


def tokens_to_blocks(tokens: Sequence[int], block_size: int) -> List[int]:
    """Chain-hash full token blocks into block ids (engine-side helper)."""
    out = []
    h = 0
    for i in range(0, len(tokens) - block_size + 1, block_size):
        h = hash((h,) + tuple(tokens[i:i + block_size]))
        out.append(h)
    return out


class _Node:
    __slots__ = ("children", "parent", "key", "last_use", "terminal")

    def __init__(self, parent: Optional["_Node"], key):
        self.children: Dict[int, "_Node"] = {}
        self.parent = parent
        self.key = key
        self.last_use = 0
        self.terminal = False   # explicit snapshot point (exact_only mode)


class RadixKVIndex:
    def __init__(self, block_size: int = 64,
                 capacity_tokens: int = 1 << 62,
                 exact_only: bool = False):
        assert block_size >= 1
        self.block_size = block_size
        self.capacity_tokens = capacity_tokens
        self.exact_only = exact_only
        self.root = _Node(None, None)
        self._clock = itertools.count(1)
        self._n_blocks = 0
        # every non-root node without children, kept as the tree changes
        self._leaves = set()
        # coherence hooks (see module docstring); None = disabled
        self.on_insert = None
        self.on_evict = None
        self.on_clear = None

    # ------------------------------------------------------------------
    def match(self, blocks: Sequence[int], prompt_len: Optional[int] = None,
              touch: bool = True) -> int:
        """Cached-prefix length in TOKENS for a prompt given as block ids.

        prompt_len: true token length (>= len(blocks)*block_size); the hit
        is capped at prompt_len.
        """
        node = self.root
        depth = 0
        term_depth = 0
        now = next(self._clock) if touch else 0
        for b in blocks:
            child = node.children.get(b)
            if child is None:
                break
            node = child
            depth += 1
            if node.terminal:
                term_depth = depth
            if touch:
                node.last_use = now
        if self.exact_only:
            # recurrent-state semantics: only resumable from an explicit
            # snapshot boundary (deepest terminal node on the path)
            depth = term_depth
        hit = depth * self.block_size
        if prompt_len is not None:
            hit = min(hit, prompt_len)
        return hit

    # ------------------------------------------------------------------
    def insert(self, blocks: Sequence[int]) -> int:
        """Insert prefix blocks; returns number of newly-added tokens."""
        node = self.root
        now = next(self._clock)
        added = 0
        for b in blocks:
            child = node.children.get(b)
            if child is None:
                if not added and not node.children:
                    self._leaves.discard(node)   # stops being a leaf
                child = _Node(node, b)
                node.children[b] = child
                self._n_blocks += 1
                added += 1
            child.last_use = now
            node = child
        if added:
            self._leaves.add(node)  # new nodes form one chain below it
        if node is not self.root:
            node.terminal = True    # snapshot saved at this boundary
        if self.on_insert is not None and blocks:
            self.on_insert(blocks)
        if added and self.tokens_stored > self.capacity_tokens:
            self._evict_to_capacity()
        return added * self.block_size

    # ------------------------------------------------------------------
    def _evict_to_capacity(self):
        # heapify the kept leaves by last_use, pop until under budget,
        # promoting parents that become leaves.  Nodes sharing a stamp
        # lie on one root path, so at most one of them is a leaf at a
        # time: leaf stamps are unique and the pop order is total.
        leaves = self._leaves
        heap = [(n.last_use, id(n), n) for n in leaves]
        heapq.heapify(heap)
        keep = self.capacity_tokens // self.block_size
        on_evict = self.on_evict
        root = self.root
        path, m, nxt = None, 0, None
        while self._n_blocks > keep and heap:
            _, _, leaf = heapq.heappop(heap)
            if on_evict is not None:
                if leaf is not nxt:         # a new run starts here
                    if m:
                        on_evict(path, m)
                    path, n = [], leaf
                    while n is not root:
                        path.append(n.key)
                        n = n.parent
                    path.reverse()
                    m = 0
                m += 1
            parent = nxt = leaf.parent
            del parent.children[leaf.key]
            leaf.parent = None
            leaves.discard(leaf)
            self._n_blocks -= 1
            if parent is not root and not parent.children:
                leaves.add(parent)
                heapq.heappush(heap, (parent.last_use, id(parent), parent))
        if m:
            on_evict(path, m)

    def evict_tokens(self, n_tokens: int):
        """Force-evict at least n_tokens (LRU leaves)."""
        save = self.capacity_tokens
        self.capacity_tokens = max(self.tokens_stored - n_tokens, 0)
        self._evict_to_capacity()
        self.capacity_tokens = save

    # ------------------------------------------------------------------
    def chains(self):
        """Yield every root→leaf key path (the tree's maximal chains).

        Each yielded list is a prefix-closed block chain this instance
        holds; rebuilding an aggregated prefix index from every
        instance's ``chains()`` reproduces the callback-maintained
        aggregate exactly (the coherence check in
        ``tests/test_prefix_index.py``).
        """
        stack = [(self.root, [])]
        while stack:
            node, path = stack.pop()
            if not node.children:
                if path:
                    yield path
                continue
            for key, child in node.children.items():
                stack.append((child, path + [key]))

    @property
    def tokens_stored(self) -> int:
        return self._n_blocks * self.block_size

    @property
    def n_blocks(self) -> int:
        return self._n_blocks

    def clear(self):
        self.root = _Node(None, None)
        self._n_blocks = 0
        self._leaves = set()
        if self.on_clear is not None:
            self.on_clear()
