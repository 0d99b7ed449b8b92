"""Unit + property tests for the block-granular radix KV$ index."""
import pytest

pytest.importorskip(
    "hypothesis",
    reason="optional dev dep (requirements-dev.txt); property tests only")
import hypothesis.strategies as st
from hypothesis import given, settings

from repro.core.radix import RadixKVIndex, tokens_to_blocks

B = 4  # block size for tests


def test_match_empty():
    kv = RadixKVIndex(block_size=B)
    assert kv.match((1, 2, 3), 12) == 0


def test_insert_then_match_full_and_partial():
    kv = RadixKVIndex(block_size=B)
    kv.insert((10, 11, 12))
    assert kv.match((10, 11, 12), 12) == 12
    assert kv.match((10, 11), 8) == 8
    assert kv.match((10, 99), 8) == B
    assert kv.match((99,), 4) == 0


def test_prompt_len_caps_hit():
    kv = RadixKVIndex(block_size=B)
    kv.insert((1, 2))
    # prompt has 2 full blocks + 3 trailing tokens (len 11): hit <= 11
    assert kv.match((1, 2), prompt_len=7) == 7


def test_lru_eviction_under_capacity():
    kv = RadixKVIndex(block_size=B, capacity_tokens=3 * B)
    kv.insert((1,))
    kv.insert((2,))
    kv.insert((3,))
    assert kv.tokens_stored == 3 * B
    kv.match((2,), touch=True)   # refresh 2
    kv.match((3,), touch=True)
    kv.insert((4,))              # evicts 1 (LRU leaf)
    assert kv.tokens_stored <= 3 * B
    assert kv.match((1,), 4) == 0
    assert kv.match((3,), 4) == B


def test_eviction_respects_tree_structure():
    kv = RadixKVIndex(block_size=B, capacity_tokens=2 * B)
    kv.insert((1, 2, 3))   # over capacity: evicts deepest LRU leaves
    assert kv.tokens_stored <= 2 * B
    assert kv.match((1,), 4) == B   # prefix survives, leaf evicted


def test_exact_only_snapshot_semantics():
    kv = RadixKVIndex(block_size=B, exact_only=True)
    kv.insert((1, 2, 3))        # snapshot at depth 3 only
    assert kv.match((1, 2, 3, 4), 16) == 12   # resume from snapshot
    assert kv.match((1, 2), 8) == 0           # no snapshot at depth 2
    kv.insert((1, 2))
    assert kv.match((1, 2), 8) == 8


def test_tokens_to_blocks_prefix_property():
    a = list(range(100))
    b = list(range(100)) + [7, 7, 7]
    ba = tokens_to_blocks(a, 16)
    bb = tokens_to_blocks(b, 16)
    assert bb[:len(ba)] == ba
    c = [1] + list(range(99))
    bc = tokens_to_blocks(c, 16)
    assert bc[0] != ba[0]


@settings(max_examples=200, deadline=None)
@given(st.lists(st.tuples(st.booleans(),
                          st.lists(st.integers(0, 5), min_size=1,
                                   max_size=8)),
                min_size=1, max_size=40))
def test_property_match_is_longest_inserted_prefix(ops):
    """match() == block_size * (longest inserted prefix path length)."""
    kv = RadixKVIndex(block_size=B)
    inserted = []
    for is_insert, seq in ops:
        seq = tuple(seq)
        if is_insert:
            kv.insert(seq)
            inserted.append(seq)
        else:
            got = kv.match(seq, len(seq) * B)
            best = 0
            for ins in inserted:
                d = 0
                for x, y in zip(ins, seq):
                    if x != y:
                        break
                    d += 1
                best = max(best, d)
            assert got == best * B


@settings(max_examples=100, deadline=None)
@given(st.lists(st.lists(st.integers(0, 3), min_size=1, max_size=6),
                min_size=1, max_size=20),
       st.integers(1, 4))
def test_property_capacity_never_exceeded_after_insert(seqs, cap_blocks):
    kv = RadixKVIndex(block_size=B, capacity_tokens=cap_blocks * B)
    for s in seqs:
        kv.insert(tuple(s))
        assert kv.tokens_stored <= cap_blocks * B


def _scan_leaves(kv):
    """Every non-root node without children, by a full tree walk."""
    out, stack = set(), [kv.root]
    while stack:
        n = stack.pop()
        if n is not kv.root and not n.children:
            out.add(n)
        stack.extend(n.children.values())
    return out


class _ScanLRU(RadixKVIndex):
    """The leaf-scanning LRU the kept leaf set replaced: every eviction
    walks the whole tree for its leaves and reports each evicted leaf
    on its own, root→leaf path rebuilt per leaf."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.evicted = []

    def _evict_to_capacity(self):
        import heapq
        leaves = [(n.last_use, id(n), n) for n in _scan_leaves(self)]
        heapq.heapify(leaves)
        while self.tokens_stored > self.capacity_tokens and leaves:
            _, _, leaf = heapq.heappop(leaves)
            if leaf.children or leaf.parent is None:
                continue  # stale entry
            parent = leaf.parent
            path, n = [], leaf
            while n.parent is not None:
                path.append(n.key)
                n = n.parent
            path.reverse()
            self.evicted.append(path)
            del parent.children[leaf.key]
            leaf.parent = None
            self._n_blocks -= 1
            if parent is not self.root and not parent.children:
                heapq.heappush(leaves,
                               (parent.last_use, id(parent), parent))


def _recording(kv):
    """Attach an ``on_evict`` that expands each run back into leaves."""
    runs, leaves = [], []

    def on_evict(path, m):
        runs.append((list(path), m))
        leaves.extend(list(path[:len(path) - j]) for j in range(m))
    kv.on_evict = on_evict
    return runs, leaves


def test_eviction_reports_one_run_per_chain_tail():
    kv = RadixKVIndex(block_size=B, capacity_tokens=B)
    runs, leaves = _recording(kv)
    kv.insert((1, 2, 3))       # 3 then 2 leave: one run of 2
    assert runs == [([1, 2, 3], 2)]
    assert leaves == [[1, 2, 3], [1, 2]]
    assert list(kv.chains()) == [[1]]
    assert _scan_leaves(kv) == kv._leaves
    kv.insert((4, 5))          # LRU is 1 (its own run), then 5
    assert runs[1:] == [([1], 1), ([4, 5], 1)]
    assert list(kv.chains()) == [[4]]


@settings(max_examples=200, deadline=None)
@given(st.integers(1, 6),
       st.lists(st.tuples(st.sampled_from(
                    ["insert", "insert", "match", "peek", "evict",
                     "clear"]),
                          st.lists(st.integers(0, 3), min_size=1,
                                   max_size=7),
                          st.integers(1, 4)),
                min_size=1, max_size=50))
def test_property_run_eviction_matches_leaf_scan(cap_blocks, ops):
    """The kept-leaf, run-reporting LRU evicts the same leaves in the
    same order as the leaf-scanning LRU, leaves the same tree, and its
    leaf set equals a full scan's after every step."""
    kv = RadixKVIndex(block_size=B, capacity_tokens=cap_blocks * B)
    ref = _ScanLRU(block_size=B, capacity_tokens=cap_blocks * B)
    runs, leaves = _recording(kv)
    for kind, seq, k in ops:
        seq = tuple(seq)
        for t in (kv, ref):
            if kind == "insert":
                t.insert(seq)
            elif kind == "match":
                t.match(seq, touch=True)
            elif kind == "peek":
                t.match(seq, touch=False)
            elif kind == "evict":
                t.evict_tokens(k * B)
            else:
                t.clear()
        assert leaves == ref.evicted
        assert sorted(kv.chains()) == sorted(ref.chains())
        assert kv.tokens_stored == ref.tokens_stored
        assert kv._leaves == _scan_leaves(kv)
        # a run is a chain tail taken bottom-up: never longer than its
        # deepest leaf's path
        assert all(1 <= m <= len(path) for path, m in runs)
