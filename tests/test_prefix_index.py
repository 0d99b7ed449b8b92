"""Differential tests for the flat bitset aggregated prefix index.

The flat structure-of-arrays index (``repro.core.indicators.
AggregatedPrefixIndex``) must produce hit vectors identical to the
frozen bigint-mask reference (``repro.core._prefix_ref``) under every
interleaving of ``add`` / ``remove_leaf`` / ``remove_instance`` /
``match_depths`` / ``match_depths_many`` that respects the prefix-
closure protocol — i.e. everything the ``RadixKVIndex`` callback wiring
can ever emit.  Ops are therefore driven through real per-instance
radix trees (insert / capacity eviction / clear), exactly like
``IndicatorFactory`` drives the production aggregate.

A hypothesis state machine explores random interleavings; the seeded
numpy tests below it always run (hypothesis is an optional dev dep) and
pin the walk-reuse edge cases: LCP-sorted resumes across dead ends,
zero-mask narrowing, free-list recycling, non-multiple-of-64 instance
counts, and the 4096-instance scale the bigint masks choked on.
"""
import numpy as np
import pytest

from repro.core._prefix_ref import AggregatedPrefixIndexRef
from repro.core.indicators import (AggregatedPrefixIndex, _lcp_block,
                                   _pairwise_lcp)
from repro.core.radix import RadixKVIndex

B = 4  # block size for the driver trees


def _remove_leaf_ref(idx, iid, path):
    """``AggregatedPrefixIndex.remove_leaf`` as it was before eviction
    runs: one descent per leaf, then clear and prune — the reference
    ``remove_tail`` must equal, leaf for leaf."""
    from repro.core.indicators import _M64, _PHI, _ihash
    kids = idx._kids
    node = 0
    for b in path:
        node = kids[node].get(b)
        if node is None:
            return
    w = iid >> 6
    mbit = 1 << (iid & 63)
    v = idx._masks.item(node, w)
    if v & mbit:
        idx._masks[node, w] = np.uint64(v & ~mbit)
        idx._pop[node] -= 1
        if idx._dig_on:
            idx._dig = (idx._dig - ((idx._chash[node] ^ _ihash(iid))
                                    * _PHI & _M64)) & _M64
            idx._bits -= 1
    while node and not idx._pop[node] and not kids[node]:
        node = idx._free_node(node)


def _assert_same_state(a, b):
    """Two flat indexes hold the same rows: masks, popcounts, tree
    links, free list and, once on, the incremental digest."""
    assert a._top == b._top
    top = a._top
    assert np.array_equal(a._masks[:top], b._masks[:top])
    for name in ("_pop", "_parent", "_live", "_key", "_kids"):
        assert getattr(a, name)[:top] == getattr(b, name)[:top], name
    assert a._free == b._free
    assert a._dig_on == b._dig_on
    if a._dig_on:
        assert (a._dig, a._bits) == (b._dig, b._bits)
        assert a.digest == b.digest == a.rescan_digest()


class _Pair:
    """New + reference index driven through one set of radix trees.

    Each ``on_evict`` run goes to ``new`` as one ``remove_tail`` and,
    expanded into its leaves, one removal per leaf to the bigint
    reference and to ``leafwise``, a second flat index driven by the
    per-leaf ``_remove_leaf_ref``, whose whole state ``check_state``
    holds equal to ``new``'s."""

    def __init__(self, n, capacity_tokens=10 ** 9, agg_capacity=2,
                 digest=False):
        self.n = n
        # tiny initial capacity so growth + free-list recycling is
        # exercised by every scenario
        self.new = AggregatedPrefixIndex(n, capacity=agg_capacity)
        self.leafwise = AggregatedPrefixIndex(n, capacity=agg_capacity)
        self.ref = AggregatedPrefixIndexRef(n)
        if digest:
            self.new.digest, self.leafwise.digest   # switch upkeep on
        self.kvs = []
        for i in range(n):
            kv = RadixKVIndex(block_size=B, capacity_tokens=capacity_tokens)
            kv.on_insert = (lambda blocks, _i=i: (
                self.new.add(_i, blocks), self.leafwise.add(_i, blocks),
                self.ref.add(_i, blocks)))
            kv.on_evict = (lambda path, m, _i=i: self._evict(_i, path, m))
            kv.on_clear = (lambda _i=i: (
                self.new.remove_instance(_i),
                self.leafwise.remove_instance(_i),
                self.ref.remove_instance(_i)))
            self.kvs.append(kv)

    def _evict(self, iid, path, m):
        self.new.remove_tail(iid, path, m)
        for j in range(m):
            _remove_leaf_ref(self.leafwise, iid, path[:len(path) - j])
            self.ref.remove_leaf(iid, path[:len(path) - j])

    def check_state(self):
        _assert_same_state(self.new, self.leafwise)

    def check(self, probes):
        got = self.new.match_depths_many(probes)
        want = self.ref.match_depths_many(probes)
        assert (got == want).all(), (got, want)
        for c in probes:
            a = self.new.match_depths(c)
            assert (a == self.ref.match_depths(c)).all(), c
            # many-path must agree with the single-walk path too
            assert (a == self.new.match_depths_many([c])[0]).all(), c

    def rebuild_matches(self, probes):
        """A fresh flat index rebuilt from every tree's chains() must
        agree with the callback-maintained aggregate."""
        fresh = AggregatedPrefixIndex(self.n, capacity=2)
        for i, kv in enumerate(self.kvs):
            for path in kv.chains():
                fresh.add(i, path)
        assert (fresh.match_depths_many(probes)
                == self.new.match_depths_many(probes)).all()


def _chain_pool(rng, n_chains=48, alphabet=6, max_len=12):
    """Chains with heavy prefix sharing (small alphabet → deep LCPs),
    of Python ints as block ids are (the digest's chain hash needs
    them)."""
    return [tuple(rng.randint(0, alphabet, rng.randint(1, max_len))
                  .tolist())
            for _ in range(n_chains)]


@pytest.mark.parametrize("digest", [False, True])
@pytest.mark.parametrize("n", [1, 3, 16, 63, 64, 65, 130, 256])
def test_random_interleavings_match_reference(n, digest):
    rng = np.random.RandomState(n)
    # tight capacity: constant eviction
    pair = _Pair(n, capacity_tokens=15 * B, digest=digest)
    pool = _chain_pool(rng)
    for step in range(300):
        op, i = rng.rand(), rng.randint(n)
        if op < 0.65:
            pair.kvs[i].insert(pool[rng.randint(len(pool))])
        elif op < 0.85:
            pair.kvs[i].evict_tokens(int(rng.randint(1, 8)) * B)
        elif op < 0.95:
            pair.kvs[i].clear()
        pair.check_state()
        if step % 29 == 0:
            k = rng.randint(1, 9)
            probes = [pool[rng.randint(len(pool))] for _ in range(k)]
            probes.append(())                     # empty chain row
            probes.append((99_999, 1))            # miss at the root
            pair.check(probes)
    pair.check(pool)
    pair.rebuild_matches(pool)


def test_remove_tail_equals_leafwise_removal_off_protocol():
    """``remove_tail`` (and ``remove_leaf``, its ``m == 1``) equals ``m``
    per-leaf removals even where the index has drifted from the trees: paths missing their deepest
    nodes, bits another mutation never set, ancestors the prune frees
    mid-run, and bits flipped under a stale popcount."""
    rng = np.random.RandomState(11)
    n = 70
    a = AggregatedPrefixIndex(n, capacity=2)
    b = AggregatedPrefixIndex(n, capacity=2)
    a.digest, b.digest                    # both paths keep the digest
    for step in range(2000):
        iid = rng.randint(n)
        path = tuple(rng.randint(0, 4, rng.randint(1, 9)).tolist())
        op = rng.rand()
        if op < 0.45:
            for ix in (a, b):
                ix.add(iid, path)
        elif op < 0.9:
            m = rng.randint(1, len(path) + 1)
            if m == 1 and rng.rand() < 0.5:
                a.remove_leaf(iid, path)
            else:
                a.remove_tail(iid, path, m)
            for j in range(m):
                _remove_leaf_ref(b, iid, path[:len(path) - j])
        elif op < 0.97:
            for ix in (a, b):
                ix.remove_instance(iid)
        else:
            seed = rng.randint(1 << 30)
            assert a.corrupt_bit(seed) == b.corrupt_bit(seed)
        assert a._top == b._top
        top = a._top
        assert np.array_equal(a._masks[:top], b._masks[:top]), step
        assert a._pop[:top] == b._pop[:top], step
        assert a._free == b._free, step
        assert (a._dig, a._bits) == (b._dig, b._bits), step


def test_walk_reuse_lcp_edge_cases():
    """Sorted-resume edge cases: a chain that dead-ends (missing child)
    followed by chains sharing MORE than the dead-end depth, exact
    prefixes of each other, duplicates, and zero-mask narrowing."""
    n = 5
    new = AggregatedPrefixIndex(n, capacity=2)
    ref = AggregatedPrefixIndexRef(n)
    for iid, chain in [(0, (1, 2, 3, 4)), (1, (1, 2, 3)), (2, (1, 2)),
                       (3, (1, 9)), (4, (7,))]:
        new.add(iid, chain)
        ref.add(iid, chain)
    probes = [
        (1, 2, 3, 4, 5),      # walks past every mask narrowing
        (1, 2, 3, 4),
        (1, 2, 3, 4),         # duplicate chain
        (1, 2, 8, 4, 5),      # dead-ends at depth 2...
        (1, 2, 8, 4, 5, 6),   # ...then a longer chain sharing 5 blocks
        (1, 2),               # exact prefix of earlier walks
        (1,),
        (7, 7),
        (2,),                 # miss at root
        (),
    ]
    assert (new.match_depths_many(probes)
            == ref.match_depths_many(probes)).all()
    # remove instance 4 entirely: (7,) subtree must die, walks agree
    new.remove_instance(4)
    ref.remove_instance(4)
    assert (new.match_depths_many(probes)
            == ref.match_depths_many(probes)).all()


def test_free_list_recycles_nodes():
    """add → evict cycles must not grow node storage unboundedly."""
    n = 8
    pair = _Pair(n, capacity_tokens=10 * B)
    rng = np.random.RandomState(7)
    pool = _chain_pool(rng, n_chains=16, alphabet=4, max_len=8)
    high = 0
    for step in range(600):
        pair.kvs[rng.randint(n)].insert(pool[rng.randint(len(pool))])
        high = max(high, pair.new.n_nodes)
        if step == 150:
            plateau = pair.new._masks.shape[0]
    # bounded working set (tight kv capacity) -> storage stops growing
    assert pair.new._masks.shape[0] == plateau
    assert pair.new.n_nodes <= high
    pair.check(pool)


def test_scales_to_4096_instances():
    """Construct + walk at 4096 instances (the bigint ceiling): chains
    spread over the whole instance range, matched per-instance."""
    n = 4096
    idx = AggregatedPrefixIndex(n)
    lineage = tuple(range(200))
    for iid in range(0, n, 7):
        idx.add(iid, lineage[: 1 + (iid % 180)])
    idx.add(n - 1, lineage)
    out = idx.match_depths(lineage)
    for iid in range(0, n - 1, 7):
        assert out[iid] == 1 + (iid % 180), iid
    assert out[n - 1] == len(lineage)
    assert out[1] == 0
    # wave path agrees with single walks, including reuse across the
    # LCP-sorted prefixes
    wave = [lineage[:d] for d in (200, 150, 97, 5, 0)]
    many = idx.match_depths_many(wave)
    for r, c in enumerate(wave):
        assert (many[r] == idx.match_depths(c)).all(), r
    # remove_instance is one column clear + prune, not a tree walk
    idx.remove_instance(n - 1)
    assert idx.match_depths(lineage)[n - 1] == 0


def test_pairwise_lcp_matches_bruteforce():
    rng = np.random.RandomState(3)
    for _ in range(30):
        u = rng.randint(1, 14)
        chains = [tuple(rng.randint(0, 3, rng.randint(0, 9)))
                  for _ in range(u)]
        got = _pairwise_lcp(chains)
        want = np.zeros((u, u), dtype=np.int64)
        nonempty = [i for i, c in enumerate(chains) if c]
        if nonempty:
            _lcp_block(chains, want, nonempty)
        for i, c in enumerate(chains):
            want[i, i] = len(c)
        assert (got == want).all(), chains


# ---------------------------------------------------------------------------
# hypothesis property test (optional dev dep, as in test_properties.py;
# guarded inside the test so the deterministic suite above always runs)
# ---------------------------------------------------------------------------
def test_property_flat_index_matches_reference():
    """Random protocol-respecting interleavings of add / remove_leaf /
    remove_instance give hit vectors identical to the bigint reference,
    checked through match_depths_many after every mutation burst."""
    pytest.importorskip(
        "hypothesis",
        reason="optional dev dep (requirements-dev.txt); property tests only")
    import hypothesis.strategies as st
    from hypothesis import given, settings

    chain = st.lists(st.integers(0, 4), min_size=1, max_size=8).map(tuple)
    ops = st.lists(
        st.one_of(
            st.tuples(st.just("insert"), st.integers(0, 5), chain),
            st.tuples(st.just("evict"), st.integers(0, 5),
                      st.integers(1, 6)),
            st.tuples(st.just("clear"), st.integers(0, 5), st.just(0)),
        ),
        min_size=1, max_size=60)

    @settings(max_examples=60, deadline=None)
    @given(ops, st.lists(chain, min_size=1, max_size=6))
    def run(op_seq, probes):
        pair = _Pair(6, capacity_tokens=12 * B)
        for kind, iid, arg in op_seq:
            if kind == "insert":
                pair.kvs[iid].insert(arg)
            elif kind == "evict":
                pair.kvs[iid].evict_tokens(arg * B)
            else:
                pair.kvs[iid].clear()
            pair.check_state()
        pair.check(list(probes) + [()])
        pair.rebuild_matches(list(probes))

    run()
