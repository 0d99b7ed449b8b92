"""Backend equivalence + lifecycle tests for the pluggable shard
backends (``repro.core.shard_backends``).

The contract under test: serial, thread, and process execution of the
sharded prefix index are **bit-identical** to the flat
``AggregatedPrefixIndex`` under arbitrary mutation/walk interleavings at
any shard count — and the process backend never leaks ``/dev/shm``
segments or worker processes, including on the mid-query failure path.

Random interleavings run twice: seeded-rng versions always run (they
are the tier-1 pin), and hypothesis-driven versions run when the
optional dev dependency is installed (drawn interleavings shrink to
minimal counterexamples).
"""
import multiprocessing as mp
import os

import numpy as np
import pytest

from repro.core import IndicatorFactory
from repro.core.indicators import AggregatedPrefixIndex
from repro.core.sharded_index import ShardedPrefixIndex

try:
    from hypothesis import HealthCheck, given, settings
    import hypothesis.strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

BACKENDS = ("serial", "thread", "process")
SHARD_COUNTS = (1, 2, 4, 8)


def _shm_segments():
    """Names of live shared-memory segments (Linux tmpfs)."""
    try:
        return {n for n in os.listdir("/dev/shm") if n.startswith("psm_")}
    except FileNotFoundError:          # non-Linux: best effort
        return set()


def _live_workers():
    return [p for p in mp.active_children()
            if p.name.startswith("prefix-shard")]


def _rand_chain(rng, vocab=6, max_len=10):
    length = int(rng.integers(1, max_len))
    return tuple(int(x) for x in rng.integers(0, vocab, size=length))


def _apply_ops(rng, ref, idxs, n, steps):
    """Drive one random mutation/walk interleaving through the flat
    reference and every sharded index, asserting equality on walks."""
    held = []
    for step in range(steps):
        op = rng.random()
        if op < 0.55 or not held:
            iid = int(rng.integers(0, n))
            chain = _rand_chain(rng)
            ref.add(iid, chain)
            for ix in idxs.values():
                ix.add(iid, chain)
            held.append((iid, chain))
        elif op < 0.70:
            iid, chain = held.pop(int(rng.integers(0, len(held))))
            # m == 0: one leaf; else one eviction run of the deepest m
            m = int(rng.integers(0, len(chain) + 1))
            args = (iid, chain, m) if m else (iid, chain)
            name = "remove_tail" if m else "remove_leaf"
            for ix in (ref, *idxs.values()):
                getattr(ix, name)(*args)
        elif op < 0.78:
            iid = int(rng.integers(0, n))
            ref.remove_instance(iid)
            for ix in idxs.values():
                ix.remove_instance(iid)
            held = [(i, c) for i, c in held if i != iid]
        else:
            queries = [_rand_chain(rng)
                       for _ in range(int(rng.integers(1, 5)))]
            want = ref.match_depths_many(queries)
            for name, ix in idxs.items():
                got = ix.match_depths_many(queries)
                assert np.array_equal(want, got), (name, step)
    # final checks: wave walk, single walk, node counts
    queries = [_rand_chain(rng) for _ in range(4)]
    want_many = ref.match_depths_many(queries)
    single = _rand_chain(rng)
    want_one = ref.match_depths(single)
    for name, ix in idxs.items():
        assert np.array_equal(want_many, ix.match_depths_many(queries)), name
        assert np.array_equal(want_one, ix.match_depths(single)), name
        # a lineage held by instances of several shards is stored once
        # per shard tree, so the sharded total can only be >= the flat
        assert ix.n_nodes >= ref.n_nodes, name


# ---------------------------------------------------------------------------
# seeded interleavings — always run (tier-1)
# ---------------------------------------------------------------------------
@pytest.mark.process
@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
def test_backend_equivalence_random_interleaving(n_shards):
    """Serial == thread == process == flat reference, bit-for-bit,
    under a seeded random mutation/walk interleaving."""
    n = 32
    rng = np.random.default_rng(100 + n_shards)
    ref = AggregatedPrefixIndex(n)
    idxs = {b: ShardedPrefixIndex(n, n_shards, backend=b)
            for b in BACKENDS}
    try:
        _apply_ops(rng, ref, idxs, n, steps=150)
    finally:
        for ix in idxs.values():
            ix.close()


@pytest.mark.process
def test_process_smoke_256_instances_2_shards():
    """The tier-1 CI smoke: a small but real process-backed index —
    routed mutations, wave walks, telemetry, clean shutdown."""
    before = _shm_segments()
    n = 256
    rng = np.random.default_rng(7)
    ref = AggregatedPrefixIndex(n)
    idx = ShardedPrefixIndex(n, 2, backend="process")
    try:
        for _ in range(80):
            iid = int(rng.integers(0, n))
            chain = _rand_chain(rng)
            ref.add(iid, chain)
            idx.add(iid, chain)
        queries = [_rand_chain(rng) for _ in range(6)]
        assert np.array_equal(ref.match_depths_many(queries),
                              idx.match_depths_many(queries))
        stats = idx.shard_stats()
        assert len(stats) == 2
        assert sum(s["walks"] for s in stats) == 12  # 6 chains × 2 shards
    finally:
        idx.close()
    assert _shm_segments() <= before
    assert not _live_workers()


# ---------------------------------------------------------------------------
# lifecycle: no leaked segments or workers
# ---------------------------------------------------------------------------
@pytest.mark.process
def test_no_leaked_shm_or_workers_after_close():
    before = _shm_segments()
    idx = ShardedPrefixIndex(64, 4, backend="process")
    idx.add(3, (1, 2, 3))
    idx.add(40, (1, 2))
    assert idx.match_depths((1, 2, 3))[3] == 3
    # while alive: 4 mask segments + 1 telemetry block exist
    assert len(_shm_segments() - before) >= 5
    assert len(_live_workers()) == 4
    idx.close()
    idx.close()                       # idempotent
    assert _shm_segments() <= before
    assert not _live_workers()


@pytest.mark.process
def test_factory_context_manager_closes_backend():
    """``IndicatorFactory`` teardown must release the walk backend —
    the context-manager form the router's ``close`` path uses."""
    before = _shm_segments()
    with IndicatorFactory(64, kv_capacity_tokens=1 << 20, n_shards=2,
                          walk_backend="process") as factory:
        factory[5].kv.insert((1, 2, 3))   # on_insert hook → routed add
        assert factory._agg.match_depths((1, 2, 3))[5] == 3
        assert len(_live_workers()) == 2
    assert _shm_segments() <= before
    assert not _live_workers()


@pytest.mark.process
def test_midquery_failure_unlinks_segments():
    """A worker error mid-query tears the backend down: the query
    raises, and every segment (masks, telemetry, walk scratch) is
    unlinked with no worker left behind."""
    before = _shm_segments()
    idx = ShardedPrefixIndex(32, 2, backend="process")
    idx.add(1, (1, 2, 3))
    idx.add(20, (1, 2, 3, 4))
    idx.backend.inject_failure(0)
    with pytest.raises(RuntimeError, match=r"prefix-shard \d+ worker"):
        idx.match_depths_many([(1, 2, 3), (1, 2)])
    assert idx.backend._closed
    idx.close()                       # idempotent after teardown
    assert _shm_segments() <= before
    assert not _live_workers()


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="unknown shard backend"):
        ShardedPrefixIndex(16, 2, backend="gpu")


# ---------------------------------------------------------------------------
# hypothesis-driven interleavings (optional dev dep)
# ---------------------------------------------------------------------------
if HAVE_HYPOTHESIS:
    @pytest.fixture
    def live_backends():
        """Long-lived sharded indexes reused across one test's hypothesis
        examples (process workers are too expensive to respawn per
        example); reset between examples by removing every instance, and
        closed when the test ends, so no worker outlives it."""
        live = {}
        yield live
        for trio in live.values():
            for ix in trio.values():
                ix.close()

    @st.composite
    def interleavings(draw):
        ops = []
        # per instance, the prefix-closed set of chains it holds: a
        # removal takes only what the radix callback protocol can (a
        # leaf, or a run of leaves up a chain tail), since a removal
        # that breaks prefix-closure leaves hit vectors undefined
        # (the subset invariant)
        held = {}
        for _ in range(draw(st.integers(10, 60))):
            kind = draw(st.sampled_from(
                ["add", "add", "add", "remove", "drop", "walk"]))
            if kind == "add":
                iid = draw(st.integers(0, 31))
                chain = tuple(draw(st.lists(st.integers(0, 5),
                                            min_size=1, max_size=8)))
                held.setdefault(iid, set()).update(
                    chain[:d] for d in range(1, len(chain) + 1))
                ops.append(("add", iid, chain))
            elif kind == "remove" and held:
                iid = draw(st.sampled_from(sorted(held)))
                nodes = held[iid]
                inner = {p[:-1] for p in nodes}
                leaf = draw(st.sampled_from(sorted(nodes - inner)))
                # the longest run: climb while the parent has no other
                # child left, so it becomes the next leaf
                top = 1
                while (top < len(leaf) and sum(
                        1 for p in nodes
                        if p[:-1] == leaf[:len(leaf) - top]) == 1):
                    top += 1
                # m == 0: one leaf; else one eviction run of m leaves
                m = draw(st.integers(0, top))
                nodes.difference_update(
                    leaf[:len(leaf) - j] for j in range(max(m, 1)))
                if not nodes:
                    del held[iid]
                ops.append(("remove_tail", iid, leaf, m) if m
                           else ("remove_leaf", iid, leaf))
            elif kind == "drop":
                iid = draw(st.integers(0, 31))
                held.pop(iid, None)
                ops.append(("remove_instance", iid))
            else:
                qs = draw(st.lists(
                    st.lists(st.integers(0, 5), min_size=1, max_size=8),
                    min_size=1, max_size=4))
                ops.append(("walk", [tuple(q) for q in qs]))
        return ops

    # the fixture is set up once per test, not per example, on purpose
    @settings(max_examples=15, deadline=None,
              suppress_health_check=[HealthCheck.function_scoped_fixture])
    @given(ops=interleavings(),
           n_shards=st.sampled_from(SHARD_COUNTS))
    @pytest.mark.process
    def test_hypothesis_backend_equivalence(ops, n_shards, live_backends):
        n = 32
        if n_shards not in live_backends:
            live_backends[n_shards] = {
                b: ShardedPrefixIndex(n, n_shards, backend=b)
                for b in BACKENDS}
        idxs = live_backends[n_shards]
        for ix in idxs.values():       # reset from the previous example
            for iid in range(n):
                ix.remove_instance(iid)
        ref = AggregatedPrefixIndex(n)
        for op in ops:
            if op[0] == "walk":
                want = ref.match_depths_many(op[1])
                for name, ix in idxs.items():
                    assert np.array_equal(
                        want, ix.match_depths_many(op[1])), name
            else:
                getattr(ref, op[0])(*op[1:])
                for ix in idxs.values():
                    getattr(ix, op[0])(*op[1:])
        final = [(0, 1, 2), (3,)]
        want = ref.match_depths_many(final)
        for name, ix in idxs.items():
            assert np.array_equal(want, ix.match_depths_many(final)), name


# ---------------------------------------------------------------------------
# full-scale sweep (slow tier)
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.process
@pytest.mark.parametrize("n_shards", (4, 8))
def test_backend_equivalence_16384_instances(n_shards):
    """The acceptance-scale sweep: 16384 instances, heavy chain load,
    all three backends against the flat reference."""
    n = 16384
    rng = np.random.default_rng(42)
    ref = AggregatedPrefixIndex(n)
    idxs = {b: ShardedPrefixIndex(n, n_shards, backend=b)
            for b in BACKENDS}
    try:
        for _ in range(400):
            iid = int(rng.integers(0, n))
            chain = _rand_chain(rng, vocab=9, max_len=14)
            ref.add(iid, chain)
            for ix in idxs.values():
                ix.add(iid, chain)
        queries = [_rand_chain(rng, vocab=9, max_len=14)
                   for _ in range(16)]
        want = ref.match_depths_many(queries)
        for name, ix in idxs.items():
            assert np.array_equal(want, ix.match_depths_many(queries)), name
    finally:
        for ix in idxs.values():
            ix.close()
    assert not _live_workers()


def test_core_import_leaves_jax_unloaded():
    """Process-backend shard workers import ``repro.core`` and must never
    touch JAX: a process that loads it could take the accelerator from
    the parent that serves on it."""
    import subprocess
    import sys
    code = ("import sys, repro.core, repro.core.shard_backends; "
            "assert not any(m == 'jax' or m.startswith('jax.') "
            "for m in sys.modules), 'repro.core imported jax'")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120,
                   env={**os.environ, "PYTHONPATH": src})
