"""Differential tests for the sharded aggregated prefix index.

``ShardedPrefixIndex`` partitions the flat bitset index by instance-id
range; because instance ``i``'s hit depth depends only on instance
``i``'s own chains, the partition must be *exact*: at every shard count
the concatenated per-shard hit vectors equal the unsharded flat index
(and the frozen bigint reference) under any protocol-respecting
interleaving of add / remove_leaf / remove_instance — driven here, as
in ``test_prefix_index.py``, through real ``RadixKVIndex`` trees so
only callback-reachable mutation orders are explored.  On top of the
index-level identity, ``Router.route_batch`` with a sharded factory
must reproduce the unsharded (and scalar-reference) decisions over the
2k-request hotspot trace — the acceptance bar for the sharded router
tier.
"""
import collections
import copy

import numpy as np
import pytest

from repro.core import make_policy, Router
from repro.core._prefix_ref import AggregatedPrefixIndexRef
from repro.core.indicators import (AggregatedPrefixIndex,
                                   IndicatorFactory, shard_bounds)
from repro.core.radix import RadixKVIndex
from repro.core.scalar_ref import make_scalar_policy
from repro.core.sharded_index import ShardedPrefixIndex
from repro.workloads.traces import make_hotspot_trace

B = 4  # block size for the driver trees
SHARD_COUNTS = (1, 2, 4, 8)


class _Trio:
    """Flat + sharded + bigint reference driven by one set of trees."""

    def __init__(self, n, n_shards, capacity_tokens=10 ** 9,
                 parallel=False):
        self.n = n
        self.flat = AggregatedPrefixIndex(n, capacity=2)
        self.sharded = ShardedPrefixIndex(n, n_shards, capacity=2,
                                          parallel=parallel)
        self.ref = AggregatedPrefixIndexRef(n)
        self.all = (self.flat, self.sharded, self.ref)
        self.kvs = []
        for i in range(n):
            kv = RadixKVIndex(block_size=B,
                              capacity_tokens=capacity_tokens)
            kv.on_insert = (lambda blocks, _i=i: [
                idx.add(_i, blocks) for idx in self.all])
            kv.on_evict = (lambda path, m, _i=i: [
                idx.remove_tail(_i, path, m) for idx in self.all[:2]]
                + [self.ref.remove_leaf(_i, path[:len(path) - j])
                   for j in range(m)])
            kv.on_clear = (lambda _i=i: [
                idx.remove_instance(_i) for idx in self.all])
            self.kvs.append(kv)

    def check(self, probes):
        want = self.ref.match_depths_many(probes)
        assert (self.flat.match_depths_many(probes) == want).all()
        got = self.sharded.match_depths_many(probes)
        assert (got == want).all(), (got, want)
        for c in probes:
            a = self.sharded.match_depths(c)
            assert (a == self.flat.match_depths(c)).all(), c
            assert (a == self.sharded.match_depths_many([c])[0]).all(), c


def _chain_pool(rng, n_chains=48, alphabet=6, max_len=12):
    return [tuple(rng.randint(0, alphabet, rng.randint(1, max_len)))
            for _ in range(n_chains)]


def test_shard_bounds_partition():
    """Bounds tile [0, n) contiguously with sizes within one; the
    sharded index's owner mapping agrees with them."""
    for n, S in [(1, 1), (7, 3), (16, 4), (63, 8), (64, 8), (65, 8),
                 (130, 7), (4096, 8)]:
        bounds = shard_bounds(n, S)
        assert bounds[0][0] == 0 and bounds[-1][1] == n
        sizes = [hi - lo for lo, hi in bounds]
        assert max(sizes) - min(sizes) <= 1
        for (_, hi), (lo, _) in zip(bounds, bounds[1:]):
            assert hi == lo
        idx = ShardedPrefixIndex(n, S)
        for s, (lo, hi) in enumerate(bounds):
            for i in (lo, hi - 1):
                assert idx._local(i) == (s, i - lo)


@pytest.mark.parametrize("n,n_shards",
                         [(5, 2), (16, 4), (63, 8), (64, 8), (65, 4),
                          (130, 8), (256, 8)])
def test_random_interleavings_match_flat_and_ref(n, n_shards):
    rng = np.random.RandomState(n * 31 + n_shards)
    trio = _Trio(n, n_shards, capacity_tokens=15 * B)  # tight: evictions
    pool = _chain_pool(rng)
    for step in range(250):
        op, i = rng.rand(), rng.randint(n)
        if op < 0.65:
            trio.kvs[i].insert(pool[rng.randint(len(pool))])
        elif op < 0.85:
            trio.kvs[i].evict_tokens(int(rng.randint(1, 8)) * B)
        elif op < 0.95:
            trio.kvs[i].clear()
        if step % 29 == 0:
            k = rng.randint(1, 9)
            probes = [pool[rng.randint(len(pool))] for _ in range(k)]
            probes.append(())                     # empty chain row
            probes.append((99_999, 1))            # miss at the root
            trio.check(probes)
    trio.check(pool)
    assert trio.sharded.n_nodes == sum(
        sh.n_nodes for sh in trio.sharded.shards)


def test_parallel_fanout_deterministic():
    """parallel=True must give the identical matrix as serial fan-out,
    run-to-run: each shard writes only its own column slice, so the
    merge cannot depend on thread completion order."""
    rng = np.random.RandomState(3)
    serial = _Trio(64, 8)
    par = _Trio(64, 8, parallel=True)
    pool = _chain_pool(rng)
    for _ in range(300):
        i, c = rng.randint(64), pool[rng.randint(len(pool))]
        serial.kvs[i].insert(c)
        par.kvs[i].insert(c)
    a = serial.sharded.match_depths_many(pool)
    for _ in range(3):      # repeated runs: no completion-order effects
        b = par.sharded.match_depths_many(pool)
        assert (a == b).all()
    assert (serial.sharded.match_depths(pool[0])
            == par.sharded.match_depths(pool[0])).all()


def test_shard_walk_telemetry():
    """Every query fans to every shard: per-shard walk counters advance
    in lockstep and Router.walk_telemetry exposes the critical path."""
    router = Router(make_policy("lmetric"), 16, n_shards=4)
    reqs = make_hotspot_trace(qps=10.0, duration=30.0, seed=1)[:100]
    for r in copy.deepcopy(reqs):
        router.route(r, r.arrival)
    t = router.walk_telemetry()
    assert [s["shard"] for s in t["shards"]] == [0, 1, 2, 3]
    assert [(s["lo"], s["hi"]) for s in t["shards"]] \
        == shard_bounds(16, 4)
    walks = {s["walks"] for s in t["shards"]}
    assert walks == {router.factory.walks} and router.factory.walks > 0
    assert t["max_shard_us"] == max(s["mean_walk_us"]
                                    for s in t["shards"]) > 0
    # unsharded factories report one pseudo-shard covering [0, n)
    flat = Router(make_policy("lmetric"), 16)
    for r in copy.deepcopy(reqs[:20]):
        flat.route(r, r.arrival)
    ft = flat.walk_telemetry()
    assert len(ft["shards"]) == 1
    assert (ft["shards"][0]["lo"], ft["shards"][0]["hi"]) == (0, 16)
    assert ft["max_shard_us"] == ft["mean_walk_us"]


def test_device_mirror_per_shard_dirty():
    """device_view re-uploads only touched mirror shards; values always
    equal the numpy source of truth; bare mark_dirty() is the
    conservative full invalidation."""
    jax = pytest.importorskip("jax")  # noqa: F841 (mirror needs jax)
    f = IndicatorFactory(16, n_shards=4)
    dev = f.device_view()
    cached = list(f._dev_shards)
    f[0].r_bs = 3                     # touches mirror shard 0 only
    f[13].on_decode_token()           # touches mirror shard 3 only
    dev = f.device_view()
    assert f._dev_shards[0] is not cached[0]
    assert f._dev_shards[3] is not cached[3]
    assert f._dev_shards[1] is cached[1] and f._dev_shards[2] is cached[2]
    for got, want in zip(dev, (f.r_bs, f.q_bs, f.queued_prefill_tokens,
                               f.total_tokens)):
        assert (np.asarray(got) == want).all()
    cached = list(f._dev_shards)
    f.r_bs[5:12] = 7                  # external batch write...
    f.mark_dirty()                    # ...conservative full flip
    dev = f.device_view()
    assert all(s is not c for s, c in zip(f._dev_shards, cached))
    assert (np.asarray(dev[0]) == f.r_bs).all()


def test_exact_only_factory_ignores_index_sharding():
    """exact_only has no aggregated index to shard, but the mirror
    partition still applies and hits_for still answers."""
    f = IndicatorFactory(8, exact_only=True, n_shards=4)
    assert f._agg is None
    f[2].kv.insert((1, 2, 3))
    hits = f.hits_for(type("R", (), {"blocks": (1, 2, 3),
                                     "prompt_len": 3 * 64})())
    assert hits[2] > 0 and hits.shape == (8,)
    assert len(f.shard_walk_stats()) == 1       # pseudo-shard fallback


# ---------------------------------------------------------------------------
# route_batch bit-identity with a sharded factory
# ---------------------------------------------------------------------------
def _drive(router, reqs, batch, use_batch):
    """Same wave/drain schedule as test_batch_routing._drive: factory
    states agree between runs as long as decisions do."""
    decisions = []
    outstanding = collections.deque()
    reqs = copy.deepcopy(reqs)
    for i in range(0, len(reqs), batch):
        wave = reqs[i:i + batch]
        now = wave[0].arrival
        if use_batch:
            iids = router.route_batch(wave, now)
        else:
            iids = [router.route(r, now) for r in wave]
        decisions.extend(iids)
        for r, iid in zip(wave, iids):
            outstanding.append((iid, r, r.new_tokens))
            router.factory[iid].on_prefill_progress(256)
        for _ in range(len(wave)):
            if len(outstanding) > 2:
                did, dreq, dnew = outstanding.popleft()
                di = router.factory[did]
                di.on_prefill_progress(dnew)
                di.on_start_running(dreq)
                for _ in range(dreq.output_len % 7):
                    di.on_decode_token()
                di.on_finish(dreq)
    return decisions


@pytest.fixture(scope="module")
def trace():
    reqs = make_hotspot_trace(qps=14.0, duration=160.0, seed=5,
                              burst_start=40.0, burst_len=70.0)
    assert len(reqs) >= 2000, f"trace too small: {len(reqs)}"
    return reqs[:2000]


def _router(policy, n_shards=1, **kw):
    return Router(policy, 16, kv_capacity_tokens=150_000,
                  n_shards=n_shards, **kw)


def test_route_batch_sharded_quick(trace):
    """Non-slow smoke: sharded batch == unsharded batch == sequential
    over the first 600 hotspot requests."""
    sub = trace[:600]
    seq = _drive(_router(make_policy("lmetric")), sub, 8, False)
    for S in (2, 8):
        got = _drive(_router(make_policy("lmetric"), n_shards=S),
                     sub, 8, True)
        assert got == seq, f"shards={S}"


@pytest.mark.slow
def test_route_batch_sharded_2k_bit_identity(trace):
    """The acceptance run: sharded route_batch decisions over the full
    2k-request hotspot trace are bit-identical to unsharded sequential
    routing AND to the frozen scalar reference at 1/2/4/8 shards
    (parallel fan-out included at the widest count)."""
    seq = _drive(_router(make_policy("lmetric")), trace, 64, False)
    ref = _drive(_router(make_scalar_policy("lmetric")), trace, 64,
                 False)
    assert seq == ref
    for S in SHARD_COUNTS:
        got = _drive(_router(make_policy("lmetric"), n_shards=S),
                     trace, 64, True)
        assert got == seq, f"shards={S} diverged from sequential"
    par = _drive(_router(make_policy("lmetric"), n_shards=8,
                         parallel_walks=True), trace, 64, True)
    assert par == seq


# ---------------------------------------------------------------------------
# hypothesis property test (optional dev dep, as in test_prefix_index)
# ---------------------------------------------------------------------------
def test_property_sharded_matches_flat_and_reference():
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

    @settings(max_examples=40, deadline=None)
    @given(ops, st.lists(chain, min_size=1, max_size=6),
           st.sampled_from([2, 3, 6]))
    def run(op_seq, probes, n_shards):
        trio = _Trio(6, n_shards, capacity_tokens=12 * B)
        for kind, iid, arg in op_seq:
            if kind == "insert":
                trio.kvs[iid].insert(arg)
            elif kind == "evict":
                trio.kvs[iid].evict_tokens(arg * B)
            else:
                trio.kvs[iid].clear()
        trio.check(list(probes) + [()])

    run()
