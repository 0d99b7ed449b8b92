"""Real-engine integration: prefix-cache compute skip, chunked prefill
correctness, cluster routing."""
import sys
import threading

import jax
import numpy as np
import pytest

from repro.cluster.metrics import summarize
from repro.configs import get_config
from repro.core import LMetricPolicy
from repro.models import Model
from repro.serving.engine import EngineCluster, InstanceEngine
from repro.core.types import Request


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen3_4b-smoke")
    m = Model(cfg)
    params = m.init(jax.random.key(0))
    return cfg, m, params


def _arrivals(n=8, seed=0, share=True):
    rng = np.random.RandomState(seed)
    shared = rng.randint(4, 500, size=48)
    out, t = [], 0.0
    for i in range(n):
        t += float(rng.exponential(0.05))
        sfx = rng.randint(4, 500, size=16)
        toks = (np.concatenate([shared, sfx]) if share and i % 2 else
                rng.randint(4, 500, size=64)).astype(np.int32)
        out.append((t, toks, 6))
    return out


def test_cluster_serves_all_and_hits_prefix(setup):
    cfg, m, params = setup
    cluster = EngineCluster(2, m, params, LMetricPolicy(), block_size=16,
                            max_batch=4, max_len=160, chunk_tokens=64)
    done = cluster.run(_arrivals())
    s = summarize(done)
    assert s["n"] == 8
    assert s["ttft_mean"] > 0 and s["tpot_mean"] > 0
    hits = [r.hit_tokens for r in done]
    assert any(h >= 48 // 16 * 16 for h in hits), \
        "shared prefix must produce cache hits"


def test_engine_outputs_match_unchunked_reference(setup):
    """Greedy decode via the engine == greedy decode via plain
    prefill+decode on the same model."""
    cfg, m, params = setup
    rng = np.random.RandomState(3)
    toks = rng.randint(4, 500, size=40).astype(np.int32)
    n_new = 5
    # reference: full prefill, then argmax decode loop
    import jax.numpy as jnp
    logits, _ = jax.jit(m.prefill)(params, jnp.asarray(toks[None]), {})
    cache = m.init_cache(1, 128)
    pos = jnp.arange(40, dtype=jnp.int32)[None]
    l, cache = jax.jit(m.prefill_cached)(params, jnp.asarray(toks[None]),
                                         pos, cache,
                                         jnp.zeros((1,), jnp.int32))
    ref_out = [int(np.asarray(l)[0, -1].argmax())]
    cur = ref_out[0]
    p = 40
    for _ in range(n_new - 1):
        lg, cache = jax.jit(m.decode_step)(
            params, jnp.asarray([[cur]], jnp.int32),
            jnp.asarray([p], jnp.int32), cache)
        cur = int(np.asarray(lg)[0, -1].argmax())
        ref_out.append(cur)
        p += 1
    # engine path (chunked prefill in 16-token chunks)
    eng = InstanceEngine(m, params, max_batch=2, max_len=128,
                         chunk_tokens=16, block_size=16)
    req = Request(rid=0, arrival=0.0, blocks=(), prompt_len=40,
                  output_len=n_new)
    eng.submit(req, toks)
    outs = None
    for _ in range(100):
        ev = eng.step()
        if ev["finished"]:
            outs = ev["finished"][0].out_tokens
            break
        if not eng.has_work():
            break
    assert outs == ref_out


def test_prefix_hit_preserves_output(setup):
    """Serving the same prompt twice: the second (cache-hit) serve must
    emit the same tokens as the first (compute skip is exact)."""
    cfg, m, params = setup
    rng = np.random.RandomState(5)
    toks = rng.randint(4, 500, size=64).astype(np.int32)
    eng = InstanceEngine(m, params, max_batch=2, max_len=128,
                         chunk_tokens=32, block_size=16)

    def serve():
        req = Request(rid=0, arrival=0.0, blocks=(), prompt_len=64,
                      output_len=4)
        eng.submit(req, toks)
        for _ in range(100):
            ev = eng.step()
            if ev["finished"]:
                return ev["finished"][0], ev["finished"][0].out_tokens
        raise AssertionError("did not finish")

    seq1, out1 = serve()
    seq2, out2 = serve()
    assert seq1.req.hit_tokens == 0
    assert seq2.req.hit_tokens >= 48, "second serve must hit the prefix"
    assert out1 == out2, "cache-hit serve must be exact"


def test_partial_prefix_hit_matches_cold_serve(setup):
    """A prompt that shares only the first blocks of a stored chain
    reuses those blocks' KV, and emits what a cold serve emits."""
    cfg, m, params = setup
    rng = np.random.RandomState(7)
    shared = rng.randint(4, 500, size=48)
    a, b = (np.concatenate([shared, rng.randint(4, 500, size=16)])
            .astype(np.int32) for _ in range(2))

    def serve(eng, toks):
        req = Request(rid=0, arrival=0.0, blocks=(), prompt_len=64,
                      output_len=5)
        eng.submit(req, toks)
        for _ in range(100):
            ev = eng.step()
            if ev["finished"]:
                return ev["finished"][0]
        raise AssertionError("did not finish")

    def engine():
        return InstanceEngine(m, params, max_batch=2, max_len=128,
                              chunk_tokens=32, block_size=16)

    warm = engine()
    serve(warm, a)
    hit = serve(warm, b)
    cold = serve(engine(), b)
    assert hit.reused == 48 and cold.reused == 0
    assert hit.out_tokens == cold.out_tokens


@pytest.mark.parametrize("partial,want", [(False, 0), (True, 32)])
def test_prefix_store_partial_lookup(partial, want):
    """Only a partial store serves a prompt that diverges inside a
    stored chain; both serve a stored chain that is a whole prefix."""
    from repro.serving.engine import PrefixStore
    store = PrefixStore(partial=partial)
    store.insert((1, 2, 3, 4), "frag", 64)
    assert store.lookup((1, 2, 9))[2] == want
    assert store.lookup((1, 2, 3, 4, 5))[1:] == ("frag", 64)
    assert store.lookup((7,)) == (None, None, 0)


def test_cluster_places_engines_on_devices(setup):
    """Each engine's params and cache live on its device; engines on
    one device share one copy of the params."""
    cfg, m, params = setup
    dev = jax.devices()[0]
    cluster = EngineCluster(2, m, params, LMetricPolicy(), devices=[dev],
                            block_size=16, max_batch=2, max_len=64)
    for e in cluster.engines:
        assert e.device == dev
        for leaf in jax.tree.leaves((e.params, e.cache)):
            assert leaf.devices() == {dev}
    a, b = (jax.tree.leaves(e.params)[0] for e in cluster.engines)
    assert a.unsafe_buffer_pointer() == b.unsafe_buffer_pointer()


def test_cluster_obs_serves_identical_tokens_and_counts_fragments(
        setup, tmp_path, monkeypatch):
    """A cluster with a profiler-clock bundle routes and serves exactly
    as one without; each fragment moved is one slot of the cache by its
    shapes, every prefill and decode step is spanned inside its engine
    step, and the engines' reuse is counted beside the router's
    prediction."""
    import itertools
    import types
    from test_obs import inside, profiled_spans
    from repro.obs import make_obs
    from repro.serving import engine as engine_mod
    cfg, m, params = setup
    # the cluster's clock advances by each step's measured wall, which
    # would reorder events between runs: every step reads 5 ms here
    ticks = itertools.count()
    monkeypatch.setattr(engine_mod, "time", types.SimpleNamespace(
        perf_counter=lambda: next(ticks) * 0.005))
    kw = dict(block_size=16, max_batch=4, max_len=160, chunk_tokens=160)
    arrivals = _arrivals(n=10, seed=2)

    def serve(obs):
        cluster = EngineCluster(2, m, params, LMetricPolicy(), obs=obs,
                                **kw)
        done = cluster.run(arrivals)
        return cluster, {r.rid: (r.sched_to, r.hit_tokens,
                                 list(cluster.served[r.rid].out_tokens))
                         for r in done}

    _, base = serve(None)
    obs = make_obs(metrics=True, trace="profiler")
    got = []
    spans = profiled_spans(tmp_path / "p", lambda: got.append(serve(obs)))
    cluster, served = got[0]
    assert served == base
    # one slot of the cache, from the shapes alone
    shapes = jax.eval_shape(lambda: m.init_cache(1, kw["max_len"]))
    slot = sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(shapes))
    assert all(e.slot_bytes == slot for e in cluster.engines)
    seqs = list(cluster.served.values())
    c = obs.registry.counters
    assert c["engine.fragment_d2h_bytes"] == slot * len(seqs)
    injected = sum(1 for s in seqs if s.reused)
    assert injected > 0
    assert c["engine.fragment_h2d_bytes"] == slot * injected
    assert c["engine.reused_tokens"] == sum(s.reused for s in seqs)
    assert c["engine.predicted_hit_tokens"] > 0
    saves = [a for name, _, _, a in spans
             if name == "repro.engine.fragment_save"]
    assert len(saves) == len(seqs)
    assert all(a["bytes"] == slot for a in saves)
    for child in ("admit", "prefill", "fragment_save", "decode"):
        assert inside(spans, f"repro.engine.{child}", "repro.engine.step")
    kinds = {a["kind"] for name, _, _, a in spans
             if name == "repro.engine.step"}
    assert kinds == {"prefill", "decode"}


# ---------------------------------------------------------------------------
# prefix fragments copied to the host in the background

@pytest.fixture
def held(monkeypatch):
    """Hold every fragment's host copy in the copier's worker until the
    returned event is set; it is set at the latest when a test ends."""
    from repro.serving import engine as engine_mod
    gate = threading.Event()
    real = engine_mod._to_host

    def to_host(leaves, treedef):
        gate.wait(30)
        return real(leaves, treedef)

    monkeypatch.setattr(engine_mod, "_to_host", to_host)
    yield gate
    gate.set()


def _release_on_wait(monkeypatch, gate):
    """Set ``gate`` when the engine starts to wait on a copy; returns
    the list of the copier clock's readings."""
    from repro.serving import engine as engine_mod
    real, calls = engine_mod._clock_ns, []

    def clock():
        gate.set()
        calls.append(real())
        return calls[-1]

    monkeypatch.setattr(engine_mod, "_clock_ns", clock)
    return calls


def _req(rid, toks, out):
    return Request(rid=rid, arrival=0.0, blocks=(), prompt_len=len(toks),
                   output_len=out)


def _stored(eng):
    """The store's (future, reusable length) entries, oldest first."""
    return list(eng.prefix_store._store.values())


def test_saved_fragment_is_the_slot_at_the_prefills_end(setup, held):
    """The fragment a prefill saves is the slot as it was when the
    prefill ended, though later decode steps overwrite the slot before
    its copy is collected."""
    from repro.serving.engine import _slice_slot
    cfg, m, params = setup
    toks = np.random.RandomState(11).randint(4, 500, size=48).astype(
        np.int32)
    eng = InstanceEngine(m, params, max_batch=2, max_len=128,
                         chunk_tokens=64, block_size=16)
    eng.submit(_req(0, toks, 8), toks)
    ev = eng.step()
    seq, = ev["first"]
    then = jax.tree.map(np.asarray, _slice_slot(eng.cache, seq.slot))
    (pending, length), = _stored(eng)
    for _ in range(3):
        assert eng.step()["kind"] == "decode"
    now = jax.tree.map(np.asarray, _slice_slot(eng.cache, seq.slot))
    assert not pending.done()
    assert any(not np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(then), jax.tree.leaves(now)))
    held.set()
    got = pending.result(timeout=30)
    assert length == 48
    assert jax.tree.structure(got) == jax.tree.structure(then)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(then)):
        assert isinstance(a, np.ndarray)
        np.testing.assert_array_equal(a, b)
    eng.copier.drain()


def test_lookup_of_pending_fragment_injects_as_a_collected_one(
        setup, held, monkeypatch):
    """A prompt whose prefix is stored but still being copied waits for
    the copy and then reuses as many tokens, injects as many bytes and
    emits the same tokens as when the copy was collected first."""
    from repro.obs import make_obs
    cfg, m, params = setup
    rng = np.random.RandomState(13)
    shared = rng.randint(4, 500, size=48)
    a, b = (np.concatenate([shared, rng.randint(4, 500, size=16)])
            .astype(np.int32) for _ in range(2))
    _release_on_wait(monkeypatch, held)

    def serve(collect_first):
        obs = make_obs(metrics=True)
        eng = InstanceEngine(m, params, max_batch=2, max_len=128,
                             chunk_tokens=64, block_size=16, obs=obs)
        eng.submit(_req(0, a, 3), a)
        eng.step()
        (pending, _), = _stored(eng)
        if collect_first:
            held.set()
            pending.result(timeout=30)
        assert pending.done() == collect_first
        eng.submit(_req(1, b, 5), b)
        for _ in range(100):
            for seq in eng.step()["finished"]:
                if seq.req.rid == 1:
                    eng.copier.drain()
                    held.clear()
                    return seq, obs.registry.counters
        raise AssertionError("did not finish")

    hit, c_hit = serve(collect_first=False)
    ref, c_ref = serve(collect_first=True)
    assert hit.reused == ref.reused == 48
    assert hit.out_tokens == ref.out_tokens
    assert c_hit["engine.fragment_h2d_bytes"] == \
        c_ref["engine.fragment_h2d_bytes"] > 0
    assert c_hit["engine.fragment_waits"] == 1
    assert "engine.fragment_waits" not in c_ref


def test_one_fragment_in_flight_per_device(setup, monkeypatch):
    """Engines on one device share one copier, and a save slices its
    slot only once the copy before it has dropped its device buffers."""
    import time
    import weakref
    from repro.obs import make_obs
    from repro.serving import engine as engine_mod
    cfg, m, params = setup
    real_slice, real_host = engine_mod.slot_fragment, engine_mod._to_host
    live, seen = [], []

    def slot_fragment(cache, b):
        seen.append(sum(r() is not None for r in live))
        out = real_slice(cache, b)
        live.extend(weakref.ref(x) for x in jax.tree.leaves(out))
        return out

    def to_host(leaves, treedef):
        time.sleep(0.1)
        return real_host(leaves, treedef)

    monkeypatch.setattr(engine_mod, "slot_fragment", slot_fragment)
    monkeypatch.setattr(engine_mod, "_to_host", to_host)
    obs = make_obs(metrics=True)
    cluster = EngineCluster(3, m, params, LMetricPolicy(), block_size=16,
                            max_batch=4, max_len=160, chunk_tokens=160,
                            obs=obs)
    assert len({id(e.copier) for e in cluster.engines}) == 1
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        done = cluster.run(_arrivals(n=8, seed=4))
    finally:
        sys.setswitchinterval(interval)
    assert len(done) == 8
    # warm-up slices, then one per prefill
    assert len(seen) == len(cluster.engines) + len(done)
    assert max(seen) == 0
    assert obs.registry.counters["engine.fragment_waits"] > 0


@pytest.mark.parametrize("fail", [False, True])
def test_run_leaves_no_copy_in_flight(setup, monkeypatch, fail):
    """However ``EngineCluster.run`` ends, a step raising mid-run
    included, every stored fragment is on the host and no copier holds
    a device buffer or a thread when it returns."""
    import time
    from repro.serving import engine as engine_mod
    cfg, m, params = setup
    real_host = engine_mod._to_host

    def to_host(leaves, treedef):
        time.sleep(0.05)
        return real_host(leaves, treedef)

    monkeypatch.setattr(engine_mod, "_to_host", to_host)
    cluster = EngineCluster(2, m, params, LMetricPolicy(), block_size=16,
                            max_batch=4, max_len=160, chunk_tokens=160)
    if fail:
        eng = cluster.engines[0]
        orig = eng.step

        def step():
            ev = orig()
            if ev["first"]:        # a copy has just started
                raise RuntimeError("step failed")
            return ev

        eng.step = step
        with pytest.raises(RuntimeError, match="step failed"):
            cluster.run(_arrivals(n=8, seed=5))
    else:
        assert len(cluster.run(_arrivals(n=8, seed=5))) == 8
    assert not [t for t in threading.enumerate()
                if t.name.startswith("fragment-copy")]
    for c in cluster.copiers.values():
        assert c._pool is None and c._inflight is None
    stored = [f for e in cluster.engines for f, _ in _stored(e)]
    assert stored
    for f in stored:
        assert f.done()
        assert all(isinstance(x, np.ndarray)
                   for x in jax.tree.leaves(f.result()))


def test_fragment_waits_counted_only_with_obs(setup, held, monkeypatch,
                                              tmp_path):
    """A save behind a copy still in flight waits for it: with a
    metrics bundle the wait is counted and timed, inside the save's
    span; with ``obs=None`` nothing reads the copier's clock, and the
    tokens are the same."""
    from test_obs import inside, profiled_spans
    from repro.obs import make_obs
    cfg, m, params = setup
    rng = np.random.RandomState(17)
    prompts = [rng.randint(4, 500, size=32).astype(np.int32)
               for _ in range(2)]
    calls = _release_on_wait(monkeypatch, held)

    def serve(obs):
        eng = InstanceEngine(m, params, max_batch=2, max_len=64,
                             chunk_tokens=64, block_size=16, obs=obs)
        for rid, p in enumerate(prompts):
            eng.submit(_req(rid, p, 3), p)
        out = {}
        while eng.has_work():
            for seq in eng.step()["finished"]:
                out[seq.req.rid] = seq.out_tokens
        eng.copier.drain()
        held.clear()
        return out

    timer = threading.Timer(0.2, held.set)
    timer.start()
    base = serve(None)
    timer.join(30)
    assert not timer.is_alive()
    assert calls == []
    obs = make_obs(metrics=True, trace="profiler")
    got = []
    spans = profiled_spans(tmp_path / "p", lambda: got.append(
        serve(obs)))
    assert got[0] == base
    c = obs.registry.counters
    assert c["engine.fragment_waits"] == 1
    assert c["engine.fragment_wait_ns"] == calls[1] - calls[0] > 0
    assert inside(spans, "repro.engine.fragment_wait",
                  "repro.engine.fragment_save")
