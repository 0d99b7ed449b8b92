"""Real JAX serving engine (in-process): continuous batching, chunked
prefill, prefix-cache KV$ reuse — the substrate under the LMETRIC router
for the end-to-end example.

One ``InstanceEngine`` owns a slot-based KV cache (``max_batch`` slots ×
``max_len``), a jit'd chunked-prefill function (``Model.prefill_cached``)
and a jit'd batched decode step.  A host-side ``PrefixStore`` keeps KV
fragments (or recurrent-state snapshots) keyed by block-hash chains: on a
KV$ hit the fragment is injected into the slot and ONLY the suffix tokens
are prefilled — the paper's compute skip, for real.

A finished prefill stores its slot as a fragment without waiting for it:
one jitted program (``slot_fragment``) slices the slot into buffers of
its own, and the ``FragmentCopier`` of the engine's device starts their
copy to the host and collects it in a worker thread, while the host
steps this engine and the others.  The store keeps the fragment's
``Future`` under its key at once; a lookup that picks it waits for it
then.  The engines on one device share one copier, which holds at most
one fragment's device buffers (a save first waits for the copy before):
a whole slot is 170 MB for Qwen3-4B, on a chip that four engines'
caches and a whole-prompt prefill's temporaries fill to ~97%.

``EngineCluster`` wires N engines to a ``core.Router`` under a
virtual-time event loop whose step durations are the *measured* wall
times of the JAX computations, giving honest relative TTFT/TPOT between
policies.  Each engine may be pinned to a device (its params and cache
live there); engines on one device share one copy of the params.

``EngineCluster(..., obs=...)`` hands an observability bundle
(``repro.obs``) to its router and its engines.  An engine step then
records the span ``engine.step`` (iid, kind) around ``engine.admit``
(slot zeroing, store lookup, fragment injection), ``engine.prefill``
(dispatch to ``block_until_ready``), ``engine.fragment_save`` (the slot
sliced, its copy to the host started and the pending fragment stored;
bytes) and ``engine.decode`` (dispatch to the tokens on the host; bs),
and the counters ``engine.fragment_d2h_bytes``/
``engine.fragment_h2d_bytes`` (fragment leaves' bytes, counted as a copy
starts and as a fragment is injected) and
``engine.predicted_hit_tokens``/``engine.reused_tokens`` (the router's
hit for an admitted request and the tokens the engine took from its
store).  Each time the engine blocks on a copy still in flight (a save
behind the one before, or a lookup that picked a pending fragment) it
records the span ``engine.fragment_wait`` (inside
``engine.fragment_save`` or ``engine.admit``) and counts
``engine.fragment_waits`` and ``engine.fragment_wait_ns``: how often the
overlap fails, and what it costs.  An MoE model's steps also count, from
the ``moe_counts`` its programs return in the cache (a decode step
computes every slot, a free one's stale token too):
``engine.moe_routed_local`` (routing choices that landed on a held
expert, prefill and decode), and over decode steps and MoE layers
``engine.moe_layer_steps``, ``engine.moe_experts_touched`` (held experts
given at least one choice) and ``engine.moe_max_expert_tokens`` (the
most choices one held expert took).  With ``obs=None`` none of it runs
and nothing is copied to the host for it (Contract 5).  The ``moe_tap``
that an MoE model's programs also leave in the cache (``Model``) stays
on the device: the engine reads nothing of it.

Encoder-decoder archs (whisper) are not served by this engine (the
cluster simulator covers their scheduling); everything decoder-only —
dense, MoE, SSM, hybrid — works.
"""
from __future__ import annotations

import collections
import concurrent.futures
import contextlib
import functools
import heapq
import itertools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.radix import tokens_to_blocks
from repro.core.router import Router
from repro.core.types import Request
from repro.models import Model


# ---------------------------------------------------------------------------
# cache slot surgery
# ---------------------------------------------------------------------------

def _slice_slot(cache, b: int):
    """Extract slot b as a B=1 cache view (units axis 1, rest axis 0)."""
    units = jax.tree.map(
        lambda l: jax.lax.dynamic_slice_in_dim(l, b, 1, axis=1),
        cache["units"])
    rest = jax.tree.map(
        lambda l: jax.lax.dynamic_slice_in_dim(l, b, 1, axis=0),
        cache["rest"])
    return {"units": units, "rest": rest, "enc_out": cache.get("enc_out", ())}


def _write_slot(cache, sub, b: int):
    """Write a B=1 view into slot b; the MoE counts and tap of a
    program's call (``sub`` from ``prefill_cached``) replace the
    cache's."""
    units = jax.tree.map(
        lambda l, s: jax.lax.dynamic_update_slice_in_dim(l, s, b, axis=1),
        cache["units"], sub["units"])
    rest = jax.tree.map(
        lambda l, s: jax.lax.dynamic_update_slice_in_dim(l, s, b, axis=0),
        cache["rest"], sub["rest"])
    out = {"units": units, "rest": rest, "enc_out": cache.get("enc_out", ())}
    for k in ("moe_counts", "moe_tap"):
        if k in cache:
            out[k] = sub.get(k, cache[k])
    return out


def _zero_slot(cache, b: int):
    sub = _slice_slot(cache, b)
    zeroed = jax.tree.map(jnp.zeros_like, sub)
    return _write_slot(cache, zeroed, b)


def _nbytes(tree) -> int:
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


_NULL_CTX = contextlib.nullcontext()
#: the copier's clock for ``engine.fragment_wait_ns``, apart from the
#: step clock (``time.perf_counter``) that sets the cluster's virtual time
_clock_ns = time.perf_counter_ns


@jax.jit
def slot_fragment(cache, b):
    """Slot ``b`` of ``cache``'s units and rest as a B=1 view, in buffers
    of its own: the steps that later overwrite the slot leave it as it
    was.  ``b`` is traced, so one compile serves every slot."""
    return _slice_slot(cache, b)


def _to_host(leaves: list, treedef):
    """The host copy of ``leaves`` (copies already started), as a tree;
    each device buffer is dropped as soon as its host copy has landed."""
    host = []
    while leaves:
        host.append(np.asarray(leaves.pop(0)))
    return jax.tree.unflatten(treedef, host)


class FragmentCopier:
    """Copies prefix fragments from one device to the host in the
    background, for every engine on that device.

    ``start`` slices a slot out of a cache, starts the copy of the slice
    and returns at once with a ``Future`` of the host tree; a worker
    thread collects it.  At most one fragment's device buffers are in
    flight: ``start`` first waits for the one before.  That bounds the
    extra device memory at one cache slot, on a chip that the engines'
    caches and a whole-prompt prefill's temporaries nearly fill.
    ``drain`` waits for the copy in flight and stops the thread."""

    def __init__(self):
        self._pool: Optional[concurrent.futures.ThreadPoolExecutor] = None
        self._inflight: Optional[concurrent.futures.Future] = None

    def start(self, cache, b: int, obs=None) -> concurrent.futures.Future:
        """Start copying slot ``b`` of ``cache`` to the host."""
        if self._inflight is not None:
            self.result(self._inflight, obs)
        leaves, treedef = jax.tree.flatten(slot_fragment(
            {"units": cache["units"], "rest": cache["rest"]}, b))
        for leaf in leaves:
            leaf.copy_to_host_async()
        if self._pool is None:
            self._pool = concurrent.futures.ThreadPoolExecutor(
                1, thread_name_prefix="fragment-copy")
        self._inflight = self._pool.submit(_to_host, leaves, treedef)
        return self._inflight

    def result(self, fut: concurrent.futures.Future, obs=None):
        """The host tree of ``fut``; a wait on a copy still in flight is
        spanned (``engine.fragment_wait``) and counted
        (``engine.fragment_waits``, ``engine.fragment_wait_ns``)."""
        if obs is None or fut.done():
            return fut.result()
        tr, reg = obs.tracer, obs.registry
        t0 = _clock_ns()
        with (tr.span("engine.fragment_wait") if tr is not None
              else _NULL_CTX):
            host = fut.result()
        if reg is not None:
            reg.inc("engine.fragment_waits")
            reg.inc("engine.fragment_wait_ns", _clock_ns() - t0)
        return host

    def drain(self):
        try:
            if self._inflight is not None:
                self._inflight.result()       # a failed copy raises here
        finally:
            if self._pool is not None:
                self._pool.shutdown(wait=True)
                self._pool = None
            self._inflight = None


class PrefixStore:
    """Host-side LRU store of per-slot cache fragments keyed by block-id
    chains.  ``exact_only`` archs (recurrent) store state snapshots; the
    mechanism is identical — inject fragment, resume at its length.

    A fragment may be stored before its value exists (the engine stores
    the ``Future`` of its copy to the host): the store only keeps it,
    and evicting it just drops it.

    ``partial``: a fragment also serves a prompt that shares only the
    first blocks of its chain.  That is exact for full-attention KV
    (position p's K/V depend on tokens <= p, and the engine masks the
    cache past the reused length); a recurrent snapshot or a wrapped
    sliding-window ring holds the whole chain and needs all of it."""

    def __init__(self, capacity: int = 64, partial: bool = False):
        self.capacity = capacity
        self.partial = partial
        self._store: "collections.OrderedDict[Tuple, Tuple]" = \
            collections.OrderedDict()

    def lookup(self, blocks: Tuple[int, ...]):
        """Longest reusable stored prefix of ``blocks``: (key, fragment,
        reusable tokens), or (None, None, 0)."""
        best, best_n = None, 0
        for key in self._store:
            m = min(len(key), len(blocks))
            n = 0
            while n < m and key[n] == blocks[n]:
                n += 1
            if n < len(key) and not self.partial:
                continue
            if n > 0 and n >= best_n:
                best, best_n = key, n
        if best is None:
            return None, None, 0
        self._store.move_to_end(best)
        frag, length = self._store[best]
        return best, frag, length * best_n // len(best)

    def insert(self, blocks: Tuple[int, ...], frag, length: int):
        if not blocks:
            return
        self._store[tuple(blocks)] = (frag, length)
        self._store.move_to_end(tuple(blocks))
        while len(self._store) > self.capacity:
            self._store.popitem(last=False)


# ---------------------------------------------------------------------------

class _Seq:
    __slots__ = ("req", "tokens", "slot", "pos", "generated", "out_tokens",
                 "prefill_done", "reused")

    def __init__(self, req: Request, tokens: np.ndarray, slot: int):
        self.req = req
        self.tokens = tokens
        self.slot = slot
        self.pos = 0                 # tokens already in cache
        self.reused = 0              # of them, injected from the store
        self.generated = 0
        self.out_tokens: List[int] = []
        self.prefill_done = False


# The engine's two device programs.  ``model`` is static, so every
# engine serving one model shares one compile per shape and device.
@functools.partial(jax.jit, static_argnums=0)
def prefill_slot(model: Model, params, cache, tokens, positions, cache_len,
                 b):
    """Prefill one chunk into slot ``b``; returns (last-token logits,
    cache)."""
    sub = _slice_slot(cache, b)
    if "moe_tap" in cache:                  # its shape sets the rows tapped
        sub["moe_tap"] = cache["moe_tap"]
    logits, new_sub = model.prefill_cached(
        params, tokens, positions, sub, cache_len[None])
    return logits[:, -1], _write_slot(cache, new_sub, b)


@functools.partial(jax.jit, static_argnums=0)
def decode_step(model: Model, params, cache, tokens, pos):
    """One greedy decode step for every slot; returns (next tokens,
    cache)."""
    logits, cache = model.decode_step(params, tokens, pos, cache)
    return jnp.argmax(logits[:, -1], axis=-1), cache


def _count_decode(reg, counts: np.ndarray):
    """A decode step's MoE counters from its (MoE layers, held) counts."""
    reg.inc("engine.moe_routed_local", int(counts.sum()))
    reg.inc("engine.moe_layer_steps", counts.shape[0])
    reg.inc("engine.moe_experts_touched", int((counts > 0).sum()))
    reg.inc("engine.moe_max_expert_tokens", int(counts.max(axis=1).sum()))


class InstanceEngine:
    def __init__(self, model: Model, params, iid: int = 0,
                 max_batch: int = 8, max_len: int = 512,
                 chunk_tokens: int = 128, block_size: int = 16,
                 prefix_capacity: int = 64, device=None, obs=None,
                 copier: Optional[FragmentCopier] = None):
        """``device``: where this engine's params and KV cache live
        (``None``: JAX's default device, params left where they are);
        ``obs``: an observability bundle, or ``None`` (off);
        ``copier``: the ``FragmentCopier`` of the engines on ``device``
        (``None``: one of its own)."""
        assert not model.cfg.is_encdec, "enc-dec not served by this engine"
        self.model = model
        self.device = device
        self.params = params if device is None else \
            jax.device_put(params, device)
        self.iid = iid
        self.max_batch = max_batch
        self.max_len = max_len
        self.chunk = chunk_tokens
        self.block_size = block_size
        self.cache = model.init_cache(max_batch, max_len)
        if device is not None:
            self.cache = jax.device_put(self.cache, device)
        #: bytes of one slot of the cache: a stored fragment's size
        self.slot_bytes = _nbytes((self.cache["units"],
                                   self.cache["rest"])) // max_batch
        self.obs = obs
        self.copier = copier if copier is not None else FragmentCopier()
        self.prefix_store = PrefixStore(
            prefix_capacity,
            partial=all(k == "attn" for k in model.cfg.block_pattern))
        self.waiting: collections.deque = collections.deque()
        self.running: Dict[int, _Seq] = {}      # slot -> seq
        self.free_slots = list(range(max_batch))
        self._last_tokens = np.zeros(max_batch, np.int64)
        self._pos = np.zeros(max_batch, np.int64)

    # ------------------------------------------------------------------
    def submit(self, req: Request, tokens: np.ndarray):
        self.waiting.append(_Seq(req, tokens, -1))

    def has_work(self) -> bool:
        return bool(self.waiting) or bool(self.running)

    # ------------------------------------------------------------------
    def _head(self):
        """The queue's head if this step can prefill it (it holds a slot,
        or one is free), else ``None``."""
        if not self.waiting or not self.free_slots:
            return None
        return self.waiting[0]

    def _admit(self, seq: _Seq, reg):
        """Give ``seq`` a zeroed slot; on a prefix-store hit inject the
        fragment and skip its compute."""
        slot = self.free_slots.pop(0)
        seq.slot = slot
        self.cache = _zero_slot(self.cache, slot)
        if reg is not None:
            # the router's prediction, before a store hit overwrites it
            reg.inc("engine.predicted_hit_tokens", seq.req.hit_tokens)
        blocks = tuple(tokens_to_blocks(seq.tokens.tolist(),
                                        self.block_size))
        key, pending, length = self.prefix_store.lookup(blocks)
        if pending is not None:
            # always leave >=1 token to prefill (logits source); a full-
            # prompt hit re-processes just the final token
            usable = min(length, len(seq.tokens) - 1)
            if usable > 0:
                frag = self.copier.result(pending, self.obs)
                self.cache = _write_slot(self.cache, frag, slot)
                seq.pos = seq.reused = usable
                seq.req.hit_tokens = usable
                if reg is not None:
                    reg.inc("engine.fragment_h2d_bytes", _nbytes(frag))
        if reg is not None:
            reg.inc("engine.reused_tokens", seq.reused)

    def step(self) -> Dict:
        """One engine step: a prefill chunk (head-of-queue) OR a batched
        decode step for all running slots.  Returns events + wall time."""
        t0 = time.perf_counter()
        seq = self._head()
        obs = self.obs
        if obs is None or obs.tracer is None:
            events = self._step(seq, obs)
        else:
            kind = ("prefill" if seq is not None
                    else "decode" if self.running else "idle")
            with obs.tracer.span("engine.step",
                                 args={"iid": self.iid, "kind": kind}):
                events = self._step(seq, obs)
        events["wall"] = time.perf_counter() - t0
        return events

    def _step(self, seq: Optional[_Seq], obs) -> Dict:
        tr = obs.tracer if obs is not None else None
        reg = obs.registry if obs is not None else None
        events = {"first": [], "finished": [], "kind": "idle",
                  "prefill_tokens": 0, "decode_bs": 0}
        if seq is not None:
            events["kind"] = "prefill"
            if seq.slot < 0:
                with (tr.span("engine.admit", args={"rid": seq.req.rid})
                      if tr is not None else _NULL_CTX):
                    self._admit(seq, reg)
            n = min(self.chunk, len(seq.tokens) - seq.pos,
                    self.max_len - seq.pos)
            with (tr.span("engine.prefill",
                          args={"rid": seq.req.rid, "tokens": n})
                  if tr is not None else _NULL_CTX):
                toks = jnp.asarray(
                    seq.tokens[seq.pos: seq.pos + n][None], jnp.int32)
                positions = jnp.arange(seq.pos, seq.pos + n,
                                       dtype=jnp.int32)[None]
                cache_len = jnp.asarray(seq.pos, jnp.int32)
                logits, self.cache = prefill_slot(
                    self.model, self.params, self.cache, toks, positions,
                    cache_len, seq.slot)
                logits.block_until_ready()
                if reg is not None and "moe_counts" in self.cache:
                    reg.inc("engine.moe_routed_local",
                            int(np.asarray(self.cache["moe_counts"]).sum()))
            events["prefill_tokens"] = n
            seq.pos += n
            if seq.pos >= min(len(seq.tokens), self.max_len):
                # prefill complete -> first token
                seq.prefill_done = True
                first = int(np.asarray(logits)[0].argmax())
                seq.out_tokens.append(first)
                seq.generated = 1
                self.waiting.popleft()
                self.running[seq.slot] = seq
                self._last_tokens[seq.slot] = first
                self._pos[seq.slot] = seq.pos
                events["first"].append(seq)
                # save the prompt's KV as a reusable prefix fragment
                blocks = tuple(tokens_to_blocks(
                    seq.tokens.tolist(), self.block_size))
                if blocks:
                    with (tr.span("engine.fragment_save",
                                  args={"rid": seq.req.rid,
                                        "bytes": self.slot_bytes})
                          if tr is not None else _NULL_CTX):
                        pending = self.copier.start(self.cache, seq.slot,
                                                    obs)
                        self.prefix_store.insert(
                            blocks, pending, len(blocks) * self.block_size)
                    if reg is not None:
                        reg.inc("engine.fragment_d2h_bytes",
                                self.slot_bytes)
                if seq.generated >= seq.req.output_len:
                    self._finish(seq, events)
        elif self.running:
            events["kind"] = "decode"
            events["decode_bs"] = len(self.running)
            with (tr.span("engine.decode", args={"bs": len(self.running)})
                  if tr is not None else _NULL_CTX):
                toks = jnp.asarray(self._last_tokens[:, None], jnp.int32)
                pos = jnp.asarray(self._pos, jnp.int32)
                nxt, self.cache = decode_step(self.model, self.params,
                                              self.cache, toks, pos)
                nxt = np.asarray(nxt)
                if reg is not None and "moe_counts" in self.cache:
                    _count_decode(reg, np.asarray(self.cache["moe_counts"]))
            for slot, seq in list(self.running.items()):
                tok = int(nxt[slot])
                seq.out_tokens.append(tok)
                seq.generated += 1
                self._last_tokens[slot] = tok
                self._pos[slot] = min(self._pos[slot] + 1, self.max_len - 1)
                if seq.generated >= seq.req.output_len or \
                        self._pos[slot] >= self.max_len - 1:
                    self._finish(seq, events)
        return events

    def _finish(self, seq: _Seq, events):
        events["finished"].append(seq)
        if seq.slot in self.running:
            del self.running[seq.slot]
        self.free_slots.append(seq.slot)

    def warmup(self):
        """Trigger jit compiles so measured step times are steady-state."""
        toks = jnp.zeros((1, min(self.chunk, 8)), jnp.int32)
        pos = jnp.arange(toks.shape[1], dtype=jnp.int32)[None]
        _, c = prefill_slot(self.model, self.params, self.cache, toks, pos,
                            jnp.asarray(0, jnp.int32), 0)
        t = jnp.zeros((self.max_batch, 1), jnp.int32)
        p = jnp.zeros((self.max_batch,), jnp.int32)
        decode_step(self.model, self.params, c, t, p)[0].block_until_ready()
        jax.block_until_ready(slot_fragment(
            {"units": self.cache["units"], "rest": self.cache["rest"]}, 0))


# ---------------------------------------------------------------------------

class EngineCluster:
    """N real engines + the paper's router under virtual time."""

    def __init__(self, n_instances: int, model: Model, params, policy,
                 block_size: int = 16, kv_capacity_tokens: int = 1 << 62,
                 devices: Optional[Sequence] = None, obs=None,
                 **engine_kw):
        """``devices``: engine ``i`` runs on ``devices[i % len(devices)]``
        (``None``: every engine on JAX's default device).  The params are
        copied once to each distinct device.  ``obs`` (``repro.obs``)
        goes to the router and every engine; ``None`` is off."""
        devs = [devices[i % len(devices)] if devices else None
                for i in range(n_instances)]
        placed = {d: params if d is None else jax.device_put(params, d)
                  for d in set(devs)}
        self.copiers = {d: FragmentCopier() for d in placed}
        self.engines = [InstanceEngine(model, placed[d], iid=i, device=d,
                                       block_size=block_size, obs=obs,
                                       copier=self.copiers[d], **engine_kw)
                        for i, d in enumerate(devs)]
        exact_only = not model.cfg.has_kv_blocks
        self.router = Router(policy, n_instances,
                             kv_capacity_tokens=kv_capacity_tokens,
                             block_size=block_size, exact_only=exact_only,
                             obs=obs)
        self.block_size = block_size
        self.served: Dict[int, _Seq] = {}   # rid -> finished sequence

    def run(self, arrivals: List[Tuple[float, np.ndarray, int]],
            verbose: bool = False, feedback=None) -> List[Request]:
        """arrivals: (time, prompt_tokens, max_new_tokens[, session_id]).

        ``feedback(req, now)`` (optional) closes the loop: called on
        every finish with the completed request and its virtual finish
        time, it returns follow-up arrival tuples (same shape) that are
        pushed into the live event heap — the real-engine analogue of
        ``repro.cluster.closed_loop``.

        However it ends, the copies of prefix fragments still in flight
        are collected before it returns: no device buffer or copier
        thread outlives the call.
        """
        try:
            return self._run(arrivals, verbose, feedback)
        finally:
            for c in self.copiers.values():
                c.drain()

    def _run(self, arrivals, verbose, feedback) -> List[Request]:
        for e in self.engines:
            e.warmup()
        finished: List[Request] = []
        self.served = {}
        heap: List = []
        seqno = itertools.count()
        rids = itertools.count()

        def push(t, toks, out, sid=-1):
            toks = np.asarray(toks)
            blocks = tuple(tokens_to_blocks(list(toks), self.block_size))
            req = Request(rid=next(rids), arrival=t, blocks=blocks,
                          prompt_len=len(toks), output_len=out,
                          session_id=sid)
            heapq.heappush(heap, (t, next(seqno), "arrival", (req, toks)))

        for entry in arrivals:
            push(*entry)
        engine_time = [0.0] * len(self.engines)
        while heap:
            t, _, kind, payload = heapq.heappop(heap)
            if kind == "arrival":
                req, toks = payload
                iid = self.router.route(req, t)
                eng = self.engines[iid]
                was_idle = not eng.has_work()
                eng.submit(req, np.asarray(toks))
                if was_idle:
                    # an idle engine has no pending step event; resume it
                    # at max(arrival, its virtual clock) — feedback
                    # arrivals can land behind an engine that ran ahead
                    engine_time[iid] = max(engine_time[iid], t)
                    heapq.heappush(heap, (engine_time[iid], next(seqno),
                                          "step", iid))
            else:
                iid = payload
                eng = self.engines[iid]
                if not eng.has_work():
                    continue
                ev = eng.step()
                now = engine_time[iid] + ev["wall"]
                engine_time[iid] = now
                if ev["prefill_tokens"]:
                    self.router.on_prefill_progress(iid,
                                                    ev["prefill_tokens"])
                for seq in ev["first"]:
                    seq.req.t_first_token = now
                    self.router.on_start_running(iid, seq.req)
                if ev["kind"] == "decode":
                    for _ in range(ev["decode_bs"]):
                        self.router.on_decode_token(iid)
                for seq in ev["finished"]:
                    seq.req.t_finish = now
                    self.router.on_finish(iid, seq.req)
                    finished.append(seq.req)
                    self.served[seq.req.rid] = seq
                    if feedback is not None:
                        for entry in feedback(seq.req, now):
                            push(*entry)
                    if verbose:
                        print(f"[{now:8.3f}] inst{iid} rid={seq.req.rid} "
                              f"hit={seq.req.hit_tokens} "
                              f"out={len(seq.out_tokens)}")
                if eng.has_work():
                    heapq.heappush(heap, (now, next(seqno), "step", iid))
        return finished
