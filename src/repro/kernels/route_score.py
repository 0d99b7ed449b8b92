"""Fused batch-routing score loop (jax).

Scores a whole arrival wave of ``k`` requests against ``n`` instances in
one device computation.  The core is a *sequential argmin with feedback*:
request ``j``'s score depends on the indicator updates (``q_bs``,
``queued_prefill_tokens``, ``total_tokens``) and the KV$ blocks inserted
by requests ``0..j-1`` of the same wave, so the loop must run in order —
but it runs entirely on device over the factory's mirrored indicator
arrays, amortising dispatch and all per-decision numpy overhead across
the wave.

Bit-identical contract
----------------------
For every supported policy kind the wave loop reproduces the exact
floating-point operation order of the numpy scoring path in
``repro.core.policies`` (itself bit-compatible with the frozen scalar
reference).  Two ingredients make that possible:

* **x64**: every entry point runs under :func:`x64` (the public wrappers
  here do it for you) so indicators are int64 and scores float64
  exactly like numpy.  On a TPU, XLA emulates both types;
  ``chip_smoke.py`` checks there that wave decisions still equal the
  host's sequential ``route`` decisions, and the differential tests pin
  the same contract on the CPU.
* **intra-wave KV$ credit**: the host passes the pre-wave aggregated-
  index hit depths ``depth[k, n]`` plus the pairwise longest-common-
  prefix matrix ``lcp[k, k]`` of the wave's block chains.  After request
  ``j'`` is assigned to instance ``i`` (and will insert its chain
  there), any later request ``j`` sees
  ``depth[j, i] = max(depth[j, i], lcp[j, j'])`` — exactly what the
  per-instance radix walk would return, *provided no eviction fires
  mid-wave* (the router guards that with the factory's eviction counter
  and falls back to sequential host routing).

The host half behind those inputs (``IndicatorFactory.wave_inputs``) is
the flat bitset aggregated index: one LCP-chained walk per unique
prompt (sorted chains resume from their predecessor's shared-prefix
frontier) and the pairwise LCP matrix reconstructed from the same sort
by running minima.  Both are integer-exact, so the wave loop's inputs —
and therefore its decisions — are bit-identical to what per-request
walks would produce; the device-mirror / dirty-flag contract in
``repro.core.indicators`` is untouched by how the host computes them.
That independence extends to sharding: a sharded factory
(``n_shards > 1``) concatenates per-shard hit vectors into the same
full-width ``depth[k, n]`` matrix and slices the mirror per shard, so
the loop is oblivious to the host index's partitioning — the
``depth``/``lcp``/``plen`` input schema here is the only coupling.

Policy kinds
------------
``jsq``      4*Q-BS + R-BS                                 (vLLM Fig. 6a)
``linear``   λ(1 − hit/L) + (1−λ)(BS/max BS)               (Fig. 6b)
``filter``   BS-range filter then max-hit candidates       (Fig. 13)
``lmetric``  (P-token + 1) × (BS + 1) and §5.1 ablations   (Fig. 17b)
``ptoken``   raw P-token, first-min selection (PD-disagg prefill pool)

Every kind runs the same step body as one jitted ``lax.fori_loop``, on
every platform.  ``route_wave`` pads the wave to a power-of-two length;
``route_wave_ref`` runs the unpadded wave — the padding's differential
reference.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_EPS = 1e-9  # keep in sync with repro.core.policies._EPS


def x64():
    """The device dtype scope: int64 indicators, float64 scores.  Every
    upload of routing state and every wave dispatch runs inside it."""
    return jax.enable_x64(True)


# ---------------------------------------------------------------------------
# selection: vectorized twin of Policy._select_min
# ---------------------------------------------------------------------------
def _pick(scores, allowed, tie, *, eps=_EPS):
    """argmin with epsilon-tie round-robin over an allowed mask.

    Mirrors ``Policy._select_min``: minimum over allowed indices, ties
    within ``eps``, round-robin among ties (ascending index order, as
    ``np.flatnonzero`` yields) via the ``tie`` counter value.
    ``allowed=None`` means every instance is allowed.
    """
    if allowed is None:
        best = jnp.min(scores)
        ties = scores <= best + eps
    else:
        best = jnp.min(jnp.where(allowed, scores, jnp.inf))
        ties = allowed & (scores <= best + eps)
    csum = jnp.cumsum(ties.astype(jnp.int64))
    r = jnp.mod(tie, csum[-1])
    return jnp.argmax(ties & (csum == r + 1))


# ---------------------------------------------------------------------------
# one wave step: score -> select -> feedback
# ---------------------------------------------------------------------------
def _wave_step(kind, params, block_size, iota_n, rbs, depth, lcp, plen,
               tie, j, state):
    """Route request ``j`` of the wave and apply its indicator feedback.

    ``state = (qbs, qpt, tt, cred, sel, hit_out)``.  ``cred[k, n]`` is
    the intra-wave KV$ depth-credit matrix: after request ``j'`` routes
    to instance ``i`` (and will insert its chain there), column ``i``
    takes ``max(cred[:, i], lcp[:, j'])`` — a dynamic-slice column
    read-modify-write, O(k) work that XLA updates in place inside the
    loop carry (a full-matrix masked select is O(k·n) per step and an
    XLA scatter-max pays ~0.5ms of fixed CPU cost).  Feedback updates a
    kind doesn't score with are skipped statically.  All arithmetic
    replicates the numpy scoring expressions' operation order (see
    module docstring).
    """
    qbs, qpt, tt, cred, sel, hit_out = state
    needs_hits = kind != "jsq"
    needs_qpt = (kind == "ptoken"
                 or (kind == "lmetric" and params[0] == "ptoken"))
    needs_tt = kind == "lmetric" and params[1] == "tokens"

    plen_j = lax.dynamic_index_in_dim(plen, j, keepdims=False)
    if needs_hits:
        base = lax.dynamic_index_in_dim(depth, j, keepdims=False)  # (n,)
        credit = lax.dynamic_index_in_dim(cred, j, keepdims=False)
        d = jnp.maximum(base, credit)
        hits = jnp.minimum(d * block_size, plen_j)                # tokens
    else:
        hits = jnp.int64(0)
    bs = rbs + qbs
    allowed = None
    eps = _EPS

    if kind == "jsq":
        scores = 4.0 * qbs + rbs
    elif kind == "linear":
        (lam,) = params
        max_bs = jnp.maximum(jnp.max(bs), 1)
        L = jnp.maximum(plen_j, 1)
        scores = lam * (1.0 - hits / L) + (1.0 - lam) * (bs / max_bs)
    elif kind == "filter":
        (bs_range,) = params
        imbalanced = (jnp.max(bs) - jnp.min(bs)) > bs_range
        allowed = imbalanced | (hits >= jnp.max(hits))
        scores = bs.astype(jnp.float64)
    elif kind == "lmetric":
        kv_indicator, load_indicator = params
        if kv_indicator == "ptoken":
            a = (qpt + (plen_j - hits)) + 1.0
        else:                                     # "one_minus_hit"
            L = jnp.maximum(plen_j, 1)
            a = 1.0 - hits / L + 1e-3
        if load_indicator == "bs":
            b = bs + 1.0
        else:                                     # "tokens"
            b = tt + 1.0
        scores = a * b
    elif kind == "ptoken":
        # PD-disagg prefill pool (§7): raw P-token, np.argmin semantics
        # (first exact minimum — eps 0, round-robin counter pinned to 0)
        scores = (qpt + (plen_j - hits)).astype(jnp.float64)
        eps = 0.0
    else:  # pragma: no cover - guarded by the public wrappers
        raise ValueError(kind)

    tie_j = (jnp.int64(0) if kind == "ptoken"
             else lax.dynamic_index_in_dim(tie, j, keepdims=False))
    sel_j = _pick(scores, allowed, tie_j, eps=eps)
    hit_j = hits[sel_j] if needs_hits else jnp.int64(0)

    onehot = iota_n == sel_j
    qbs = qbs + onehot
    if needs_qpt:
        qpt = qpt + onehot * (plen_j - hit_j)
    if needs_tt:
        tt = tt + onehot * plen_j
    if needs_hits:
        lcp_col = lax.dynamic_index_in_dim(lcp, j, axis=1,
                                           keepdims=True)        # (k, 1)
        col = lax.dynamic_slice(cred, (0, sel_j), (cred.shape[0], 1))
        cred = lax.dynamic_update_slice(
            cred, jnp.maximum(col, lcp_col), (0, sel_j))
        hit_out = lax.dynamic_update_index_in_dim(hit_out, hit_j, j, 0)
    sel = lax.dynamic_update_index_in_dim(sel, sel_j, j, 0)
    return qbs, qpt, tt, cred, sel, hit_out


def _run_wave(kind, params, block_size, rbs, qbs, qpt, tt, depth, aux):
    """``aux`` packs (lcp (k,k) | plen (k,) | tie (k,)) column-wise —
    one host→device transfer for all per-request wave data."""
    k, n = depth.shape
    lcp, plen, tie = aux[:, :k], aux[:, k], aux[:, k + 1]
    iota_n = jnp.arange(n, dtype=jnp.int64)
    state = (qbs, qpt, tt,
             jnp.zeros((k, n), depth.dtype),
             jnp.full((k,), -1, jnp.int64),      # -1 = not yet routed
             jnp.zeros((k,), plen.dtype))
    body = functools.partial(_wave_step, kind, params, block_size,
                             iota_n, rbs, depth, lcp, plen, tie)
    _, _, _, _, sel, hit_out = lax.fori_loop(0, k, body, state)
    return sel, hit_out


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------
@functools.partial(jax.jit,
                   static_argnames=("kind", "params", "block_size"))
def _route_wave(kind, params, block_size, rbs, qbs, qpt, tt, depth, aux):
    return _run_wave(kind, params, block_size, rbs, qbs, qpt, tt, depth,
                     aux)


def _pack_aux(lcp, plen, tie0, kp):
    """Host-side padded pack: (lcp | plen | tie) as one (kp, kp+2)
    int64 buffer.  Padding rows route *after* every real request, so
    they cannot perturb real decisions."""
    k = len(plen)
    aux = np.zeros((kp, kp + 2), dtype=np.int64)
    aux[:k, :k] = lcp
    aux[:k, kp] = plen
    aux[:, kp + 1] = tie0 + np.arange(kp)
    return aux


def route_wave_submit(kind: str, params: tuple, block_size: int,
                      rbs, qbs, qpt, tt, depth, lcp, plen, tie0: int):
    """Dispatch a wave to the device and return a handle — the **score
    stage boundary**.  jax dispatch is asynchronous: the jitted wave
    loop is enqueued and the call returns immediately with device
    futures, so the caller can do host work (e.g. submit the next
    wave's speculative index walks) before blocking in
    :func:`route_wave_collect`.

    ``rbs``/``qbs``/``qpt``/``tt`` may be numpy arrays or the factory's
    device mirror (jnp).  ``depth`` is the pre-wave aggregated-index
    block-depth matrix ``(k, n)``, ``lcp`` the pairwise intra-wave LCP
    matrix ``(k, k)``, ``plen`` the prompt lengths ``(k,)`` and ``tie0``
    the policy's tie counter value for the wave's first request.

    The wave is padded host-side to a power-of-two length so jit
    recompiles stay bounded and the per-request inputs ship as two
    contiguous transfers (depth + packed aux).
    """
    k = len(plen)
    kp = 1
    while kp < k:
        kp *= 2
    if kp != k:
        depth = np.pad(np.asarray(depth), ((0, kp - k), (0, 0)))
    aux = _pack_aux(lcp, plen, tie0, kp)
    with x64():
        sel, hit = _route_wave(kind, params, block_size, jnp.asarray(rbs),
                               jnp.asarray(qbs), jnp.asarray(qpt),
                               jnp.asarray(tt), jnp.asarray(depth),
                               jnp.asarray(aux))
    return sel, hit, k


def route_wave_collect(handle) -> Tuple[np.ndarray, np.ndarray]:
    """Block on a :func:`route_wave_submit` handle; returns the wave's
    (assignments, hit tokens) as host numpy arrays (padding stripped)."""
    sel, hit, k = handle
    return np.asarray(sel)[:k], np.asarray(hit)[:k]


def route_wave(kind: str, params: tuple, block_size: int,
               rbs, qbs, qpt, tt, depth, lcp, plen,
               tie0: int) -> Tuple[np.ndarray, np.ndarray]:
    """Route a whole wave on device; returns (assignments, hit tokens).
    Submit + collect in one breath — see :func:`route_wave_submit`."""
    return route_wave_collect(route_wave_submit(
        kind, params, block_size, rbs, qbs, qpt, tt, depth, lcp, plen,
        tie0))


def route_wave_ref(kind, params, block_size, rbs, qbs, qpt, tt, depth,
                   lcp, plen, tie0):
    """The wave loop on the unpadded wave — the differential reference
    for :func:`route_wave`'s power-of-two padding."""
    aux = _pack_aux(lcp, plen, tie0, len(plen))
    with x64():
        sel, hit = _route_wave(
            kind, params, block_size, jnp.asarray(rbs), jnp.asarray(qbs),
            jnp.asarray(qpt), jnp.asarray(tt), jnp.asarray(depth),
            jnp.asarray(aux))
    return np.asarray(sel), np.asarray(hit)
