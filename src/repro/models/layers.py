"""Primitive layers: norms, RoPE, FFNs, MoE, initialisers.

Parameters are plain nested dicts of jnp arrays (pytrees) so they stay
trivially shardable with NamedSharding and stackable for scan-over-units.
Every ``init_*`` works under ``jax.eval_shape`` (abstract init — the
dry-run never allocates 480B parameters).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from .config import ModelConfig
from ..kernels.moe_experts import held_experts_op


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def _dense_init(key, shape, dtype, scale=None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_linear(key, d_in, d_out, dtype, scale=None):
    return {"w": _dense_init(key, (d_in, d_out), dtype, scale)}


def linear(p, x):
    return x @ p["w"]


def init_norm(d, dtype, norm_type="rmsnorm"):
    p = {"scale": jnp.ones((d,), dtype)}
    if norm_type == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def norm_apply(p, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    if "bias" in p:  # layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                       # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# dense FFN
# ---------------------------------------------------------------------------

def init_ffn(key, d_model, d_ff, dtype, act="swiglu"):
    ks = jax.random.split(key, 3)
    if act in ("swiglu", "geglu"):
        return {"wi": init_linear(ks[0], d_model, d_ff, dtype),
                "wg": init_linear(ks[1], d_model, d_ff, dtype),
                "wo": init_linear(ks[2], d_ff, d_model, dtype)}
    return {"wi": init_linear(ks[0], d_model, d_ff, dtype),
            "wo": init_linear(ks[2], d_ff, d_model, dtype)}


def ffn_apply(p, x, act="swiglu"):
    h = linear(p["wi"], x)
    if act == "swiglu":
        h = jax.nn.silu(linear(p["wg"], x)) * h
    elif act == "geglu":
        h = jax.nn.gelu(linear(p["wg"], x)) * h
    else:
        h = jax.nn.gelu(h)
    return linear(p["wo"], h)


# ---------------------------------------------------------------------------
# MoE.  Two layers share one parameter layout: the router over all
# ``n_experts`` and the weights of the experts held here
# (``cfg.expert_first`` on, ``cfg.resolved_experts_held`` of them).
#
# * ``moe_apply`` (training): top-k routing with per-group capacity
#   dispatch (sort-free one-hot cumsum — GShard-style but with the
#   (tokens, E) cumsum done per group so the dispatch bookkeeping stays
#   tiny; expert compute is an einsum over (E, capacity, ·) buffers that
#   shards cleanly: experts over the "model" axis when divisible, else the
#   FFN dim).  Holds every expert; drops choices past capacity.
# * ``moe_dropless`` (serving): every choice that lands on a held expert
#   goes through it, in one kernel over the held experts some token
#   picked (``kernels/moe_experts.py``).
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig, dtype):
    D, F = cfg.d_model, cfg.resolved_moe_d_ff
    E = cfg.resolved_experts_held
    ks = jax.random.split(key, 5)
    p = {
        "router": init_linear(ks[0], D, cfg.n_experts, jnp.float32),
        "wi": {"w": _dense_init(ks[1], (E, D, F), dtype)},
        "wg": {"w": _dense_init(ks[2], (E, D, F), dtype)},
        "wo": {"w": _dense_init(ks[3], (E, F, D), dtype)},
    }
    if cfg.dense_residual_d_ff:
        p["dense"] = init_ffn(ks[4], D, cfg.dense_residual_d_ff, dtype,
                              cfg.ffn_act)
    return p


def moe_apply(p, x, cfg: ModelConfig):
    """x: (B, S, D) -> (B, S, D), aux_loss scalar.

    Routing is computed per row (group = one batch element) so all sorting
    bookkeeping is local; expert matmuls run on (E, B*C, ·) buffers.
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    assert cfg.resolved_experts_held == E, "capacity dispatch holds all"
    cap = int(math.ceil(S * K / E * cfg.capacity_factor))
    cap = max(cap, K)

    logits = (x.astype(jnp.float32) @ p["router"]["w"])        # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, K)                       # (B,S,K)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch-style)
    me = jnp.mean(probs, axis=(0, 1))                          # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(topi, E, dtype=jnp.float32), axis=2),
        axis=(0, 1))
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)

    # position of each (token, k) routing choice inside its expert buffer
    sel = jax.nn.one_hot(topi, E, dtype=jnp.int32)             # (B,S,K,E)
    sel_flat = sel.reshape(B, S * K, E)
    pos = jnp.cumsum(sel_flat, axis=1) - 1                     # (B,S*K,E)
    pos = jnp.sum(pos * sel_flat, axis=-1)                     # (B,S*K)
    keep = pos < cap                                           # capacity drop
    eid = topi.reshape(B, S * K)
    w = topw.reshape(B, S * K) * keep

    # scatter tokens into (B, E*cap, D)
    slot = jnp.where(keep, eid * cap + pos, E * cap)           # drop slot
    xk = jnp.repeat(x, K, axis=1)                              # (B,S*K,D)
    buf = jnp.zeros((B, E * cap + 1, D), x.dtype)
    bidx = jnp.arange(B)[:, None]
    buf = buf.at[bidx, slot].add(xk)
    buf = buf[:, :-1].reshape(B, E, cap, D)
    # NOTE §Perf it#7 (refuted): forcing the dispatch buffer to
    # E-over-model here makes SPMD materialise a replicated copy on both
    # sides of the reshard (arctic peak 90->231 GiB/dev).  Letting the
    # expert einsum's operand sharding drive propagation is strictly
    # better; the buffer stays batch-sharded.

    # expert FFN on the buffers
    h = jnp.einsum("becd,edf->becf", buf, p["wi"]["w"])
    if cfg.ffn_act in ("swiglu", "geglu"):
        g = jnp.einsum("becd,edf->becf", buf, p["wg"]["w"])
        act = jax.nn.silu if cfg.ffn_act == "swiglu" else jax.nn.gelu
        h = act(g) * h
    else:
        h = jax.nn.gelu(h)
    out_buf = jnp.einsum("becf,efd->becd", h, p["wo"]["w"])
    out_buf = out_buf.reshape(B, E * cap, D)
    out_buf = jnp.concatenate(
        [out_buf, jnp.zeros((B, 1, D), out_buf.dtype)], axis=1)

    # gather back, weighted-combine over k
    ytok = out_buf[bidx, slot] * w[..., None].astype(out_buf.dtype)
    y = ytok.reshape(B, S, K, D).sum(axis=2)

    if "dense" in p:                                           # arctic residual
        y = y + ffn_apply(p["dense"], x, cfg.ffn_act)
    return y, aux


def _route(p, x, cfg: ModelConfig):
    """Router logits over all ``n_experts`` in float32, softmax, top-k,
    renormalised over the k (Qwen3-MoE's ``norm_topk_prob``): weights
    and expert ids, each (T, K)."""
    logits = jnp.dot(x.astype(jnp.float32), p["router"]["w"],
                     precision=jax.lax.Precision.HIGHEST)
    topw, topi = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
    return topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9), topi


def moe_dropless(p, x, cfg: ModelConfig):
    """x: (..., D) -> (y (..., D), counts (held,) int32).

    Every routing choice that lands on a held expert goes through that
    expert, whatever the imbalance; a choice that lands elsewhere adds
    nothing here (another device's share).  The held experts that some
    token picked run as one grouped kernel
    (``kernels.moe_experts.held_experts_op``), which reads no other
    expert's weights.  ``counts[e]``: the choices held expert ``e`` took.

    ``p["layer"]``, where present, says the expert weights are stacked
    over a scan's layers, (layers, held, ·, ·), and this call is layer
    ``p["layer"]``: the kernel then reads that layer's experts in place
    in the stack, where a slice taken per scan step would be copied whole
    before it could be read.
    """
    shape, D = x.shape, x.shape[-1]
    xt = x.reshape(-1, D)
    held = cfg.resolved_experts_held
    topw, topi = _route(p, xt, cfg)
    pick = (topi - cfg.expert_first)[..., None] == jnp.arange(held)
    comb = jnp.sum(jnp.where(pick, topw[..., None], 0.0), axis=1)
    counts = jnp.sum(pick, axis=(0, 1), dtype=jnp.int32)
    w = [p[k]["w"] for k in ("wi", "wg", "wo")]
    base = 0
    if "layer" in p:                           # every layer's experts
        base = p["layer"] * held
        w = [v.reshape(-1, *v.shape[2:]) for v in w]
    y = held_experts_op(xt, comb, counts, *w, jnp.asarray(base, jnp.int32),
                        act=cfg.ffn_act)
    y = y.astype(x.dtype).reshape(shape)
    if "dense" in p:                                           # arctic residual
        y = y + ffn_apply(p["dense"], x, cfg.ffn_act)
    return y, counts


def init_embed(key, vocab, d_model, dtype):
    # llama-style 0.02 init; gemma-family archs recover input magnitude via
    # scale_embed (×sqrt(d)) and keep tied logits well-scaled.
    return {"w": _dense_init(key, (vocab, d_model), jnp.float32, 0.02)
            .astype(dtype)}


def embed_apply(p, tokens):
    return jnp.take(p["w"], tokens, axis=0)
