"""Plain float32 reference of a Qwen3 dense decoder (the architecture of
https://huggingface.co/Qwen/Qwen3-4B, as transformers' ``Qwen3Model``
writes it), layer by layer so that it fits beside the served weights.

Per layer: RMSNorm, q/k/v projections, RMSNorm of each query and key head
(``q_norm``/``k_norm``), rotary embedding on the two halves of each head
(``rotate_half``, base ``rope_theta``), causal grouped-query attention
(query head h reads key/value head ``h // (heads / kv_heads)``), output
projection, residual; RMSNorm, SwiGLU MLP (``down(silu(gate(x)) * up(x))``),
residual.  Then the final RMSNorm and the tied vocabulary projection.
Every product runs in float32 at ``Precision.HIGHEST``; nothing is cached
and nothing is batched across sequences.

The weights are read by name from the pytree the benchmark made:
``embed.w`` (vocab, d), ``final_norm.scale``, and ``units[0]`` with each
leaf stacked over layers: ``norm1``/``norm2`` ``.scale``, ``attn.wq``/
``wk``/``wv``/``wo`` ``.w`` (in, out), ``attn.q_norm``/``k_norm``
``.scale``, ``ffn.wi`` (up), ``ffn.wg`` (gate), ``ffn.wo`` (down) ``.w``.

``quantize="fp8"`` is the control: every matrix rounded to float8 e4m3
with one scale per output column (per vocabulary row for the embedding),
the rest as above.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _mat(w, quantize: Optional[str], axis: int):
    """A weight matrix in float32, or rounded through float8 e4m3 with one
    scale per slice along ``axis`` (the reduced dimension)."""
    w = w.astype(jnp.float32)
    if quantize is None:
        return w
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]      # (S, hd/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("dims", "quantize"))
def _layer(x, lw, dims, quantize):
    H, KV, hd, eps, theta = dims
    S = x.shape[0]
    a = lw["attn"]
    h = _rms(x, lw["norm1"]["scale"], eps)
    q = jnp.dot(h, _mat(a["wq"]["w"], quantize, 0), precision=HI)
    k = jnp.dot(h, _mat(a["wk"]["w"], quantize, 0), precision=HI)
    v = jnp.dot(h, _mat(a["wv"]["w"], quantize, 0), precision=HI)
    q = _rms(q.reshape(S, H, hd), a["q_norm"]["scale"], eps)
    k = _rms(k.reshape(S, KV, hd), a["k_norm"]["scale"], eps)
    v = v.reshape(S, KV, hd)
    pos = jnp.arange(S)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    g = H // KV
    k = jnp.repeat(k, g, axis=1)                                 # (S, H, hd)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(hd)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(S, H * hd)
    x = x + jnp.dot(o, _mat(a["wo"]["w"], quantize, 0), precision=HI)
    f = lw["ffn"]
    h = _rms(x, lw["norm2"]["scale"], eps)
    up = jnp.dot(h, _mat(f["wi"]["w"], quantize, 0), precision=HI)
    gate = jnp.dot(h, _mat(f["wg"]["w"], quantize, 0), precision=HI)
    return x + jnp.dot(jax.nn.silu(gate) * up, _mat(f["wo"]["w"], quantize, 0),
                       precision=HI)


@functools.partial(jax.jit, static_argnames=("quantize",))
def _embed(embed, tokens, quantize):
    return _mat(embed, quantize, 1)[tokens]


@functools.partial(jax.jit, static_argnames=("eps", "vocab", "quantize"))
def _logits(x, final_scale, embed, eps, vocab, quantize):
    h = _rms(x, final_scale, eps)
    e = _mat(embed, quantize, 1)[:vocab]
    return jnp.dot(h, e.T, precision=HI)                        # (S, vocab)


def forward(weights, cfg: dict, tokens: np.ndarray,
            quantize: Optional[str] = None):
    """Logits (S, vocab) of every position of ``tokens`` (a padded,
    fixed-length row: padding at the end does not reach earlier
    positions), as a device array."""
    dims = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["rms_norm_eps"], float(cfg["rope_theta"]))
    eps, vocab = cfg["rms_norm_eps"], cfg["vocab_size"]
    toks = jnp.asarray(tokens, jnp.int32)
    x = _embed(weights["embed"]["w"], toks, quantize)
    units = weights["units"][0]
    for layer in range(cfg["num_hidden_layers"]):
        lw = jax.tree.map(lambda a: a[layer], units)
        x = _layer(x, lw, dims, quantize)
    return _logits(x, weights["final_norm"]["scale"], weights["embed"]["w"],
                   eps, vocab, quantize)


@jax.jit
def _gaps(logits, served):
    """Per position: how far the served token's logit lies below the
    best logit, and the best token."""
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return best - got, jnp.argmax(logits, axis=-1)


def served_gap(weights, cfg: dict, prompt: np.ndarray, out_tokens,
               length: int, control: Optional[str] = None) -> dict:
    """Run the reference once over ``prompt`` followed by the served
    tokens (all but the last, which no position predicts), padded to
    ``length``.  Returns the widest gap of a served token below the
    reference's best logit, and, with ``control``, the widest gap of the
    token that the control's logits put first."""
    out_tokens = np.asarray(out_tokens, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), out_tokens[:-1]])
    n_out = len(out_tokens)
    first = len(prompt) - 1                   # predicts out_tokens[0]
    row = np.zeros(length, np.int32)
    row[:len(seq)] = seq
    served = np.zeros(length, np.int32)
    served[first:first + n_out] = out_tokens
    ref = forward(weights, cfg, row)
    gap, _ = _gaps(ref, jnp.asarray(served))
    res = {"gap": float(np.asarray(gap)[first:first + n_out].max()),
           "tokens": n_out}
    if control is not None:
        ctl = forward(weights, cfg, row, quantize=control)
        _, pick = _gaps(ctl, jnp.asarray(served))
        cgap, _ = _gaps(ref, pick.astype(jnp.int32))
        res["control_gap"] = float(np.asarray(cgap)[first:first + n_out].max())
        del ctl
    del ref
    return res
