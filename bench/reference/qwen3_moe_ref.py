"""Plain float32 reference of a Qwen3-MoE decoder (the architecture of
https://huggingface.co/Qwen/Qwen3-30B-A3B, as transformers'
``Qwen3MoeModel`` writes it), layer by layer so that it fits beside the
served weights.

Per layer: RMSNorm, q/k/v projections, RMSNorm of each query and key head,
rotary embedding on the two halves of each head (base ``rope_theta``),
causal grouped-query attention, output projection, residual; RMSNorm, the
router (``gate``: logits over all ``num_experts``, softmax, the top
``num_experts_per_tok``, renormalised over them as ``norm_topk_prob``
says), each chosen expert's SwiGLU (``down(silu(gate(x)) * up(x))``)
weighted by its renormalised probability, residual.  Then the final
RMSNorm and the untied vocabulary projection (``lm_head``).  Every product
runs in float32 at ``Precision.HIGHEST``; nothing is cached and nothing is
batched across sequences; each held expert is computed densely over every
position and weighted by zero where no choice picked it.

Departures from the published model, each shared with the program:

* the expert share: the weights hold experts ``first_expert_held`` to
  ``first_expert_held + n_experts_held - 1`` of each layer, and a choice
  that lands on another expert adds nothing (that expert lives on another
  chip of the deployment).  With the share set to all experts this is the
  published layer.

The weights are read by name from the pytree the benchmark made:
``embed.w`` (vocab, d), ``lm_head.w`` (d, vocab), ``final_norm.scale``,
and ``units[0]`` with each leaf stacked over layers: ``norm1``/``norm2``
``.scale``, ``attn.wq``/``wk``/``wv``/``wo`` ``.w`` (in, out),
``attn.q_norm``/``k_norm`` ``.scale``, ``ffn.router.w`` (d, experts),
``ffn.wi`` (up), ``ffn.wg`` (gate) (held, d, F) and ``ffn.wo`` (down)
(held, F, d) ``.w``.

``expert_err`` checks the program's expert layer on its own: given the
layer inputs that the program's served calls tapped (``moe_tap``), this
layer's output is compared with the program's, relative to its norm.  A
chip holds one eighth of a token's expert work, so the expert layer moves
the logits little and its faults hardly show in the served tokens; here
they do.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference.qwen3_ref import _gaps, _rms, _rope

HI = jax.lax.Precision.HIGHEST


def _f32(w):
    return w.astype(jnp.float32)


def _attention(x, lw, dims):
    H, KV, hd, eps, theta = dims
    S = x.shape[0]
    a = lw["attn"]
    h = _rms(x, lw["norm1"]["scale"], eps)
    q = jnp.dot(h, _f32(a["wq"]["w"]), precision=HI)
    k = jnp.dot(h, _f32(a["wk"]["w"]), precision=HI)
    v = jnp.dot(h, _f32(a["wv"]["w"]), precision=HI)
    q = _rms(q.reshape(S, H, hd), a["q_norm"]["scale"], eps)
    k = _rms(k.reshape(S, KV, hd), a["k_norm"]["scale"], eps)
    v = v.reshape(S, KV, hd)
    pos = jnp.arange(S)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    g = H // KV
    k = jnp.repeat(k, g, axis=1)                                 # (S, H, hd)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(hd)
    s = jnp.where((pos[:, None] >= pos[None, :])[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(S, H * hd)
    return x + jnp.dot(o, _f32(a["wo"]["w"]), precision=HI)


def moe_ffn(h, f, moe):
    """The expert layer on normed activations ``h`` (S, d) in float32:
    ``moe`` is (experts per token, first held expert, experts held),
    ``f`` the layer's ``ffn`` weights.  Also the router logits."""
    top_k, first, held = moe
    logits = jnp.dot(h, f["router"]["w"].astype(jnp.float32), precision=HI)
    topw, topi = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    pick = (topi - first)[..., None] == jnp.arange(held)          # (S, k, E)
    w = jnp.sum(jnp.where(pick, topw[..., None], 0.0), axis=1)    # (S, E)
    up = jnp.einsum("sd,edf->esf", h, _f32(f["wi"]["w"]),
                    precision=HI)
    gate = jnp.einsum("sd,edf->esf", h, _f32(f["wg"]["w"]),
                      precision=HI)
    out = jnp.einsum("esf,efd->esd", jax.nn.silu(gate) * up,
                     _f32(f["wo"]["w"]), precision=HI)
    return jnp.einsum("se,esd->sd", w, out, precision=HI), logits


@functools.partial(jax.jit, static_argnames=("dims", "moe"))
def _layer(x, lw, n_valid, dims, moe):
    """One layer: its output, and the number of valid positions whose
    router logits put less than 1e-3 between the k-th and the (k+1)-th
    expert."""
    x = _attention(x, lw, dims)
    y, logits = moe_ffn(_rms(x, lw["norm2"]["scale"], dims[3]), lw["ffn"],
                        moe)
    top = jax.lax.top_k(logits, moe[0] + 1)[0]
    close = jnp.sum((top[:, -2] - top[:, -1] < 1e-3)
                    & (jnp.arange(x.shape[0]) < n_valid))
    return x + y, close


@functools.partial(jax.jit, static_argnames=("moe",))
def _tap_err(h, y, f, moe):
    """Per row: ``|y - want| / |want|``, ``want`` this layer's output on
    the program's input ``h``; where no choice landed on a held expert
    (``want`` zero), NaN if ``y`` is zero too, else infinite."""
    want, _ = moe_ffn(h.astype(jnp.float32), f, moe)
    y = y.astype(jnp.float32)
    den = jnp.linalg.norm(want, axis=-1)
    err = jnp.linalg.norm(y - want, axis=-1) / jnp.where(den > 0, den, 1.0)
    empty = jnp.where(jnp.any(y != 0, axis=-1), jnp.inf, jnp.nan)
    return jnp.where(den > 0, err, empty)


def expert_err(weights, cfg: dict, h, y) -> np.ndarray:
    """The program's expert layer against this one, on the inputs the
    program's calls tapped: ``h`` and ``y`` (MoE layers, rows, d), its
    inputs and outputs.  Returns the (layers, rows) errors of
    ``_tap_err``."""
    ffn = weights["units"][0]["ffn"]
    moe = (cfg["num_experts_per_tok"], cfg["first_expert_held"],
           cfg["n_experts_held"])
    return np.stack([np.asarray(_tap_err(
        jnp.asarray(h[layer]), jnp.asarray(y[layer]),
        jax.tree.map(lambda a: a[layer], ffn), moe))
        for layer in range(cfg["num_hidden_layers"])])


@jax.jit
def _embed(embed, tokens):
    return _f32(embed)[tokens]


@functools.partial(jax.jit, static_argnames=("eps", "vocab"))
def _logits(x, final_scale, head, eps, vocab):
    h = _rms(x, final_scale, eps)
    return jnp.dot(h, _f32(head)[:, :vocab], precision=HI)


def forward(weights, cfg: dict, tokens: np.ndarray, n_valid: int):
    """Logits (S, vocab) of every position of ``tokens`` (a padded,
    fixed-length row: padding at the end does not reach earlier
    positions), as a device array, and the share of the first
    ``n_valid`` positions' (position, layer) routings whose k-th and
    (k+1)-th router logits lie within 1e-3."""
    dims = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["rms_norm_eps"], float(cfg["rope_theta"]))
    moe = (cfg["num_experts_per_tok"], cfg["first_expert_held"],
           cfg["n_experts_held"])
    eps, vocab = cfg["rms_norm_eps"], cfg["vocab_size"]
    x = _embed(weights["embed"]["w"], jnp.asarray(tokens, jnp.int32))
    units = weights["units"][0]
    close = 0
    for layer in range(cfg["num_hidden_layers"]):
        x, c = _layer(x, jax.tree.map(lambda a: a[layer], units), n_valid,
                      dims, moe)
        close += c
    logits = _logits(x, weights["final_norm"]["scale"],
                     weights["lm_head"]["w"], eps, vocab)
    return logits, float(close) / (n_valid * cfg["num_hidden_layers"])


def served_gap(weights, cfg: dict, prompt: np.ndarray, out_tokens,
               length: int) -> dict:
    """Run the reference once over ``prompt`` followed by the served
    tokens (all but the last, which no position predicts), padded to
    ``length``.  Returns the widest gap of a served token below the
    reference's best logit and the share of near-tied routings."""
    out_tokens = np.asarray(out_tokens, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), out_tokens[:-1]])
    n_out = len(out_tokens)
    first = len(prompt) - 1                   # predicts out_tokens[0]
    row = np.zeros(length, np.int32)
    row[:len(seq)] = seq
    served = np.zeros(length, np.int32)
    served[first:first + n_out] = out_tokens
    ref, close = forward(weights, cfg, row, len(seq))
    gap, _ = _gaps(ref, jnp.asarray(served))
    res = {"gap": float(np.asarray(gap)[first:first + n_out].max()),
           "tokens": n_out, "positions": len(seq), "near_ties": close}
    del ref
    return res
