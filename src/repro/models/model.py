"""Unified block-pattern model.

One ``Model`` class covers all 10 assigned architectures: the layer stack
is a ``lax.scan`` over the config's repeating unit (stacked params), with
any remainder layers unrolled.  Three entry points:

* ``forward_train``  — full forward + CE loss (+ MoE aux, z-loss)
* ``prefill``        — forward returning logits + populated cache
* ``prefill_cached`` — a prompt chunk continuing a cache (engine prefill)
* ``decode_step``    — one token with cache (the serving hot path)

MoE layers take the capacity dispatch (``layers.moe_apply``) in
``forward_train`` and ``prefill`` (the dry-run's shapes) and the dropless
layer over the held experts (``layers.moe_dropless``) in the engine's two
programs, ``prefill_cached`` and ``decode_step``.  The one-shot
``prefill`` keeps the capacity dispatch for the dry-run: it lowers
``prefill`` on meshes that shard the experts over the "model" axis, where
the capacity einsums partition and the dropless layer's kernel would not
(GSPMD would gather every expert's weights to each device).  The engine's
two programs return, in the cache, what each MoE layer did in that call:
under ``"moe_counts"`` the routing choices it gave each held expert,
(MoE layers, experts held) int32, and under ``"moe_tap"`` the expert
layer's input and output (``"h"``, ``"y"``, each (MoE layers, rows, d))
at as many of the call's token rows as the incoming tap has: a decode
step's every slot, a prefill chunk's rows spread from its first token to
its last (``tap_rows``).  A check compares them with a reference.

Caches are pytrees mirroring the unit structure; attention blocks hold
(k, v) ring/linear buffers, recurrent blocks hold fixed-size state.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig, KV_BLOCKS
from . import attention as attn
from . import recurrent as rec
from .layers import (embed_apply, ffn_apply, init_embed, init_ffn, init_moe,
                     init_norm, linear, moe_apply, moe_dropless, norm_apply,
                     init_linear)

VISION_DIM = 1152  # stub SigLIP patch-embedding width (paligemma)

from .sharding_hooks import (set_activation_sharding,          # noqa: F401
                             clear_activation_sharding,        # noqa: F401
                             constrain_logits as _constrain_logits,
                             constrain_tokens_dim as _constrain_tokens_dim)


# ---------------------------------------------------------------------------
# per-block param init / apply
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ModelConfig, kind: str):
    ks = jax.random.split(key, 4)
    dtype = jnp.dtype(cfg.dtype)
    p: Dict[str, Any] = {"norm1": init_norm(cfg.d_model, dtype, cfg.norm_type)}
    if kind in ("attn", "swa", "xattn"):
        p["attn"] = attn.init_attention(ks[0], cfg, kind)
        p["norm2"] = init_norm(cfg.d_model, dtype, cfg.norm_type)
        if cfg.n_experts:
            p["ffn"] = init_moe(ks[1], cfg, dtype)
        else:
            p["ffn"] = init_ffn(ks[1], cfg.d_model, cfg.d_ff, dtype,
                                cfg.ffn_act)
    elif kind == "rglru":
        p["rglru"] = rec.init_rglru(ks[0], cfg)
        p["norm2"] = init_norm(cfg.d_model, dtype, cfg.norm_type)
        p["ffn"] = init_ffn(ks[1], cfg.d_model, cfg.d_ff, dtype, cfg.ffn_act)
    elif kind == "mlstm":
        p["mlstm"] = rec.init_mlstm(ks[0], cfg)
    elif kind == "slstm":
        p["slstm"] = rec.init_slstm(ks[0], cfg)
        p["norm2"] = init_norm(cfg.d_model, dtype, cfg.norm_type)
        p["ffn"] = init_ffn(ks[1], cfg.d_model,
                            max(4 * cfg.d_model // 3, 64), dtype, "geglu")
    else:
        raise ValueError(kind)
    return p


def _dense_ffn(p, x, cfg):
    act = "geglu" if "wg" in p and cfg.ffn_act == "geglu" else (
        "swiglu" if "wg" in p else "gelu")
    return ffn_apply(p, x, act)


def _ffn_or_moe(p, x, cfg):
    """Training-path FFN: (y, MoE aux loss)."""
    if cfg.n_experts and "router" in p:
        return moe_apply(p, x, cfg)
    return _dense_ffn(p, x, cfg), 0.0


def tap_rows(n: int, rows: int):
    """The ``rows`` of a call's ``n`` token rows whose expert layer the
    call taps: all of them where ``n == rows``, else spread evenly from
    the first to the last (repeated where ``n < rows``)."""
    if n == rows:
        return None
    if rows == 1:
        return np.array([n - 1])
    return np.arange(rows) * (n - 1) // (rows - 1)


def _ffn_serve(p, x, cfg, rows=None):
    """Serving-path FFN: (y, None if dense, else (per-held-expert counts,
    the expert layer's input and output at ``rows`` of the call's token
    rows, all of them by default))."""
    if not (cfg.n_experts and "router" in p):
        return _dense_ffn(p, x, cfg), None
    y, counts = moe_dropless(p, x, cfg)
    D = x.shape[-1]
    xt, yt = x.reshape(-1, D), y.reshape(-1, D)
    idx = tap_rows(xt.shape[0], rows or xt.shape[0])
    if idx is not None:
        xt, yt = xt[idx], yt[idx]
    return y, (counts, xt, yt)


def _block_prefill(p, kind, x, positions, cfg, enc_out, state_in):
    """Returns (x, cache_entry, aux)."""
    aux = 0.0
    if kind in ("attn", "swa", "xattn"):
        h, cache = attn.attn_prefill(p["attn"], norm_apply(p["norm1"], x,
                                                           cfg.norm_eps),
                                     positions, cfg, kind, enc_out)
        x = x + h
        h, a = _ffn_or_moe(p["ffn"], norm_apply(p["norm2"], x, cfg.norm_eps),
                           cfg)
        x = x + h
        return x, cache, aux + a
    if kind == "rglru":
        h, st = rec.rglru_prefill(p["rglru"],
                                  norm_apply(p["norm1"], x, cfg.norm_eps),
                                  cfg, state_in)
        x = x + h
        h, a = _ffn_or_moe(p["ffn"], norm_apply(p["norm2"], x, cfg.norm_eps),
                           cfg)
        return x + h, st, aux + a
    if kind == "mlstm":
        h, st = rec.mlstm_prefill(p["mlstm"],
                                  norm_apply(p["norm1"], x, cfg.norm_eps),
                                  cfg, state_in)
        return x + h, st, aux
    if kind == "slstm":
        h, st = rec.slstm_prefill(p["slstm"],
                                  norm_apply(p["norm1"], x, cfg.norm_eps),
                                  cfg, state_in)
        x = x + h
        h, a = _ffn_or_moe(p["ffn"], norm_apply(p["norm2"], x, cfg.norm_eps),
                           cfg)
        return x + h, st, aux + a
    raise ValueError(kind)


def _block_decode(p, kind, x, pos, cfg, cache):
    """Returns (x, new_cache_entry, MoE outputs (``_ffn_serve``) or
    None)."""
    if kind in ("attn", "swa", "xattn"):
        h, cache = attn.attn_decode(p["attn"],
                                    norm_apply(p["norm1"], x, cfg.norm_eps),
                                    cache, pos, cfg, kind)
        x = x + h
        h, n = _ffn_serve(p["ffn"], norm_apply(p["norm2"], x, cfg.norm_eps),
                          cfg)
        return x + h, cache, n
    if kind == "rglru":
        h, st = rec.rglru_decode(p["rglru"],
                                 norm_apply(p["norm1"], x, cfg.norm_eps),
                                 cache, cfg)
        x = x + h
        h, n = _ffn_serve(p["ffn"], norm_apply(p["norm2"], x, cfg.norm_eps),
                          cfg)
        return x + h, st, n
    if kind == "mlstm":
        h, st = rec.mlstm_decode(p["mlstm"],
                                 norm_apply(p["norm1"], x, cfg.norm_eps),
                                 cache, cfg)
        return x + h, st, None
    if kind == "slstm":
        h, st = rec.slstm_decode(p["slstm"],
                                 norm_apply(p["norm1"], x, cfg.norm_eps),
                                 cache, cfg)
        x = x + h
        h, n = _ffn_serve(p["ffn"], norm_apply(p["norm2"], x, cfg.norm_eps),
                          cfg)
        return x + h, st, n
    raise ValueError(kind)


def _init_block_cache(kind, B, cache_len, cfg: ModelConfig):
    KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    dtype = jnp.dtype(cfg.dtype)
    if kind == "attn":
        shp = (B, cache_len, KV, hd)
        return (jnp.zeros(shp, dtype), jnp.zeros(shp, dtype))
    if kind == "swa":
        shp = (B, min(cfg.window_size, cache_len), KV, hd)
        return (jnp.zeros(shp, dtype), jnp.zeros(shp, dtype))
    if kind == "xattn":
        shp = (B, cache_len, KV, hd)
        xshp = (B, cfg.enc_seq, KV, hd)
        return (jnp.zeros(shp, dtype), jnp.zeros(shp, dtype),
                jnp.zeros(xshp, dtype), jnp.zeros(xshp, dtype))
    if kind == "rglru":
        return rec.rglru_init_state(B, cfg)
    if kind == "mlstm":
        return rec.mlstm_init_state(B, cfg)
    if kind == "slstm":
        return rec.slstm_init_state(B, cfg)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# whisper encoder (stub frontend: input is (B, enc_seq, enc_d_model) frames)
# ---------------------------------------------------------------------------

def _init_encoder(key, cfg: ModelConfig):
    eD = cfg.enc_d_model or cfg.d_model
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, cfg.enc_layers + 1)

    def one(k):
        kk = jax.random.split(k, 6)
        return {
            "norm1": init_norm(eD, dtype, "layernorm"),
            "wq": init_linear(kk[0], eD, eD, dtype),
            "wk": init_linear(kk[1], eD, eD, dtype),
            "wv": init_linear(kk[2], eD, eD, dtype),
            "wo": init_linear(kk[3], eD, eD, dtype),
            "norm2": init_norm(eD, dtype, "layernorm"),
            "ffn": init_ffn(kk[4], eD, 4 * eD, dtype, "gelu"),
        }
    layers = jax.vmap(one)(jnp.stack(ks[:-1]))
    return {"layers": layers, "final_norm": init_norm(eD, dtype, "layernorm")}


def _encoder_apply(p, frames, cfg: ModelConfig):
    eD = cfg.enc_d_model or cfg.d_model
    H = cfg.n_heads
    hd = eD // H
    S = frames.shape[1]
    # sinusoidal positions
    pos = jnp.arange(S)[:, None]
    dim = jnp.arange(eD // 2)[None, :]
    angle = pos / jnp.power(10000.0, 2 * dim / eD)
    pe = jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)
    x = frames + pe.astype(frames.dtype)

    def body(x, lp):
        h = norm_apply(lp["norm1"], x, cfg.norm_eps)
        B, S, _ = h.shape
        q = linear(lp["wq"], h).reshape(B, S, H, hd)
        k = linear(lp["wk"], h).reshape(B, S, H, hd)
        v = linear(lp["wv"], h).reshape(B, S, H, hd)
        y = attn._sdpa(q, k, v, None)
        x = x + linear(lp["wo"], y.reshape(B, S, -1))
        x = x + ffn_apply(lp["ffn"], norm_apply(lp["norm2"], x, cfg.norm_eps),
                          "gelu")
        return x, None

    x, _ = jax.lax.scan(body, x, p["layers"])
    return norm_apply(p["final_norm"], x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.unit, self.n_units, self.remainder = cfg.repeating_unit()
        #: layers with a router (attention blocks of an MoE model)
        self.moe_layers = (sum(k in ("attn", "swa", "xattn")
                               for k in cfg.block_pattern)
                           if cfg.n_experts else 0)

    # -------------------------------------------------------------- init
    def init(self, rng) -> Dict[str, Any]:
        cfg = self.cfg
        keys = jax.random.split(rng, 8)
        params: Dict[str, Any] = {
            "embed": init_embed(keys[0], cfg.padded_vocab_size, cfg.d_model,
                                jnp.dtype(cfg.dtype)),
            "final_norm": init_norm(cfg.d_model, jnp.dtype(cfg.dtype),
                                    cfg.norm_type),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = init_linear(keys[1], cfg.d_model,
                                            cfg.padded_vocab_size,
                                            jnp.dtype(cfg.dtype))
        # stacked unit params: for each position j in unit, vmap init over
        # n_units
        unit_params = []
        for j, kind in enumerate(self.unit):
            ks = jax.random.split(jax.random.fold_in(keys[2], j),
                                  self.n_units)
            unit_params.append(
                jax.vmap(lambda k, kind=kind: _init_block(k, cfg, kind))(
                    jnp.stack(ks)))
        params["units"] = tuple(unit_params)
        rest = []
        for j, kind in enumerate(self.remainder):
            rest.append(_init_block(jax.random.fold_in(keys[3], j), cfg,
                                    kind))
        params["rest"] = tuple(rest)
        if cfg.is_encdec:
            params["encoder"] = _init_encoder(keys[4], cfg)
            params["dec_pos"] = (jax.random.normal(
                keys[5], (cfg.max_position, cfg.d_model), jnp.float32)
                * 0.01).astype(jnp.dtype(cfg.dtype))
        if cfg.n_patches:
            params["vlm_proj"] = init_linear(keys[6], VISION_DIM, cfg.d_model,
                                             jnp.dtype(cfg.dtype))
        return params

    def abstract_params(self):
        return jax.eval_shape(self.init, jax.random.key(0))

    # ----------------------------------------------------------- embeds
    def _embed_inputs(self, params, tokens, batch, positions):
        cfg = self.cfg
        x = embed_apply(params["embed"], tokens)
        if cfg.scale_embed:
            x = x * math.sqrt(cfg.d_model)
        if cfg.n_patches and batch.get("patch_embeds") is not None:
            pe = linear(params["vlm_proj"], batch["patch_embeds"]
                        .astype(x.dtype))
            x = jnp.concatenate([pe, x], axis=1)
            positions = jnp.arange(x.shape[1])[None, :] * jnp.ones(
                (x.shape[0], 1), jnp.int32)
        if cfg.is_encdec:
            x = x + jnp.take(params["dec_pos"],
                             jnp.clip(positions, 0, cfg.max_position - 1),
                             axis=0)
        x = _constrain_tokens_dim(x)
        return x, positions

    def _logits(self, params, x):
        cfg = self.cfg
        x = norm_apply(params["final_norm"], x, cfg.norm_eps)
        if cfg.tie_embeddings:
            w = params["embed"]["w"].T
        else:
            w = params["lm_head"]["w"]
        logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype),
                            preferred_element_type=jnp.float32)
        logits = _constrain_logits(logits)
        if cfg.logit_soft_cap:
            logits = jnp.tanh(logits / cfg.logit_soft_cap) * cfg.logit_soft_cap
        return logits

    # ---------------------------------------------------------- prefill
    def _stack_forward(self, params, x, positions, enc_out, cache,
                       remat=False):
        """Run the full layer stack in prefill mode.

        cache: None (fresh) or pytree from ``init_cache``; recurrent blocks
        consume carried state from it.  Returns (x, new_cache, aux).
        """
        cfg = self.cfg
        unit = self.unit

        def unit_body(carry, xs):
            x, aux = carry
            p_j = xs["params"]
            st_j = xs["state"]
            new_states = []
            for j, kind in enumerate(unit):
                x, st, a = _block_prefill(p_j[j], kind, x, positions, cfg,
                                          enc_out,
                                          None if st_j is None else st_j[j])
                x = _constrain_tokens_dim(x)
                new_states.append(st)
                aux = aux + a
            return (x, aux), tuple(new_states)

        body = jax.checkpoint(unit_body) if remat else unit_body
        xs = {"params": params["units"],
              "state": None if cache is None else cache["units"]}
        if cache is None:
            xs["state"] = tuple(None for _ in unit)
            # scan requires concrete xs; use empty placeholders via None ->
            # replace with zeros-free sentinel: wrap as all-None pytree is
            # not scannable, so pass fresh states only for recurrent blocks.
            xs["state"] = self._fresh_scan_states(x.shape[0])
        (x, aux), new_unit_caches = jax.lax.scan(body, (x, 0.0), xs)

        rest_caches = []
        for j, kind in enumerate(self.remainder):
            st_in = (None if cache is None else cache["rest"][j])
            if st_in is None and kind not in KV_BLOCKS:
                st_in = _init_block_cache(kind, x.shape[0], 1, cfg)
            x, st, a = _block_prefill(params["rest"][j], kind, x, positions,
                                      cfg, enc_out, st_in)
            rest_caches.append(st)
            aux = aux + a
        return x, {"units": new_unit_caches, "rest": tuple(rest_caches)}, aux

    def _fresh_scan_states(self, B):
        """Stacked zero states for recurrent unit positions (prefill)."""
        out = []
        for kind in self.unit:
            if kind in KV_BLOCKS:
                out.append(None)
            else:
                st = _init_block_cache(kind, B, 1, self.cfg)
                out.append(jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (self.n_units,) + a.shape),
                    st))
        return tuple(out)

    # ------------------------------------------------------------ train
    def forward_train(self, params, batch, remat=True):
        """batch: tokens (B,S), targets (B,S), optional frames/patch_embeds.
        Returns (loss, metrics)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))
        enc_out = None
        if cfg.is_encdec:
            enc_out = _encoder_apply(params["encoder"], batch["frames"], cfg)
        x, positions = self._embed_inputs(params, tokens, batch, positions)
        x, _, aux = self._stack_forward(params, x, positions, enc_out, None,
                                        remat=remat)
        if cfg.n_patches:
            x = x[:, cfg.n_patches:]          # loss only on text tokens
        logits = self._logits(params, x)
        targets = batch["targets"]
        mask = (targets >= 0).astype(jnp.float32)
        tgt = jnp.maximum(targets, 0)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tgt[..., None],
                                   axis=-1)[..., 0]
        nll = (logz - gold) * mask
        denom = jnp.maximum(mask.sum(), 1.0)
        ce = nll.sum() / denom
        zloss = 1e-4 * jnp.sum(jnp.square(logz) * mask) / denom
        loss = ce + zloss + aux
        return loss, {"ce": ce, "aux": aux, "zloss": zloss,
                      "tokens": mask.sum()}

    # ---------------------------------------------------------- serving
    def init_cache(self, B, cache_len):
        cfg = self.cfg
        unit_caches = []
        for kind in self.unit:
            c = _init_block_cache(kind, B, cache_len, cfg)
            unit_caches.append(jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a, (self.n_units,) + a.shape).copy(), c))
        rest = tuple(_init_block_cache(k, B, cache_len, cfg)
                     for k in self.remainder)
        cache = {"units": tuple(unit_caches), "rest": rest,
                 "enc_out": (jnp.zeros((B, cfg.enc_seq,
                                        cfg.enc_d_model or cfg.d_model),
                                       jnp.dtype(cfg.dtype))
                             if cfg.is_encdec else ())}
        if self.moe_layers:
            cache["moe_counts"] = jnp.zeros(
                (self.moe_layers, cfg.resolved_experts_held), jnp.int32)
            tap = jnp.zeros((self.moe_layers, B, cfg.d_model),
                            jnp.dtype(cfg.dtype))
            cache["moe_tap"] = {"h": tap, "y": tap}
        return cache

    def _scan_inputs(self, params, cache):
        """The serving scans' inputs, and the stacked expert weights of
        each unit position (``None`` where it has none).  A dense model's
        are as they always were.  An MoE model's expert weights leave the
        scanned inputs and the scan counts layers: ``moe_dropless`` reads
        a layer's experts in place in the stack (``"layer"``)."""
        xs = {"params": params["units"], "cache": cache["units"]}
        if not self.moe_layers:
            return xs, None
        units, stacks = [], []
        for p in params["units"]:
            f = p.get("ffn", {})
            w = ({k: f[k] for k in ("wi", "wg", "wo") if k in f}
                 if "router" in f else None)
            units.append(p if w is None else dict(p, ffn={
                k: v for k, v in f.items() if k not in w}))
            stacks.append(w)
        xs.update(params=tuple(units), layer=jnp.arange(self.n_units))
        return xs, stacks

    @staticmethod
    def _unit_params(xs, stacks):
        """One scan step's unit parameters, with the stacked experts."""
        if stacks is None:
            return xs["params"]
        return tuple(p if w is None else
                     dict(p, ffn=dict(p["ffn"], layer=xs["layer"], **w))
                     for p, w in zip(xs["params"], stacks))

    def _with_moe(self, cache, unit_moe, rest_moe):
        """``cache`` with the call's MoE outputs (``_ffn_serve``) in layer
        order: ``"moe_counts"`` (MoE layers, held) and ``"moe_tap"``;
        a dense model's cache is returned as it is."""
        parts = [m for m in unit_moe if m is not None]
        out = ([jax.tree.map(lambda *a: jnp.stack(a, axis=1).reshape(
            -1, *a[0].shape[1:]), *parts)] if parts else [])
        out += [jax.tree.map(lambda a: a[None], m) for m in rest_moe
                if m is not None]
        if out:
            counts, h, y = jax.tree.map(lambda *a: jnp.concatenate(a), *out)
            cache["moe_counts"] = counts
            cache["moe_tap"] = {"h": h, "y": y}
        return cache

    def prefill(self, params, tokens, batch=None, positions=None,
                last_only=False):
        """Prefill; returns (logits, cache). Cache buffers are sized to
        the prompt (use ``pad_cache``/engine paging for growth).

        last_only: compute lm-head logits for the final position only —
        the serving semantic (§Perf it#3: skips a (B,S,V) matmul + its
        vocab-axis all-reduce)."""
        cfg = self.cfg
        batch = batch or {}
        B, S = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                         (B, S))
        enc_out = None
        if cfg.is_encdec:
            enc_out = _encoder_apply(params["encoder"], batch["frames"], cfg)
        x, positions = self._embed_inputs(params, tokens, batch, positions)
        x, cache, _ = self._stack_forward(params, x, positions, enc_out, None,
                                          remat=False)
        cache["enc_out"] = enc_out if cfg.is_encdec else ()
        if last_only:
            x = x[:, -1:]
        logits = self._logits(params, x)[..., :cfg.vocab_size]
        return logits, cache

    def prefill_cached(self, params, tokens, positions, cache, cache_len,
                       enc_out=None):
        """Chunked prefill continuing ``cache`` (engine hot path).

        tokens: (B,S_c); positions: (B,S_c) absolute; cache_len: (B,).
        Recurrent blocks resume from their cached state; attention blocks
        attend over cached prefix + chunk.  Returns (logits, new_cache).
        """
        cfg = self.cfg
        x = embed_apply(params["embed"], tokens)
        if cfg.scale_embed:
            x = x * math.sqrt(cfg.d_model)
        if cfg.is_encdec:
            x = x + jnp.take(params["dec_pos"],
                             jnp.clip(positions, 0, cfg.max_position - 1),
                             axis=0)

        rows = (cache["moe_tap"]["h"].shape[1] if "moe_tap" in cache
                else 1)

        def block_step(p, kind, x, c):
            if kind in ("attn", "swa", "xattn"):
                h, c2 = attn.attn_prefill_cached(
                    p["attn"], norm_apply(p["norm1"], x, cfg.norm_eps),
                    positions, cfg, kind, c, cache_len, enc_out)
                x = x + h
                h, n = _ffn_serve(p["ffn"],
                                  norm_apply(p["norm2"], x, cfg.norm_eps),
                                  cfg, rows)
                return x + h, c2, n
            # recurrent blocks: plain prefill continuation from state
            x2, c2, _ = _block_prefill(p, kind, x, positions, cfg, enc_out, c)
            return x2, c2, None

        scan_xs, stacks = self._scan_inputs(params, cache)

        def unit_body(x, xs):
            p_j, c_j = self._unit_params(xs, stacks), xs["cache"]
            new_c, moe = [], []
            for j, kind in enumerate(self.unit):
                x, c2, n = block_step(p_j[j], kind, x, c_j[j])
                new_c.append(c2)
                moe.append(n)
            return x, (tuple(new_c), tuple(moe))

        x, (new_unit, unit_moe) = jax.lax.scan(unit_body, x, scan_xs)
        new_rest, rest_moe = [], []
        for j, kind in enumerate(self.remainder):
            x, c2, n = block_step(params["rest"][j], kind, x,
                                  cache["rest"][j])
            new_rest.append(c2)
            rest_moe.append(n)
        logits = self._logits(params, x)[..., :cfg.vocab_size]
        return logits, self._with_moe(
            {"units": new_unit, "rest": tuple(new_rest),
             "enc_out": cache.get("enc_out", ())}, unit_moe, rest_moe)

    def decode_step(self, params, token, pos, cache):
        """token: (B,1) int32; pos: (B,) absolute position. Returns
        (logits (B,1,V), new_cache)."""
        cfg = self.cfg
        B = token.shape[0]
        positions = pos[:, None]
        x, positions = self._embed_inputs(params, token, {}, positions)

        scan_xs, stacks = self._scan_inputs(params, cache)

        def unit_body(x, xs):
            p_j, c_j = self._unit_params(xs, stacks), xs["cache"]
            new_c, moe = [], []
            for j, kind in enumerate(self.unit):
                x, c, n = _block_decode(p_j[j], kind, x, pos, cfg, c_j[j])
                new_c.append(c)
                moe.append(n)
            return x, (tuple(new_c), tuple(moe))

        x, (new_unit, unit_moe) = jax.lax.scan(unit_body, x, scan_xs)
        new_rest, rest_moe = [], []
        for j, kind in enumerate(self.remainder):
            x, c, n = _block_decode(params["rest"][j], kind, x, pos, cfg,
                                    cache["rest"][j])
            new_rest.append(c)
            rest_moe.append(n)
        logits = self._logits(params, x)[..., :cfg.vocab_size]
        return logits, self._with_moe(
            {"units": new_unit, "rest": tuple(new_rest),
             "enc_out": cache.get("enc_out", ())}, unit_moe, rest_moe)
