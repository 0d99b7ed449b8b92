"""Operations and bytes of a Qwen3-MoE decoder that holds a share of each
layer's experts, served in bf16 (``serve_moe`` cells), from its shapes.

The expert work of a step is not a function of its shapes: it follows
the routing.  So expert FLOPs are counted from the routing choices that
landed on held experts (``engine.moe_routed_local``) and expert bytes
from the held experts that some token picked
(``engine.moe_experts_touched``); everything else from the shapes.
"""
from __future__ import annotations


def moe_sizes(cfg: dict) -> dict:
    """Parameter counts of the held share (``n_experts_held`` experts of
    every layer, the router over all ``num_experts``)."""
    d = cfg["hidden_size"]
    L = cfg["num_hidden_layers"]
    H = cfg["num_attention_heads"]
    KV = cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    F = cfg["moe_intermediate_size"]
    V = cfg["vocab_size"]
    attn = d * (H * hd) + 2 * d * (KV * hd) + (H * hd) * d
    router = d * cfg["num_experts"]
    expert = 3 * d * F
    norms = L * (2 * d + 2 * hd) + d
    embed = V * d
    head = 0 if cfg["tie_word_embeddings"] else V * d
    held = L * cfg["n_experts_held"] * expert
    return {"attn_params": L * attn, "router_params": L * router,
            "expert_params": expert, "held_expert_params": held,
            "embed_params": embed, "head_params": head, "norm_params": norms,
            "params": L * (attn + router) + held + embed + head + norms,
            "kv_bytes_per_token": L * 2 * KV * hd * 2,
            "L": L, "H": H, "hd": hd, "d": d, "V": V}


def expert_flops(cfg: dict, routed: int) -> float:
    """``routed`` routing choices through a held expert's three
    matmuls (gate, up, down)."""
    return 2.0 * moe_sizes(cfg)["expert_params"] * routed


def token_flops(cfg: dict, context: int, logits: bool) -> float:
    """Model FLOPs of one token attending over ``context`` positions
    (itself included), expert work left out: attention's matmuls and two
    products, the router, and the vocabulary projection where the token's
    logits are needed."""
    s = moe_sizes(cfg)
    f = (2.0 * (s["attn_params"] + s["router_params"])
         + 4.0 * s["L"] * s["H"] * s["hd"] * context)
    if logits:
        f += 2.0 * s["V"] * s["d"]
    return f


def chunk_flops(cfg: dict, start: int, n: int) -> float:
    """A prefill chunk of ``n`` tokens at positions ``start..start+n-1``,
    expert work left out; logits for its last token only."""
    s = moe_sizes(cfg)
    ctx_sum = n * start + n * (n + 1) / 2
    return (2.0 * (s["attn_params"] + s["router_params"]) * n
            + 4.0 * s["L"] * s["H"] * s["hd"] * ctx_sum
            + 2.0 * s["V"] * s["d"])


def decode_step_flops(cfg: dict, contexts, routed: int) -> float:
    """A batched decode step over sequences of ``contexts`` cached
    positions whose tokens made ``routed`` choices of held experts."""
    return (sum(token_flops(cfg, c + 1, True) for c in contexts)
            + expert_flops(cfg, routed))


def decode_step_bytes(cfg: dict, contexts, touched: int) -> float:
    """Least HBM traffic of one batched decode step: attention weights,
    norms, the float32 router and the head once, one embedding row per
    sequence, the ``touched`` (layer, held expert) pairs that some token
    picked, the cache of each running sequence's context and each new
    token's keys and values."""
    s = moe_sizes(cfg)
    kv = s["kv_bytes_per_token"]
    n = len(contexts)
    return (2.0 * (s["attn_params"] + s["norm_params"] + s["head_params"])
            + 4.0 * s["router_params"] + 2.0 * s["d"] * n
            + 2.0 * s["expert_params"] * touched
            + kv * float(sum(contexts)) + kv * n)
