"""Operations and bytes that each measured device program needs, from its
shapes.  Roofline shares divide the least time these allow on the chip
by the program's device time in the trace."""
from __future__ import annotations


# ---------------------------------------------------------------------------
# the router's wave score (kernels/route_score.py, lmetric ptoken x bs)
# ---------------------------------------------------------------------------
#: element operations per (request, instance) pair of one wave step:
#: max(depth, credit), *block, min(plen), rbs+qbs, plen-hit, qpt+., +1,
#: bs+1, the product, <= best+eps, cumsum, ==, and, the one-hot update
ROUTE_OPS_PER_PAIR = 14


def route_wave_cost(n: int, k: int) -> dict:
    """A wave of ``k`` (padded) requests over ``n`` instances: the inputs
    read once (four int64 indicator columns, the (k, n) depth matrix,
    the (k, k+2) packed lcp/plen/tie block) and the outputs written once
    (assignments and hits), and ``ROUTE_OPS_PER_PAIR`` per pair."""
    i64 = 8
    read = i64 * (4 * n + k * n + k * (k + 2))
    write = i64 * 2 * k
    return {"flops": float(ROUTE_OPS_PER_PAIR * n * k),
            "bytes": float(read + write)}


# ---------------------------------------------------------------------------
# a dense GQA decoder (Qwen3) served in bf16
# ---------------------------------------------------------------------------
def decoder_sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    L = cfg["num_hidden_layers"]
    H = cfg["num_attention_heads"]
    KV = cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    ff = cfg["intermediate_size"]
    V = cfg["vocab_size"]
    per_layer = d * (H * hd) + 2 * d * (KV * hd) + (H * hd) * d + 3 * d * ff
    norms = L * (2 * d + 2 * hd) + d
    embed = V * d
    head = 0 if cfg["tie_word_embeddings"] else V * d
    return {"layer_params": L * per_layer, "embed_params": embed,
            "params": L * per_layer + embed + head + norms,
            "kv_bytes_per_token": L * 2 * KV * hd * 2,
            "L": L, "H": H, "hd": hd, "d": d, "V": V}


def token_flops(cfg: dict, context: int, logits: bool) -> float:
    """Model FLOPs of one token attending over ``context`` positions
    (itself included): the layers' matmuls, attention's two products,
    and the vocabulary projection where the token's logits are needed."""
    s = decoder_sizes(cfg)
    f = 2.0 * s["layer_params"] + 4.0 * s["L"] * s["H"] * s["hd"] * context
    if logits:
        f += 2.0 * s["V"] * s["d"]
    return f


def chunk_flops(cfg: dict, start: int, n: int) -> float:
    """A prefill chunk of ``n`` tokens at positions ``start..start+n-1``;
    logits for its last token only."""
    s = decoder_sizes(cfg)
    ctx_sum = n * start + n * (n + 1) / 2
    return (2.0 * s["layer_params"] * n
            + 4.0 * s["L"] * s["H"] * s["hd"] * ctx_sum
            + 2.0 * s["V"] * s["d"])


def decode_step_bytes(cfg: dict, contexts) -> float:
    """Least HBM traffic of one batched decode step: every weight once
    (the tied embedding is the vocabulary projection), the cache of each
    running sequence's context, and each new token's keys and values."""
    s = decoder_sizes(cfg)
    kv = s["kv_bytes_per_token"]
    return 2.0 * s["params"] + kv * float(sum(contexts)) + kv * len(contexts)
