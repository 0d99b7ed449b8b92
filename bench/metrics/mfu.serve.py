"""Model FLOPs of every prefill and decode token the engines computed in
the window (``flops.chunk_flops``, ``flops.token_flops``) over the
window's wall time times the chip's bf16 peak, in percent."""
from flops import chunk_flops, token_flops


def read(ctx):
    steps = ctx.get("steps")
    if not steps or ctx["window_s"] <= 0:
        return None
    cfg = ctx["config"]
    total = 0.0
    for s in steps:
        if s["kind"] == "prefill":
            total += chunk_flops(cfg, s["start"], s["tokens"])
        elif s["kind"] == "decode":
            total += sum(token_flops(cfg, c + 1, True) for c in s["cached"])
    return 100.0 * total / (ctx["window_s"] * ctx["peaks"]["bf16_flops_per_s"])
