"""Model FLOPs of every prefill and decode token the engines computed while
the profiler traced (the steps marked ``traced``) over the traced
window's wall seconds times the chip's bf16 peak, in percent: attention,
router and head from the shapes (``flops_moe.chunk_flops``,
``flops_moe.token_flops``), expert work from each step's routed-local
choices (``engine.moe_routed_local``, ``flops_moe.expert_flops``).

The traced window, not the whole one: the profiler's trace is written
when it stops, inside the measured window, and at this model's depth that
takes longer than the rest of the window, in which hardly a step runs.
Needs the steps' MoE counter deltas (``serve_moe`` with ``--trace 1``)."""
from flops_moe import chunk_flops, decode_step_flops, expert_flops


def read(ctx):
    steps = [s for s in ctx.get("steps", ())
             if s["kind"] != "idle" and s["traced"]]
    window_s = ctx["trace"]["window_s"]
    if not steps or window_s <= 0 or not all("moe" in s for s in steps):
        return None
    cfg = ctx["config"]
    total = 0.0
    for s in steps:
        if s["kind"] == "prefill":
            total += (chunk_flops(cfg, s["start"], s["tokens"])
                      + expert_flops(cfg, s["moe"]["routed"]))
        else:
            total += decode_step_flops(cfg, s["cached"], s["moe"]["routed"])
    return 100.0 * total / (window_s * ctx["peaks"]["bf16_flops_per_s"])
