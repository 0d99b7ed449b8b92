"""Share of the roofline reached by the engines' decode program of an
MoE model (the jitted ``serving.engine.decode_step``, whose expert layer
is ``layers.moe_dropless``) in the traced window: for each traced decode
step the least time it allows (the larger of its bytes,
``flops_moe.decode_step_bytes`` with the step's touched held experts,
over HBM bandwidth and its operations, ``flops_moe.decode_step_flops``
with the step's routed-local choices, over peak), averaged, over the
program's mean device time per call in the trace, in percent.  Needs the
steps' MoE counter deltas (``serve_moe`` with ``--trace 1``)."""
from devtrace import module_seconds
from flops_moe import decode_step_bytes, decode_step_flops


def read(ctx):
    secs, calls = module_seconds(ctx["trace"], "decode_step")
    steps = [s for s in ctx.get("steps", ())
             if s["kind"] == "decode" and s["traced"] and "moe" in s]
    if not calls or secs <= 0 or not steps:
        return None
    cfg, pk = ctx["config"], ctx["peaks"]
    bound = 0.0
    for s in steps:
        m = s["moe"]
        bound += max(decode_step_bytes(cfg, s["cached"], m["touched"])
                     / pk["hbm_bytes_per_s"],
                     decode_step_flops(cfg, s["cached"], m["routed"])
                     / pk["bf16_flops_per_s"])
    # per call: the traced steps' mean bound over the program's mean time
    return 100.0 * (bound / len(steps)) / (secs / calls)
