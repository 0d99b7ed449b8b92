"""Share of the roofline reached by the engines' decode program (the
jitted ``serving.engine.decode_step``) in the traced window: for each
traced decode step the least time it allows (the larger of its bytes,
``flops.decode_step_bytes``, over HBM bandwidth and its operations over
peak), averaged, over the program's mean device time per call in the
trace, in percent."""
from devtrace import module_seconds
from flops import decode_step_bytes, token_flops


def read(ctx):
    secs, calls = module_seconds(ctx["trace"], "decode_step")
    steps = [s for s in ctx.get("steps", ())
             if s["kind"] == "decode" and s["traced"]]
    if not calls or secs <= 0 or not steps:
        return None
    cfg, pk = ctx["config"], ctx["peaks"]
    bound = 0.0
    for s in steps:
        flops = sum(token_flops(cfg, c + 1, True) for c in s["cached"])
        bound += max(decode_step_bytes(cfg, s["cached"])
                     / pk["hbm_bytes_per_s"],
                     flops / pk["bf16_flops_per_s"])
    # per call: the traced steps' mean bound over the program's mean time
    return 100.0 * (bound / len(steps)) / (secs / calls)
