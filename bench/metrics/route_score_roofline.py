"""Share of the roofline reached by the router's wave program
(``kernels/route_score.py``, the jitted ``_route_wave``) in the traced
window: the least time its calls allow (the larger of bytes over HBM
bandwidth and operations over peak, from ``flops.route_wave_cost``) over
their device time, in percent."""
from devtrace import module_seconds
from flops import route_wave_cost


def read(ctx):
    secs, calls = module_seconds(ctx["trace"], "route_wave")
    if not calls or secs <= 0:
        return None
    kp = 1
    while kp < ctx["wave"]:
        kp *= 2
    cost = route_wave_cost(ctx["n"], kp)
    pk = ctx["peaks"]
    bound = max(cost["bytes"] / pk["hbm_bytes_per_s"],
                cost["flops"] / pk["bf16_flops_per_s"])
    return 100.0 * calls * bound / secs
