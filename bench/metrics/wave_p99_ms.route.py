"""p99 of the wall time of each ``route_batch`` call in the window (every
wave), in ms: the wave tail, set by the waves whose inserts evict and
fall back to sequential routes."""
from common import pct


def read(ctx):
    walls = ctx.get("walls")
    return pct(walls, 99) * 1e3 if walls else None
