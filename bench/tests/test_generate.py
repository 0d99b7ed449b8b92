"""The traffic generator: a seed fixes the inputs, and every seed gets the
same set of sizes."""
from common import traffic_for
from generate import ServiceModel, route_trace, served_arrivals


def _sizes(reqs):
    return sorted((r.prompt_len, r.output_len) for r in reqs)


def test_route_trace_same_seed_same_inputs():
    mix = traffic_for("coder")
    a, b = route_trace(mix, 3, 500), route_trace(mix, 3, 500)
    assert [(r.arrival, r.blocks, r.output_len) for r in a] == \
        [(r.arrival, r.blocks, r.output_len) for r in b]


def test_route_trace_seeds_permute_sessions():
    mix = traffic_for("coder")
    a, b = route_trace(mix, 3, 3000), route_trace(mix, 2**31 + 9, 3000)
    assert [r.blocks for r in a] != [r.blocks for r in b]
    # whole sessions move, so the first 3000 arrivals hold nearly the
    # same work under either seed
    ta = sum(r.prompt_len for r in a)
    tb = sum(r.prompt_len for r in b)
    assert abs(ta - tb) / ta < 0.1
    assert all(r.prompt_len == len(r.blocks) * 64 for r in a)
    assert a == sorted(a, key=lambda r: r.arrival)


def test_route_trace_is_stationary_from_its_start():
    """Sessions begun before the trace contribute their later turns, so
    the first and the second half of the arrivals come at one rate."""
    mix = traffic_for("coder")
    reqs = route_trace(mix, 3, 20000)
    t = [r.arrival for r in reqs]
    first, second = t[10000] - t[0], t[-1] - t[10000]
    assert abs(first - second) / second < 0.1
    assert abs(10000 / first - mix["rate"]) / mix["rate"] < 0.15


def test_unshared_prompts_share_nothing():
    """A mix of single-turn sessions with no app prefix (the generator's
    shape for a cell where the walk stops at depth 0)."""
    mix = dict(traffic_for("coder"), n_apps=1, zipf_a=1.0,
               app_prefix_blocks=0, turns_mean=1.0, first_input_blocks=14)
    reqs = route_trace(mix, 5, 2000)
    firsts = [r.blocks[0] for r in reqs]
    assert len(set(firsts)) == len(firsts)


def test_served_arrivals_same_sizes_for_every_seed():
    mix = traffic_for("agent")
    a = served_arrivals(mix, 1, 160, 151936)
    b = served_arrivals(mix, -5, 160, 151936)
    assert [t for t, _, _ in a] == [t for t, _, _ in b]
    assert sorted((len(p), o) for _, p, o in a) == \
        sorted((len(p), o) for _, p, o in b)
    for _, p, o in a:
        assert len(p) % 64 == 0 and len(p) + o <= mix["max_total_tokens"]
    heads = {tuple(p[:640]) for _, p, _ in a}
    assert len(heads) <= mix["n_apps"] < len(a)


def test_served_seeds_reorder_only_within_blocks():
    """Every block of ``order_block`` consecutive arrivals holds the same
    sizes under every seed, so a window that serves only the earliest
    arrivals does the same work whatever the seed."""
    mix = traffic_for("agent")
    b = mix["order_block"]
    a = served_arrivals(mix, 1, 163, 151936)
    c = served_arrivals(mix, 2**31 + 77, 163, 151936)
    assert [(len(p), o) for _, p, o in a] != [(len(p), o) for _, p, o in c]
    for lo in range(0, 163, b):
        assert sorted((len(p), o) for _, p, o in a[lo:lo + b]) == \
            sorted((len(p), o) for _, p, o in c[lo:lo + b])


def test_served_unshared_has_no_common_prefix():
    mix = dict(traffic_for("agent"), prefix_shared=False)
    a = served_arrivals(mix, 1, 160, 151936)
    heads = {tuple(p[:16]) for _, p, _ in a}
    assert len(heads) == len(a)


def test_service_model_orders_events():
    svc = ServiceModel({"service": {"prefill_tokens_per_s": 1000.0,
                                    "tpot_s": 0.01}}, 2)
    svc.push(0.0, 0, 0, 500, 10)      # first at 0.5, finish at 0.6
    svc.push(0.0, 0, 1, 500, 1)       # queued behind: first 1.0, finish 1.01
    assert list(svc.due(0.55)) == [("first", 0, 0)]
    assert list(svc.due(2.0)) == [("finish", 0, 0), ("first", 0, 1),
                                  ("finish", 0, 1)]
