"""The MoE served cell end to end on the CPU with a tiny Qwen3-MoE that
holds a share of its experts: the harness finds it correct, its traced
run reads the MoE metrics, and a token altered where it is produced, a
program that routes to one expert fewer, each broken expert layer of
``serve_moe.CONTROLS`` and a fault confined to one layer's weights or
one slot's rows in the served programs are caught; the engines' MoE
counters leave the tokens as they are and equal a recount of the counts
the programs return."""
import numpy as np
import pytest

import serve
import serve_moe
from common import config_for, find, load_spec, traffic_for
from small import env, run


def moe_cell():
    spec = load_spec()
    cell = find(spec["workloads"], "serve.a3b-x4.chat")
    config = dict(config_for(spec, cell["config"]), hidden_size=64,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16,
                  moe_intermediate_size=32, num_experts=16,
                  num_experts_per_tok=4, n_experts_held=4,
                  first_expert_held=4, vocab_size=512,
                  initializer_range=0.2)
    config["engine"] = dict(config["engine"], max_len=256, max_batch=4)
    mix = dict(traffic_for(cell["traffic"]), prefix_tokens=64,
               fresh_tokens_mean=64, output_tokens_mean=12,
               max_total_tokens=255, rate=400.0, warm_requests=8,
               check_tokens=40, trace_seconds=0.5)
    # the tiny model's program gaps read 0-0.06 on the CPU and its fp8
    # and top-3 controls 0.36-4.2 (seeds 1-3): 0.1 lies between.  On the
    # tapped (row, layer) pairs the program's expert layer errs by a
    # median 0.0022-0.0023 (bf16 rounding) and none by over half, its
    # fp8-expert and top-3 controls by 0.043-0.047 and 0.16-0.17 (seeds
    # 1-4): 0.02 lies between; top-3 and the capacity control err by over
    # half at 20-23% and 10-21% of the pairs (seeds 1-3): 0.01 lies below
    return spec, cell, config, dict(mix, gap_limit=0.1, expert_limit=0.02,
                                    lost_limit=0.01)


@pytest.fixture(scope="module")
def cell():
    return moe_cell()


def test_small_run_is_correct(cell):
    out = run(*cell, seconds=1.5)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert set(out["metrics"]) == {"output_tokens_per_s", "setup_s"}


def test_traced_run_reads_the_moe_metrics(cell):
    out = run(*cell, seconds=1.5, trace=True)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert out["correct"], out["checks"]
    assert 0 < m["experts_touched.a3b"] <= 100
    assert m["expert_skew.a3b"] >= 1
    assert m["mfu.a3b"] > 0
    # the dense cell's metrics are not this cell's
    assert "mfu.serve" not in m and "decode_roofline" not in m


def test_altered_token_is_caught(cell, monkeypatch):
    from repro.serving import engine
    orig = engine.decode_step

    def broken(model, params, cache, tokens, pos):
        nxt, cache = orig(model, params, cache, tokens, pos)
        return (nxt + 1) % model.cfg.vocab_size, cache

    monkeypatch.setattr(engine, "decode_step", broken)
    out = run(*cell, seconds=1.5)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > 0.1


def test_one_expert_fewer_is_caught(cell):
    """The program routes each token to top_k - 1 experts (here 3 of 16;
    top-7 of 128 at full size) while the reference keeps top_k."""
    _, _, config, mix = cell
    checks = serve_moe.run(config, mix, 7, 1.5, env(),
                           control="top7")["checks"]
    for name in ("logit_gap", "expert_err", "expert_lost"):
        value, limit = checks[name]
        assert value > limit, name


def test_controls_read_wider_gaps(cell):
    """Each broken expert layer in the program's place fails the check on
    the tapped pairs: fp8 expert weights by the median error, the
    capacity dispatch of a prompt by the pairs that lost their choice;
    the program's expert layer errs by bf16 rounding alone, well under
    its limits."""
    _, _, config, mix = cell
    res = serve_moe.run(config, mix, 3, 1.5, env())
    err, limit = res["checks"]["expert_err"]
    assert 0 < err < limit / 2
    assert res["checks"]["expert_lost"][0] == 0
    assert 0 <= res["near_ties"] < 0.1
    fails = {"fp8_experts": "expert_err", "capacity": "expert_lost"}
    for control, check in fails.items():
        checks = serve_moe.run(config, mix, 3, 1.5, env(),
                               control=control)["checks"]
        value, limit = checks[check]
        assert value > limit, control


@pytest.mark.parametrize("fault", ["next_layer_experts", "one_slot"])
def test_expert_fault_in_served_programs_is_caught(cell, monkeypatch,
                                                   fault):
    """A fault that the served programs alone have: each layer reads the
    next layer's experts (the scan's layer index off by one), or the
    decode step drops its last slot's expert output."""
    from repro.models import Model, layers
    if fault == "next_layer_experts":
        orig = Model._unit_params
        n = cell[2]["num_hidden_layers"]          # one layer per unit

        def shifted(xs, stacks):
            if stacks is not None:
                xs = dict(xs, layer=(xs["layer"] + 1) % n)
            return orig(xs, stacks)

        monkeypatch.setattr(Model, "_unit_params", staticmethod(shifted))
    else:
        orig = layers.held_experts_op
        slots = cell[2]["engine"]["max_batch"]

        def one_slot(x, *a, **kw):
            y = orig(x, *a, **kw)
            return y.at[-1].set(0) if x.shape[0] == slots else y

        monkeypatch.setattr(layers, "held_experts_op", one_slot)
    out = run(*cell, seconds=1.5)
    assert not out["correct"]
    assert (out["checks"]["expert_lost"]["value"]
            > out["checks"]["expert_lost"]["limit"])


def test_counters_leave_tokens_alone_and_match_a_recount(cell, monkeypatch):
    """The same arrivals served with and without the MoE counters give the
    same tokens, and the counters equal a host recount of the
    ``moe_counts`` each program call returned."""
    import repro.obs
    from repro.core import make_policy
    from repro.models import Model
    from repro.serving import engine
    from generate import served_arrivals
    _, _, config, mix = cell
    model = Model(serve_moe.model_config(config))
    params = serve.make_weights(model, 5, config["initializer_range"])
    arrivals = served_arrivals(mix, 5, 12, config["vocab_size"])

    def served(obs):
        cluster = engine.EngineCluster(
            config["engines"], model, params, make_policy(config["policy"]),
            obs=obs, devices=None, **config["engine"])
        cluster.run(arrivals)
        return {rid: list(s.out_tokens) for rid, s in cluster.served.items()}

    plain = served(None)
    seen = {"prefill_slot": [], "decode_step": []}
    for name in seen:
        def wrapped(*a, _orig=getattr(engine, name), _name=name):
            out, cache = _orig(*a)
            seen[_name].append(np.asarray(cache["moe_counts"]))
            return out, cache
        monkeypatch.setattr(engine, name, wrapped)
    obs = repro.obs.make_obs(metrics=True)
    assert served(obs) == plain
    # each engine's ``warmup`` calls both programs once, outside its steps
    n = config["engines"]
    pre, dec = seen["prefill_slot"][n:], seen["decode_step"][n:]
    c = obs.registry.counters
    assert c["engine.moe_layer_steps"] == len(dec) * model.moe_layers > 0
    assert c["engine.moe_experts_touched"] == sum(int((x > 0).sum())
                                                  for x in dec)
    assert c["engine.moe_max_expert_tokens"] == sum(
        int(x.max(axis=1).sum()) for x in dec)
    assert c["engine.moe_routed_local"] == sum(int(x.sum())
                                               for x in pre + dec)
