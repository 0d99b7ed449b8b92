"""Counts of the MoE cell's operations and bytes: the held share's
parameters are the program model's, and the decode step's bytes and
operations follow the touched experts and the routed-local choices."""
import jax
import pytest

import flops_moe
import serve_moe
from common import config_for, find, load_spec


@pytest.fixture(scope="module")
def cfg():
    spec = load_spec()
    return config_for(spec, find(spec["workloads"], "serve.a3b-x4.chat")
                      ["config"])


def test_held_share_params_equal_the_program_model(cfg):
    from repro.models import Model
    model = Model(serve_moe.model_config(cfg))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert flops_moe.moe_sizes(cfg)["params"] == n \
        == model.cfg.param_count() + model.cfg.n_layers * 2 * (
            cfg["hidden_size"] + cfg["head_dim"]) + cfg["hidden_size"]
    # 10.36e9 B in bf16 (the router's 48 x 2,048 x 128 in float32)
    s = flops_moe.moe_sizes(cfg)
    weight_bytes = 2 * n + 2 * s["router_params"]
    assert 10.3e9 < weight_bytes < 10.4e9


def test_decode_counts_follow_the_routing(cfg):
    s = flops_moe.moe_sizes(cfg)
    assert s["kv_bytes_per_token"] == 98304
    assert s["expert_params"] * 2 == 9437184           # 9.44 MB per expert
    base = flops_moe.decode_step_bytes(cfg, [100] * 8, 0)
    six = flops_moe.decode_step_bytes(cfg, [100] * 8, 48 * 6)
    assert six - base == 48 * 6 * 9437184
    all16 = flops_moe.decode_step_bytes(cfg, [800] * 8, 48 * 16)
    assert 10.0e9 < all16 < 10.8e9                      # every held expert
    f0 = flops_moe.decode_step_flops(cfg, [100] * 8, 0)
    f64 = flops_moe.decode_step_flops(cfg, [100] * 8, 64)
    assert f64 - f0 == 64 * 2 * 3 * 2048 * 768
    assert flops_moe.chunk_flops(cfg, 0, 1) == pytest.approx(
        flops_moe.token_flops(cfg, 1, True))
