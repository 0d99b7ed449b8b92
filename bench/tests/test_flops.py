"""The operation and byte counts behind the roofline and MFU metrics."""
import jax

import flops
from common import config_for, load_spec


def _qwen3():
    return config_for(load_spec(), "qwen3_4b-x4")


def test_param_count_matches_the_served_model():
    from repro.models import Model
    import serve
    cfg = _qwen3()
    model = Model(serve.model_config(cfg))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert flops.decoder_sizes(cfg)["params"] == n
    assert 4.0e9 < n < 4.1e9                  # Qwen3-4B: 4.02e9 with embedding


def test_kv_bytes_per_token():
    assert flops.decoder_sizes(_qwen3())["kv_bytes_per_token"] == 147456


def test_chunk_flops_is_the_sum_of_its_tokens():
    cfg = _qwen3()
    s = flops.decoder_sizes(cfg)
    per_tok = sum(flops.token_flops(cfg, 100 + i + 1, False)
                  for i in range(64))
    assert abs(flops.chunk_flops(cfg, 100, 64)
               - (per_tok + 2.0 * s["V"] * s["d"])) < 1.0


def test_decode_bytes():
    cfg = _qwen3()
    s = flops.decoder_sizes(cfg)
    b = flops.decode_step_bytes(cfg, [10, 20])
    assert b == 2 * s["params"] + 147456 * 30 + 147456 * 2


def test_route_wave_cost():
    c = flops.route_wave_cost(4096, 64)
    assert c["bytes"] == 8 * (4 * 4096 + 64 * 4096 + 64 * 66) + 8 * 128
    assert c["flops"] == flops.ROUTE_OPS_PER_PAIR * 4096 * 64
