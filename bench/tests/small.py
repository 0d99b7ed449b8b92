"""Small-size cells for the CPU rehearsals, built from the real files."""
import jax

import run as harness
from common import config_for, find, load_spec, traffic_for

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def route_cell(name="route.fleet4096.coder", n=128, warm=512):
    spec = load_spec()
    cell = find(spec["workloads"], name)
    config = dict(config_for(spec, cell["config"]), n_instances=n)
    mix = dict(traffic_for(cell["traffic"]), warm_requests=warm,
               window_max_requests_per_second=20000, check_samples=200,
               trace_seconds=0.5)
    return spec, cell, config, mix


def serve_cell():
    spec = load_spec()
    cell = find(spec["workloads"], "serve.x4.agent")
    config = dict(config_for(spec, cell["config"]), hidden_size=64,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, intermediate_size=128,
                  vocab_size=512, initializer_range=0.2)
    config["engine"] = dict(config["engine"], max_len=256, max_batch=4)
    mix = dict(traffic_for(cell["traffic"]), prefix_tokens=64,
               fresh_tokens_mean=64, output_tokens_mean=12,
               max_total_tokens=255, rate=400.0, warm_requests=8,
               check_tokens=40, trace_seconds=0.5)
    return spec, cell, config, mix


def run(spec, cell, config, mix, seed=7, seconds=1.0, trace=False):
    return harness.run_cell(spec, cell, config, mix, seed, seconds, trace,
                            jax.devices(), PEAKS)


def env():
    return harness.Env(jax.devices()[:1], None, harness.T_START)
