"""The served cell end to end on the CPU with a tiny decoder of the same
architecture: the harness finds it correct, the fp8 control reads a wider
gap, and a token altered where it is produced is caught."""
import numpy as np
import pytest

import serve
from common import config_for, find, load_spec
from reference import qwen3_ref
from small import env, run, serve_cell


@pytest.fixture(scope="module")
def cell():
    spec, c, config, mix = serve_cell()
    # the tiny model's program gaps read 0-0.03 and its fp8 control
    # 0.23-0.70 on the CPU: 0.1 lies between
    return spec, c, config, dict(mix, gap_limit=0.1)


def test_small_run_is_correct(cell):
    out = run(*cell, seconds=2.0)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert set(out["metrics"]) == {"output_tokens_per_s", "setup_s"}


def test_traced_run_reads_the_step_metrics(cell):
    out = run(*cell, seconds=2.0, trace=True)
    m = out["metrics"]
    assert m["prefill_ms.serve"]["value"] > 0
    assert m["decode_ms.serve"]["value"] > 0
    assert 0 < m["hit_token_share.serve"]["value"] < 100
    assert m["mfu.serve"]["value"] > 0


def test_window_lasts_its_seconds(cell):
    """The window closes at the first step past ``seconds``, with the
    queues still full: the engines never wait for work."""
    _, _, config, mix = cell
    res = serve.run(config, mix, 4, 1.5, env())
    assert 1.5 <= res["ctx"]["window_s"] < 3.0
    assert res["e2e"]["output_tokens_per_s"] > 0
    assert "still queued" in res["info"]
    assert not any(s["kind"] == "idle" for s in res["ctx"]["steps"])


def test_prefill_steps_cover_cold_and_reused_prompts():
    """Each prompt is prefilled from 0 or from the end of its app prefix,
    in steps of at most ``chunk_tokens``; the cell's own configuration
    prefills every prompt in one step."""
    mix = {"prefix_tokens": 64, "prefix_shared": True}
    arrivals = [(0.0, np.zeros(n, np.int32), 3) for n in (128, 192, 192)]
    steps = serve.prefill_steps(arrivals, mix,
                                {"chunk_tokens": 64, "max_len": 256})
    assert steps == [(0, 64), (64, 64), (128, 64)]
    steps = serve.prefill_steps(arrivals, mix,
                                {"chunk_tokens": 256, "max_len": 256})
    assert steps == [(0, 128), (0, 192), (64, 64), (64, 128)]
    assert serve.prefill_steps(arrivals, dict(mix, prefix_shared=False),
                               {"chunk_tokens": 256, "max_len": 256}) == \
        [(0, 128), (0, 192)]
    spec = load_spec()
    engine = config_for(spec, find(spec["workloads"], "serve.x4.agent")
                        ["config"])["engine"]
    assert engine["chunk_tokens"] >= engine["max_len"]


def test_fp8_control_reads_a_wider_gap(cell):
    _, _, config, mix = cell
    res = serve.run(config, mix, 3, 2.0, env(), control="fp8")
    assert res["controls"]["fp8"] > res["checks"]["logit_gap"][0]


def test_altered_token_is_caught(cell, monkeypatch):
    from repro.serving import engine
    orig = engine.decode_step

    def broken(model, params, cache, tokens, pos):
        nxt, cache = orig(model, params, cache, tokens, pos)
        return (nxt + 1) % model.cfg.vocab_size, cache

    monkeypatch.setattr(engine, "decode_step", broken)
    out = run(*cell, seconds=2.0)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > 0.1


def test_reference_matches_the_program_prefill():
    """The plain forward and the program's one-shot prefill agree on a
    tiny model in float32 terms (bf16 weights, both read the same)."""
    import jax
    import jax.numpy as jnp
    from repro.models import Model
    _, _, config, _ = serve_cell()
    model = Model(serve.model_config(config))
    params = serve.make_weights(model, 5, config["initializer_range"])
    toks = np.arange(1, 40, dtype=np.int32) % config["vocab_size"]
    want, _ = jax.jit(model.prefill)(params, jnp.asarray(toks[None]))
    got = qwen3_ref.forward(params, config, toks)
    want = np.asarray(want[0], np.float32)
    got = np.asarray(got, np.float32)
    assert np.abs(got - want).max() < 0.05 * np.abs(got).max()
