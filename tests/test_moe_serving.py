"""The served MoE path against the plain reference
(``bench/reference/qwen3_moe_ref.py``, which imports nothing of the
program), on the CPU at small sizes: the dropless expert layer over a
share of the experts, the shares adding up to the whole layer, an
imbalanced batch that the training dispatch drops and the dropless layer
does not, a tiny Qwen3-MoE served through ``EngineCluster``, and the
expert layer's inputs and outputs that the served programs tap."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import make_policy
from repro.models import Model
from repro.models.config import ModelConfig, dense_pattern
from repro.models.layers import init_moe, moe_apply, moe_dropless
from repro.models.model import tap_rows
from repro.serving.engine import EngineCluster, decode_step, prefill_slot

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                     "bench")
if BENCH not in sys.path:
    sys.path.append(BENCH)

from reference import qwen3_moe_ref  # noqa: E402

D, E, K, F = 64, 16, 4, 32


def _cfg(**kw):
    base = dict(name="moe-test", arch_type="moe", n_layers=1, d_model=D,
                n_heads=4, n_kv_heads=2, d_ff=F, vocab_size=128,
                block_pattern=dense_pattern(1), n_experts=E, top_k=K,
                moe_d_ff=F, dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def _share(p, first, held):
    return dict(p, **{k: {"w": p[k]["w"][first:first + held]}
                      for k in ("wi", "wg", "wo")})


@pytest.fixture(scope="module")
def layer():
    """One float32 expert layer holding all 16 experts, and a batch."""
    p = init_moe(jax.random.key(0), _cfg(), jnp.float32)
    x = jax.random.normal(jax.random.key(1), (3, 7, D), jnp.float32)
    return p, x


def _ref(p, x, first, held, top_k=K):
    y, _ = qwen3_moe_ref.moe_ffn(x.reshape(-1, D), p, (top_k, first, held))
    return np.asarray(y).reshape(x.shape)


def test_dropless_share_equals_reference(layer):
    """4 experts held from expert 4: the layer equals the reference given
    the same share (float32 both sides: only summation order differs)."""
    p, x = layer
    cfg = _cfg(expert_first=4, experts_held=4)
    y, counts = jax.jit(lambda p, x: moe_dropless(p, x, cfg))(
        _share(p, 4, 4), x)
    np.testing.assert_allclose(np.asarray(y), _ref(_share(p, 4, 4), x, 4, 4),
                               rtol=1e-4, atol=1e-5)
    # counts: every choice of experts 4..7, counted on the host
    _, topi = jax.lax.top_k(jax.nn.softmax(
        x.reshape(-1, D) @ p["router"]["w"], -1), K)
    want = [(np.asarray(topi) == e).sum() for e in range(4, 8)]
    assert np.asarray(counts).tolist() == want


def test_shares_add_up_to_the_whole_layer(layer):
    """The four shares' outputs add up to the uncut reference layer."""
    p, x = layer
    total = 0
    for first in range(0, E, 4):
        cfg = _cfg(expert_first=first, experts_held=4)
        y, _ = jax.jit(lambda p, x, cfg=cfg: moe_dropless(p, x, cfg))(
            _share(p, first, 4), x)
        total = total + np.asarray(y)
    np.testing.assert_allclose(total, _ref(p, x, 0, E), rtol=1e-4,
                               atol=1e-5)


def test_imbalanced_batch_drops_nothing(layer):
    """Every token of a row routes to the same four experts: the capacity
    dispatch (ceil(32 * 4 / 16 * 1.25) = 10 slots per expert) drops 22 of
    each expert's 32 tokens, the dropless layer none."""
    p, _ = layer
    g = np.random.default_rng(2)
    u = g.normal(size=D) / np.sqrt(D)
    w = g.normal(size=(D, E)) * 0.01
    w[:, :K] += u[:, None] * np.arange(K, 0, -1)        # experts 0..3 win
    p = dict(p, router={"w": jnp.asarray(w, jnp.float32)})
    x = jnp.asarray(u * 8 + g.normal(size=(1, 32, D)) * 0.01, jnp.float32)
    cfg = _cfg()
    want = _ref(p, x, 0, E)
    y, counts = moe_dropless(p, x, cfg)
    assert np.asarray(counts)[:K].tolist() == [32] * K
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
    dropped, _ = moe_apply(p, x, cfg)
    assert np.abs(np.asarray(dropped) - want).max() > 0.1 * np.abs(want).max()


@pytest.mark.parametrize("T,touched", [(600, (0, 2, 3)), (8, (1,)),
                                       (8, ())])
def test_kernel_reads_the_touched_experts_in_place(T, touched):
    """The grouped kernel (interpret mode) over layer 1 of a two-layer
    stack of 4 experts: 600 tokens take two token tiles, F = 512 two FFN
    tiles, and the experts no token picked, or none at all, add nothing.
    float32 both sides: only summation order differs."""
    from repro.kernels.moe_experts import held_experts
    g = np.random.default_rng(T + len(touched))
    d, f, held = 128, 512, 4
    x = g.normal(size=(T, d)).astype(np.float32)
    wi, wg = (g.normal(size=(2 * held, d, f)).astype(np.float32) / 11
              for _ in range(2))
    wo = g.normal(size=(2 * held, f, d)).astype(np.float32) / 23
    comb = np.zeros((T, held), np.float32)
    for e in touched:
        comb[:, e] = g.uniform(size=T) * (g.uniform(size=T) < 0.5)
    counts = (comb > 0).sum(0).astype(np.int32)
    y = held_experts(jnp.asarray(x), jnp.asarray(comb), jnp.asarray(counts),
                     jnp.asarray(wi), jnp.asarray(wg), jnp.asarray(wo),
                     jnp.asarray(held, jnp.int32), act="swiglu",
                     interpret=True)
    want = np.zeros((T, d), np.float32)
    for e in range(held):
        u, gate = x @ wi[held + e], x @ wg[held + e]
        want += comb[:, e:e + 1] * ((gate / (1 + np.exp(-gate)) * u)
                                    @ wo[held + e])
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-4)


def _hf(cfg: ModelConfig, first: int, held: int) -> dict:
    """The reference's view of a program config (Hugging Face keys)."""
    return {"num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "head_dim": cfg.resolved_head_dim, "rms_norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta, "num_experts_per_tok": cfg.top_k,
            "num_experts": cfg.n_experts, "first_expert_held": first,
            "n_experts_held": held, "vocab_size": cfg.vocab_size,
            "num_hidden_layers": cfg.n_layers}


def _tiny(dtype, first=4, held=4):
    return _cfg(name="qwen3-moe-tiny", n_layers=2,
                block_pattern=dense_pattern(2), head_dim=16, qk_norm=True,
                rope_theta=1e6, vocab_size=256, expert_first=first,
                experts_held=held, dtype=dtype)


def test_prefill_then_decode_matches_reference():
    """float32 program: a 12-token prefill through ``prefill_cached``,
    then 6 decode steps through the cache, equal the reference's full
    forward pass at every position (only summation order differs, and the
    routing is the same)."""
    cfg = _tiny("float32")
    model = Model(cfg)
    params = model.init(jax.random.key(3))
    toks = np.random.default_rng(4).integers(0, cfg.vocab_size, 18)
    cache = model.init_cache(1, 32)
    logits, cache = jax.jit(model.prefill_cached)(
        params, jnp.asarray(toks[None, :12]),
        jnp.arange(12, dtype=jnp.int32)[None], cache,
        jnp.zeros((1,), jnp.int32))
    got = [np.asarray(logits[0, -1])]
    assert cache["moe_counts"].shape == (2, 4)
    step = jax.jit(model.decode_step)
    for i in range(12, 17):
        lg, cache = step(params, jnp.asarray(toks[None, i:i + 1]),
                         jnp.asarray([i], jnp.int32), cache)
        got.append(np.asarray(lg[0, -1]))
    ref, _ = qwen3_moe_ref.forward(params, _hf(cfg, 4, 4), toks[:17], 17)
    want = np.asarray(ref)[11:17]
    np.testing.assert_allclose(np.stack(got), want, rtol=1e-3, atol=1e-4)


def test_cluster_serves_tokens_the_reference_ranks_first():
    """bf16 program through ``EngineCluster`` and the lmetric router:
    each served token's reference logit lies within 0.05 of the
    reference's best (bf16 activations round at 2^-8 relative; the
    tiny model's logits span ~1, and its fp8 and top-3 variants read
    gaps of 0.2 and more)."""
    cfg = _tiny("bfloat16")
    model = Model(cfg)
    params = model.init(jax.random.key(5))
    g = np.random.default_rng(6)
    head = g.integers(0, cfg.vocab_size, 32)
    arrivals = [(0.01 * i, np.concatenate(
        [head, g.integers(0, cfg.vocab_size, 16)]), 8) for i in range(6)]
    cluster = EngineCluster(2, model, params, make_policy("lmetric"),
                            block_size=16, max_batch=2, max_len=64,
                            chunk_tokens=64, prefix_capacity=4)
    done = cluster.run(arrivals)
    assert len(done) == 6
    hf = _hf(cfg, 4, 4)
    for req in done:
        seq = cluster.served[req.rid]
        out = np.asarray(seq.out_tokens)
        assert len(out) == 8
        gap = qwen3_moe_ref.served_gap(params, hf, seq.tokens, out, 64)
        assert gap["gap"] < 0.05, gap


def test_served_programs_tap_their_expert_layer():
    """The engine's two programs leave each MoE layer's input and output
    at the tapped rows in the cache: every slot of a decode step, a
    prefill chunk's rows from its first to its last; each tapped output is
    the layer's output on the tapped input, and the reference's too."""
    assert tap_rows(3, 3) is None
    assert list(tap_rows(12, 3)) == [0, 5, 11]
    assert list(tap_rows(12, 1)) == [11]
    cfg = _tiny("float32")
    model = Model(cfg)
    params = model.init(jax.random.key(7))
    toks = np.random.default_rng(8).integers(0, cfg.vocab_size, 12)
    cache = model.init_cache(3, 32)
    ffn = params["units"][0]["ffn"]
    hf = _hf(cfg, 4, 4)

    def check(cache, rows):
        tap = cache["moe_tap"]
        assert tap["h"].shape == (2, rows, cfg.d_model)
        for layer in range(2):
            p = jax.tree.map(lambda a: a[layer], ffn)
            want, _ = moe_dropless(p, tap["h"][layer], cfg)
            np.testing.assert_allclose(tap["y"][layer], want, rtol=1e-5,
                                       atol=1e-6)
        err = qwen3_moe_ref.expert_err(params, hf, tap["h"], tap["y"])
        assert np.nanmax(err) < 1e-4, err

    _, cache = prefill_slot(model, params, cache,
                            jnp.asarray(toks[None]),
                            jnp.arange(12, dtype=jnp.int32)[None],
                            jnp.asarray(0, jnp.int32), 1)
    check(cache, 3)
    # the last tapped row of the prefill is its last token's
    _, last = prefill_slot(model, params, model.init_cache(1, 32),
                           jnp.asarray(toks[None]),
                           jnp.arange(12, dtype=jnp.int32)[None],
                           jnp.asarray(0, jnp.int32), 0)
    np.testing.assert_allclose(cache["moe_tap"]["h"][:, 2],
                               last["moe_tap"]["h"][:, 0], rtol=1e-6)
    _, cache = decode_step(model, params, cache,
                           jnp.asarray([[1], [2], [3]], jnp.int32),
                           jnp.asarray([0, 12, 0], jnp.int32))
    check(cache, 3)


def test_dense_program_has_no_counts():
    """A dense model's engine cache and decode outputs are as before: no
    ``moe_counts``, so its programs are unchanged."""
    model = Model(get_config("qwen3_4b-smoke"))
    cache = jax.eval_shape(lambda: model.init_cache(2, 16))
    assert set(cache) == {"units", "rest", "enc_out"}
    params = model.abstract_params()
    _, out = jax.eval_shape(model.decode_step, params,
                            jax.ShapeDtypeStruct((2, 1), jnp.int32),
                            jax.ShapeDtypeStruct((2,), jnp.int32), cache)
    assert set(out) == {"units", "rest", "enc_out"}


def test_bench_config_is_the_repo_model_with_all_experts():
    """The benchmark's Qwen3-30B-A3B file, read by its driver with the
    share set to all 128 experts, is ``get_config("qwen3_30b_moe")`` in
    every shape field."""
    import serve_moe
    from common import load_json
    path = os.path.join(BENCH, "configs", "qwen3_30b_a3b-x4.json")
    bench = serve_moe.model_config(dict(load_json(path),
                                        n_experts_held=128,
                                        first_expert_held=0))
    repo = get_config("qwen3_30b_moe")
    for f in ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
              "vocab_size", "block_pattern", "resolved_head_dim", "ffn_act",
              "n_experts", "top_k", "resolved_moe_d_ff",
              "dense_residual_d_ff", "qk_norm", "rope_theta", "use_rope",
              "norm_type", "norm_eps", "tie_embeddings", "scale_embed",
              "expert_first", "resolved_experts_held", "dtype"):
        assert getattr(bench, f) == getattr(repo, f), f
    assert repo.source == bench.source
    assert bench.param_count() == repo.param_count()


def test_share_fields_are_checked():
    with pytest.raises(ValueError):
        _cfg(expert_first=14, experts_held=4)
    held = _cfg(expert_first=4, experts_held=4)
    whole = _cfg()
    assert whole.param_count() - held.param_count() == 12 * 3 * D * F
    assert dataclasses.replace(held, expert_first=0, experts_held=0) \
        .resolved_experts_held == E
