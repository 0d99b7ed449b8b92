"""Compile rehearsals for one TPU v5e chip, with no chip attached.

The TPU compiler is installed beside JAX, and it compiles for a chip
that is described rather than present.  These tests compile the served
path's device programs at their real sizes: the router's wave loop, a
full-width qwen3_4b engine's jitted ``decode``, ``prefill_slot`` and
``slot_fragment``, and
those of a Qwen3-30B-A3B engine holding one chip's share of the experts
(16 of 128 per layer, 8 slots of 1,152 positions).
A compile that passes says nothing about results or times; it says the
chip's compiler takes the program and that the program fits the chip's
16 GiB of HBM.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 2**30
ENGINE_BATCH, ENGINE_LEN, CHUNK = 4, 256, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _assert_fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < used <= HBM_BYTES, used


@pytest.mark.parametrize("n,k", [(16384, 64), (256, 1)])
@pytest.mark.parametrize("kind,params", [("lmetric", ("ptoken", "bs")),
                                         ("jsq", ())])
def test_wave_loop_compiles_for_v5e(one_chip, kind, params, n, k):
    from repro.kernels.route_score import _route_wave, x64
    with x64():
        def i64(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int64, sharding=one_chip)
        compiled = _route_wave.lower(
            kind, params, 64, i64(n), i64(n), i64(n), i64(n), i64(k, n),
            i64(k, k + 2)).compile()
    _assert_fits(compiled)


@pytest.fixture(scope="module")
def qwen3_4b(one_chip):
    """Full-width qwen3_4b: the model, and its params and engine cache
    as shapes on the described chip."""
    from repro.configs import get_config
    from repro.models import Model
    model = Model(get_config("qwen3_4b"))
    params = jax.eval_shape(model.init, jax.random.key(0))
    cache = jax.eval_shape(
        lambda: model.init_cache(ENGINE_BATCH, ENGINE_LEN))
    return model, _on(one_chip, params), _on(one_chip, cache)


def test_engine_decode_compiles_for_v5e(one_chip, qwen3_4b):
    from repro.serving.engine import decode_step
    model, params, cache = qwen3_4b
    compiled = decode_step.lower(
        model, params, cache,
        jax.ShapeDtypeStruct((ENGINE_BATCH, 1), jnp.int32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((ENGINE_BATCH,), jnp.int32,
                             sharding=one_chip)).compile()
    _assert_fits(compiled)


def test_engine_prefill_slot_compiles_for_v5e(one_chip, qwen3_4b):
    from repro.serving.engine import prefill_slot
    model, params, cache = qwen3_4b

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    compiled = prefill_slot.lower(model, params, cache, i32(1, CHUNK),
                                  i32(1, CHUNK), i32(), i32()).compile()
    _assert_fits(compiled)


def test_engine_slot_fragment_compiles_for_v5e(one_chip, qwen3_4b):
    """The save path's slice program, at the served cell's cache of 7
    slots of 1,152 positions, holds one slot of the cache and no more."""
    from repro.serving.engine import slot_fragment
    model = qwen3_4b[0]
    cache = _on(one_chip, jax.eval_shape(lambda: model.init_cache(7, 1152)))
    compiled = slot_fragment.lower(
        {"units": cache["units"], "rest": cache["rest"]},
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    m = compiled.memory_analysis()
    slot = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache)) // 7
    # the slot, and the output tuple's table of buffers
    assert slot <= m.output_size_in_bytes <= slot + 4096
    assert m.temp_size_in_bytes == 0


A3B_BATCH, A3B_LEN, A3B_PROMPT = 8, 1152, 1152


@pytest.fixture(scope="module")
def a3b(one_chip):
    """Qwen3-30B-A3B at full width and depth, experts 0-15 held."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import Model
    model = Model(dataclasses.replace(get_config("qwen3_30b_moe"),
                                      experts_held=16))
    params = jax.eval_shape(model.init, jax.random.key(0))
    cache = jax.eval_shape(lambda: model.init_cache(A3B_BATCH, A3B_LEN))
    return model, _on(one_chip, params), _on(one_chip, cache)


def test_a3b_engine_programs_compile_for_v5e(one_chip, a3b):
    """Both programs compile with the dropless expert layer, its grouped
    kernel lowered by Mosaic (one TPU custom call in each program's layer
    scan, not the interpret-mode fallback), and a whole-prompt prefill
    fits beside the weights (10.36e9 B)."""
    from repro.serving.engine import decode_step, prefill_slot
    model, params, cache = a3b

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    compiled = decode_step.lower(model, params, cache, i32(A3B_BATCH, 1),
                                 i32(A3B_BATCH)).compile()
    _assert_fits(compiled)
    assert compiled.as_text().count("tpu_custom_call") == 1
    compiled = prefill_slot.lower(model, params, cache, i32(1, A3B_PROMPT),
                                  i32(1, A3B_PROMPT), i32(), i32()).compile()
    _assert_fits(compiled)
    assert compiled.as_text().count("tpu_custom_call") == 1
