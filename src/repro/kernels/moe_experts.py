"""The held experts that a step's tokens picked — Pallas TPU kernel.

One expert-parallel MoE layer computes, for every token ``t``,
``sum_e comb[t, e] * expert_e(x_t)`` over the experts ``e`` this device
holds, where ``comb`` carries the renormalised router weight of each
choice that landed on ``e`` (zero elsewhere).  An expert that no token
picked contributes nothing, so the kernel never reads it.

Grid (token tiles, slots, FFN tiles).  Slot ``j`` holds the ``j``-th
touched expert (scalar-prefetched ``ids``, touched experts first); the
slots past the ``n`` touched ones map their weight blocks to the last
block already resident, so the pipeline issues no DMA for them, and
``pl.when`` skips their compute.  Each step reads one FFN tile of one
expert's gate, up and down matrices and adds its part of the output to
the resident float32 output tile.  The weights are addressed in place in
a stack of layers (``base`` is the layer's first expert in the stack),
so a scan over layers copies none of them.

On the TPU the kernel is compiled by Mosaic; on any other platform the
same kernel runs in interpret mode (``lax.platform_dependent`` picks at
lowering, so one traced program serves both).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: FFN columns per step: one step reads 3 x d x 256 weights
BLOCK_F = 256
#: most token rows per tile (a longer prompt is split into tiles)
MAX_TILE_T = 512
#: VMEM for one step's buffers, each double-buffered: a 512-row tile's
#: input (2 MiB) and float32 output (4 MiB) and 3 weight tiles (3 MiB)
VMEM_LIMIT = 48 * 2 ** 20


def _silu(h):
    return h / (1.0 + jnp.exp(-h))


def _kernel(ids_ref, meta_ref, x_ref, c_ref, wi_ref, wg_ref, wo_ref, o_ref,
            *, act):
    j, f = pl.program_id(1), pl.program_id(2)

    @pl.when((j == 0) & (f == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j < meta_ref[0])
    def _expert():
        x = x_ref[...]
        h = jnp.dot(x, wi_ref[0], preferred_element_type=jnp.float32)
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        h = act(g) * h * c_ref[0]                          # (tT, 1) weights
        o_ref[...] += jnp.dot(h.astype(x.dtype), wo_ref[0],
                              preferred_element_type=jnp.float32)


def _tiles(T: int):
    """(tile rows, tiles) for ``T`` tokens: as few tiles as
    ``MAX_TILE_T`` allows, each a multiple of 8 rows."""
    n = -(-T // MAX_TILE_T)
    rows = -(-T // n)
    return -(-rows // 8) * 8, n


def held_experts(x, comb, counts, wi, wg, wo, base, *, act: str,
                 interpret: bool):
    """x: (T, d); comb: (T, held) float32 combine weights; counts: (held,)
    choices per held expert; wi (up), wg (gate): (n, d, F), wo: (n, F, d),
    the held experts of this layer at ``base .. base + held - 1``; ``act``
    ``"swiglu"`` or ``"geglu"``.  Returns (T, d) float32."""
    T, d = x.shape
    held = comb.shape[1]
    F = wi.shape[2]
    bf = BLOCK_F if F % BLOCK_F == 0 else F
    nf = F // bf
    tt, nt = _tiles(T)
    pad = tt * nt - T
    x = jnp.pad(x, ((0, pad), (0, 0)))
    c = jnp.pad(comb, ((0, pad), (0, 0))).T[:, :, None]    # (held, T', 1)

    touched = counts > 0
    n = jnp.sum(touched).astype(jnp.int32)
    order = jnp.argsort(~touched, stable=True).astype(jnp.int32)
    last = order[jnp.maximum(n - 1, 0)]
    ids = jnp.where(jnp.arange(held) < n, order, last)
    meta = jnp.stack([n, jnp.asarray(base, jnp.int32)])

    def fi(j, f, n):                 # past the touched: stay on the last
        return jnp.where(j < n, f, nf - 1)

    def w_in(t, j, f, ids, meta):
        return (meta[1] + ids[j], 0, fi(j, f, meta[0]))

    def w_out(t, j, f, ids, meta):
        return (meta[1] + ids[j], fi(j, f, meta[0]), 0)

    in_specs = [pl.BlockSpec((tt, d), lambda t, j, f, ids, meta: (t, 0)),
                pl.BlockSpec((1, tt, 1),
                             lambda t, j, f, ids, meta: (ids[j], t, 0)),
                pl.BlockSpec((1, d, bf), w_in),
                pl.BlockSpec((1, d, bf), w_in),
                pl.BlockSpec((1, bf, d), w_out)]
    act_fn = {"swiglu": _silu, "geglu": jax.nn.gelu}[act]
    out = pl.pallas_call(
        functools.partial(_kernel, act=act_fn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nt, held, nf),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tt, d),
                                   lambda t, j, f, ids, meta: (t, 0))),
        out_shape=jax.ShapeDtypeStruct((tt * nt, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(ids, meta, x, c, wi, wg, wo)
    return out[:T]


def held_experts_op(x, comb, counts, wi, wg, wo, base, act: str = "swiglu"):
    """``held_experts`` compiled by Mosaic on the TPU, interpreted
    elsewhere."""
    def call(interpret):
        return functools.partial(held_experts, act=act, interpret=interpret)
    return jax.lax.platform_dependent(
        x, comb, counts, wi, wg, wo, base,
        tpu=call(False), default=call(True))
