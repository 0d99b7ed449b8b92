"""jit'd public wrappers for the Pallas kernels.

The kernels target the TPU.  Interpret mode (the kernel bodies executed
as plain jax for correctness) follows the platform: on when the default
backend is the CPU, off everywhere else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .paged_attention import paged_attention as _paged
from .prefill_attention import flash_prefill as _flash
from .rglru_scan import rglru_scan as _rglru
from .mlstm_cell import mlstm_chunk as _mlstm

def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=())
def paged_attention_op(q, k_pages, v_pages, block_tables, context_lens):
    return _paged(q, k_pages, v_pages, block_tables, context_lens,
                  interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("window", "block_q", "block_k"))
def flash_prefill_op(q, k, v, kv_offset, window=None, block_q=128,
                     block_k=128):
    return _flash(q, k, v, kv_offset, window=window, block_q=block_q,
                  block_k=block_k, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_s", "block_d"))
def rglru_scan_op(a, x, h0, block_s=256, block_d=128):
    return _rglru(a, x, h0, block_s=block_s, block_d=block_d,
                  interpret=_interpret())


@jax.jit
def mlstm_chunk_op(q, k, v, ilog, flog, C0, n0, m0):
    return _mlstm(q, k, v, ilog, flog, C0, n0, m0, interpret=_interpret())
