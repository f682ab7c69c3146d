"""Jit'd public wrappers for the Pallas kernels.

``interpret=None`` follows ``kernels.kernel_impl``: kernel bodies execute
as jax ops on the CPU platform and lower through Mosaic on TPU.  The
wrappers keep the oracle-identical signatures from ref.py.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fused_batch import fused_gather_overlay_pallas
from repro.kernels.gather import gather_rows_pallas, routed_gather
from repro.kernels.sage_agg import sage_aggregate_pallas
from repro.kernels.scatter import scatter_rows_pallas


@partial(jax.jit, static_argnames=("interpret", "return_mask"))
def gather_rows(table: jax.Array, idx: jax.Array, interpret: bool = None,
                return_mask: bool = False):
    return gather_rows_pallas(table, idx, interpret=interpret,
                              return_mask=return_mask)


@partial(jax.jit, static_argnames=("interpret",))
def fused_gather_overlay(table: jax.Array, idx: jax.Array,
                         miss_rows: jax.Array, miss_inv: jax.Array,
                         interpret: bool = None):
    return fused_gather_overlay_pallas(table, idx, miss_rows, miss_inv,
                                       interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def scatter_rows(table: jax.Array, idx: jax.Array, rows: jax.Array,
                 interpret: bool = None):
    return scatter_rows_pallas(table, idx, rows, interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def sage_aggregate(table: jax.Array, idx: jax.Array, weights: jax.Array,
                   interpret: bool = None):
    return sage_aggregate_pallas(table, idx, weights, interpret=interpret)


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = None):
    return flash_attention_pallas(q, k, v, causal=causal, block_q=block_q,
                                  block_k=block_k, interpret=interpret)


__all__ = ["gather_rows", "scatter_rows", "sage_aggregate",
           "fused_gather_overlay", "flash_attention", "routed_gather", "ref"]
