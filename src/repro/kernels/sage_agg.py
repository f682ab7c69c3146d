"""Fused gather + weighted-sum aggregation (GraphSAGE AGGREGATE).

out[b] = sum_f w[b, f] * table[idx[b, f]]

Fusing the neighbor-feature gather with the mean removes the (B, F, D)
intermediate entirely — the rows stream HBM->VMEM once and reduce in a VMEM
accumulator.  Grid is (B, F) with F innermost: the output block for row b is
revisited across f steps (sequential TPU grid), accumulating in place.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default


def _agg_kernel(idx_ref, w_ref, table_ref, out_ref):
    b = pl.program_id(0)
    f = pl.program_id(1)

    @pl.when(f == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    valid = idx_ref[b, f] >= 0
    w = jnp.where(valid, w_ref[b, f], 0.0).astype(jnp.float32)
    row = table_ref[...].astype(jnp.float32)
    out_ref[...] += (row * w).astype(out_ref.dtype)


def sage_aggregate_pallas(table: jax.Array, idx: jax.Array, weights: jax.Array,
                          *, interpret: bool = None) -> jax.Array:
    """table (N, D); idx (B, F) int32 (neg = pad); weights (B, F) f32."""
    if interpret is None:
        interpret = interpret_default()
    N, D = table.shape
    B, F = idx.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # idx, weights
        grid=(B, F),
        in_specs=[
            pl.BlockSpec((1, D), lambda b, f, idx, w: (jnp.maximum(idx[b, f], 0), 0)),
        ],
        out_specs=pl.BlockSpec((1, D), lambda b, f, idx, w: (b, 0)),
    )
    fn = pl.pallas_call(
        _agg_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.float32),
        interpret=interpret,
    )
    return fn(idx.astype(jnp.int32), weights.astype(jnp.float32),
              table).astype(table.dtype)
