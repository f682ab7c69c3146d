"""Unified-cache row scatter: the online-refresh write path.

Counterpart of `gather.py` for cache admissions: ``out = table`` with
``out[idx[i]] = rows[i]`` for every valid (non-negative, in-range) index.
The result is a *new* table — the refresh runtime double-buffers the HBM
feature cache, so in-flight batches keep gathering from the previous
buffer while admitted rows land in the next one.

The kernel's output aliases the table (``input_output_aliases``); XLA
copies the table into it first whenever the caller still holds the input,
which is what keeps the input buffer untouched.  The grid walks the
*admitted* rows, ``ROWS_PER_STEP`` indices per step blocked into SMEM as
in ``gather.py``, and each valid index is one HBM-to-HBM row DMA — so the
work and the SMEM footprint scale with the admissions, never with the
table.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default
from repro.kernels.gather import (ROWS_PER_STEP, index_blocks, index_spec,
                                  lane_pad)


def _scatter_kernel(idx_ref, rows_ref, table_ref, out_ref, sem):
    del table_ref  # aliased to out_ref
    base = pl.program_id(0) * ROWS_PER_STEP

    def copy(r, dst):
        return pltpu.make_async_copy(rows_ref.at[pl.ds(base + r, 1)],
                                     out_ref.at[pl.ds(dst, 1)], sem)

    def start(r, carry):
        dst = idx_ref[r]

        @pl.when(dst >= 0)
        def _():
            copy(r, dst).start()

        return carry

    def wait(r, carry):
        @pl.when(idx_ref[r] >= 0)
        def _():
            copy(0, 0).wait()

        return carry

    jax.lax.fori_loop(0, ROWS_PER_STEP, start, 0)
    jax.lax.fori_loop(0, ROWS_PER_STEP, wait, 0)


def scatter_rows_pallas(table: jax.Array, idx: jax.Array, rows: jax.Array, *,
                        interpret: Optional[bool] = None) -> jax.Array:
    """Functional row scatter: ``out = table; out[idx[i]] = rows[i]``.

    table: (N, D); idx: (B,) int (negatives and out-of-range are dropped);
    rows: (B, D).  Indices must be unique among the valid entries — cache
    refreshes write each freed slot exactly once (the manager guarantees
    this); a row named twice ends up with unspecified contents.

    Returns a new (N, D) array; the input buffer is untouched, which is
    exactly what the double-buffered cache refresh needs.
    """
    if interpret is None:
        interpret = interpret_default()
    N, D = table.shape
    idx = idx.reshape(-1).astype(jnp.int32)
    B = idx.shape[0]
    if B == 0 or N == 0:
        return table
    valid = (idx >= 0) & (idx < N)
    blocks = index_blocks(jnp.where(valid, idx, -1))
    tab = lane_pad(table)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        _scatter_kernel,
        grid=(blocks.shape[0] // ROWS_PER_STEP,),
        in_specs=[index_spec(), any_spec, any_spec],
        out_specs=any_spec,
        out_shape=jax.ShapeDtypeStruct(tab.shape, table.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        input_output_aliases={2: 0},
        interpret=interpret,
    )(blocks, lane_pad(rows.astype(table.dtype)), tab)
    return out[:, :D] if tab.shape[1] != D else out
