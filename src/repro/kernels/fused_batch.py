"""Fused device phase of one mini-batch: cached-row gather + miss overlay.

One kernel produces the batch's full unique-vertex feature block from two
sources in a single dispatch:

  * the HBM-resident unified feature cache (``table``) for hit rows, and
  * the host-staged miss buffer (``miss_rows``) for rows the cache does not
    hold — the small H2D slice the pipeline uploads per batch.

The unfused pipeline dispatched a gather, then patched misses in with a
full-table ``.at[].set`` copy; fusing them removes the extra table-sized
copy and halves the dispatches on the per-batch hot path.  Row selection is
driven by two index maps, blocked into SMEM a grid step at a time exactly
like ``gather.py``; each output row is one DMA from whichever source claims
it:

  ``miss_inv[i]`` staging row feeding output row ``i`` (< 0: not a miss)
  ``idx[i]``      cache slot feeding output row ``i`` (< 0: not cached)

Rows where both maps are negative (shape-bucket padding) come back zero.
Both sources sit in HBM at one lane-padded width — callers keep ``table``
and ``miss_rows`` that way so no per-batch re-pad happens.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default
from repro.kernels.gather import (ROWS_PER_STEP, gather_block, index_blocks,
                                  index_spec, lane_pad)


def _fused_kernel(inv_ref, idx_ref, miss_ref, table_ref, out_ref, sem):
    gather_block([(inv_ref, miss_ref), (idx_ref, table_ref)], out_ref, sem)


def fused_gather_overlay_pallas(table: jax.Array, idx: jax.Array,
                                miss_rows: jax.Array, miss_inv: jax.Array, *,
                                interpret: Optional[bool] = None) -> jax.Array:
    """``out[i] = miss_rows[miss_inv[i]] if miss_inv[i] >= 0 else
    (table[idx[i]] if idx[i] >= 0 else 0)``.

    table: (N, D) with N >= 1; miss_rows: (M, D) with M >= 1 (callers pad
    empty miss sets to one zero row — the bucket discipline guarantees
    this); idx, miss_inv: (B,) int32.  A row must not be claimed by both
    maps (hit and miss are disjoint by construction); the miss source wins
    if it ever were.  Returns (B, D).
    """
    if interpret is None:
        interpret = interpret_default()
    N, D = table.shape
    if miss_rows.shape[1] != D:
        raise ValueError(f"miss_rows feature dim {miss_rows.shape[1]} != "
                         f"table feature dim {D} (stage at the table's "
                         "lane-padded width)")
    B = idx.size
    idx = index_blocks(idx)
    inv = index_blocks(miss_inv)
    table = lane_pad(table)
    miss_rows = lane_pad(miss_rows.astype(table.dtype))
    Dp = table.shape[1]
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        _fused_kernel,
        grid=(idx.shape[0] // ROWS_PER_STEP,),
        in_specs=[index_spec(), index_spec(), any_spec, any_spec],
        out_specs=pl.BlockSpec((ROWS_PER_STEP, Dp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((idx.shape[0], Dp), table.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
    )(inv, idx, miss_rows, table)
    return out[:B, :D]
