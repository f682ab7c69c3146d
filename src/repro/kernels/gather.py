"""Unified-cache row gather: the feature-extraction hot loop.

TPU adaptation of Legion's CUDA zero-copy gather.  The table stays in HBM
(``pl.ANY``); each grid step takes one block of ``ROWS_PER_STEP`` indices
into SMEM and issues one row DMA per index, straight from the table into
that step's ``(ROWS_PER_STEP, D)`` VMEM output block.  Misses (idx < 0)
issue no DMA and are zero-filled; an optional hit mask lets the pipeline
overlay host-fetched rows.

Why this layout: Mosaic tiles a float32 block as (8, 128), so a BlockSpec
that selects one table row per grid step is refused, and prefetching a
whole index map into SMEM (1 MiB) runs out at a few hundred thousand rows.
A row DMA has neither limit, and the SMEM index block is XLA's own tile
for a 1-D int32 array (1024), so it needs no relayout.  The feature dim is
padded to the 128-lane boundary per call (a fused copy under jit) unless
the table already is — hot-path callers keep it so (``CliqueCache``).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default, kernel_impl, ref

LANES = 128            # TPU vreg lane count: the feature-width quantum
ROWS_PER_STEP = 1024   # indices per grid step: XLA's 1-D int32 tile in SMEM


def lane_pad(x: jax.Array) -> jax.Array:
    """Pad the last dim of a (rows, D) array to a multiple of ``LANES``."""
    D = x.shape[-1]
    Dp = -(-max(D, 1) // LANES) * LANES
    return x if Dp == D else jnp.pad(x, ((0, 0), (0, Dp - D)))


def index_blocks(idx: jax.Array) -> jax.Array:
    """Flatten an index map to int32 and pad it with -1 (no source) to a
    whole number of ``ROWS_PER_STEP`` blocks."""
    idx = idx.reshape(-1).astype(jnp.int32)
    pad = -idx.shape[0] % ROWS_PER_STEP
    return jnp.pad(idx, (0, pad), constant_values=-1) if pad else idx


def index_spec() -> pl.BlockSpec:
    """One grid step's block of an index map, in SMEM."""
    return pl.BlockSpec((ROWS_PER_STEP,), lambda i: (i,),
                        memory_space=pltpu.SMEM)


def gather_block(sources: Sequence[Tuple[jax.Array, jax.Array]], out_ref,
                 sem) -> None:
    """Kernel body shared by the gathers: fill the VMEM block ``out_ref``
    row by row from HBM.  ``sources`` holds (SMEM index block, HBM table)
    pairs in priority order: row ``r`` is ``table[index[r]]`` of the first
    pair whose index is >= 0, zeros when no pair claims it.  Every claimed
    row is one DMA on ``sem``; all tables share the block's row width, so
    each wait can use any one of them."""
    rows, width = out_ref.shape

    def copy(table, src, r):
        return pltpu.make_async_copy(table.at[pl.ds(src, 1)],
                                     out_ref.at[pl.ds(r, 1)], sem)

    def start(r, carry):
        claimed = jnp.bool_(False)
        for idx_ref, table in sources:
            src = idx_ref[r]

            @pl.when(jnp.logical_and(jnp.logical_not(claimed), src >= 0))
            def _():
                copy(table, src, r).start()

            claimed = jnp.logical_or(claimed, src >= 0)

        @pl.when(jnp.logical_not(claimed))
        def _():
            out_ref[pl.ds(r, 1), :] = jnp.zeros((1, width), out_ref.dtype)

        return carry

    def wait(r, carry):
        claimed = jnp.bool_(False)
        for idx_ref, _ in sources:
            claimed = jnp.logical_or(claimed, idx_ref[r] >= 0)

        @pl.when(claimed)
        def _():
            copy(sources[0][1], 0, r).wait()

        return carry

    jax.lax.fori_loop(0, rows, start, 0)
    jax.lax.fori_loop(0, rows, wait, 0)


def _gather_kernel(idx_ref, table_ref, out_ref, sem):
    gather_block([(idx_ref, table_ref)], out_ref, sem)


def gather_rows_pallas(table: jax.Array, idx: jax.Array, *,
                       interpret: Optional[bool] = None,
                       return_mask: bool = False,
                       ) -> Union[jax.Array, Tuple[jax.Array, jax.Array]]:
    """``out[i] = table[idx[i]]`` (zeros where ``idx < 0``).

    table: (N, D), N >= 1.  idx: any integer shape B...; the output is
    B... + (D,).  ``interpret=None`` follows ``kernels.kernel_impl``:
    compiled Mosaic on TPU, interpreted on CPU.  With ``return_mask=True``
    also returns ``idx >= 0`` (the hit mask the batch pipeline uses to
    overlay host-fetched miss rows).
    """
    if interpret is None:
        interpret = interpret_default()
    N, D = table.shape
    batch_shape = idx.shape
    blocks = index_blocks(idx)
    B = idx.size
    tab = lane_pad(table)
    out = pl.pallas_call(
        _gather_kernel,
        grid=(blocks.shape[0] // ROWS_PER_STEP,),
        in_specs=[index_spec(), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((ROWS_PER_STEP, tab.shape[1]),
                               lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((blocks.shape[0], tab.shape[1]),
                                       table.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
    )(blocks, tab)
    out = out[:B, :D].reshape(batch_shape + (D,))
    if return_mask:
        return out, idx >= 0
    return out


def routed_gather(shard: jax.Array, owner: jax.Array, local_slot: jax.Array,
                  axis_name: str, *, impl: str = "auto") -> jax.Array:
    """Cache-partition-aware row gather — call *inside* ``shard_map`` over
    ``axis_name`` (the clique mesh axis).

    Each device holds one cache partition ``shard`` (R, D) and one batch's
    routing request ``owner``/``local_slot`` (n,) — per requested row, the
    clique-local device owning it and the row within that owner's shard
    (``CliqueCache.shard_routing``); ``owner < 0`` marks a host-fill miss.

    The exchange is the all-gather/psum form of Legion's peer-to-peer
    gather: every device all-gathers the clique's requests, serves the
    rows *it* owns from its local shard (local hits and peer hits alike
    run the same single-shard gather — the Pallas kernel unless ``impl``
    asks for the reference, see ``kernels.kernel_impl``), and one ``psum``
    routes each row back to its requester; rows nobody owns (misses) come
    back zero for the host-fill overlay.  Returns (n, D): this device's
    requested rows.
    """
    me = jax.lax.axis_index(axis_name)
    owner_all = jax.lax.all_gather(owner, axis_name)        # (k, n)
    local_all = jax.lax.all_gather(local_slot, axis_name)   # (k, n)
    k, n = owner_all.shape
    idx = jnp.where(owner_all == me, local_all, -1).reshape(-1)
    if kernel_impl(impl) == "xla":
        rows = ref.gather_rows(shard, idx.astype(jnp.int32))
    else:
        rows = gather_rows_pallas(shard, idx)
    rows = rows.reshape(k, n, shard.shape[1])
    rows = jax.lax.psum(rows, axis_name)
    return rows[me]


def routed_neighbor_sample(indptr: jax.Array, indices: jax.Array,
                           owner: jax.Array, local: jax.Array,
                           rand: jax.Array, axis_name: str) -> jax.Array:
    """Routed neighbor exchange — ``routed_gather`` generalized from fixed-
    width feature rows to ragged-CSR neighbor sampling.  Call *inside*
    ``shard_map`` over ``axis_name`` (the clique mesh axis).

    Each device holds one topology shard — ``indptr`` (R+1,) int, padded
    rows repeating the last offset (degree 0), and ``indices`` (E,) int32,
    its vertices' adjacency in host order — plus one batch's frontier
    routing ``owner``/``local`` (n,) (``CliqueCache`` topo routing tables;
    ``owner < 0`` marks a topology miss) and the host random draws ``rand``
    (n, f) int32, the exact per-hop draws of the host sampler.

    Every device all-gathers the clique's frontier, samples the rows *it*
    owns from its local shard CSR (``start + rand % deg`` — bit-identical
    to ``host_sample_level`` because each shard keeps host adjacency
    order; one XLA gather of scalar ids, on every backend), and one
    ``psum`` delivers each row's neighbors back to its requester.  The -1
    miss sentinel (unowned rows and deg-0 vertices) survives the sum via a
    +1 shift: owners contribute ``out + 1``, non-owners 0, so after the
    psum ownerless rows decode to exactly -1.  Returns (n, f) int32: this
    device's sampled neighbors, -1 rows left for the deferred host fill.
    """
    me = jax.lax.axis_index(axis_name)
    owner_all = jax.lax.all_gather(owner, axis_name)    # (k, n)
    local_all = jax.lax.all_gather(local, axis_name)    # (k, n)
    rand_all = jax.lax.all_gather(rand, axis_name)      # (k, n, f)
    mine = owner_all == me
    safe_l = jnp.where(mine, local_all, 0)
    start = indptr[safe_l]
    deg = indptr[safe_l + 1] - start
    offs = rand_all % jnp.maximum(deg, 1)[..., None]
    E = indices.shape[0]
    idx = jnp.minimum(start[..., None] + offs, jnp.maximum(E - 1, 0))
    out = indices[idx].astype(jnp.int32)
    # +1 shift: only the owner contributes its (shifted) samples; deg-0
    # vertices contribute 0 like non-owners, so they decode to -1 too
    serve = (mine & (deg > 0))[..., None]
    contrib = jnp.where(serve, out + 1, 0)
    total = jax.lax.psum(contrib, axis_name)
    return (total - 1)[me]
