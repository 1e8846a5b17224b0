"""Capacity-grouped expert GEMM Pallas kernel — the MoE hot spot.

After the sorted (compaction-engine) dispatch, tokens for expert e occupy
rows ``[starts[e], starts[e] + counts[e])`` of the sorted activation
buffer.  The kernel runs a (n_experts, n_row_tiles) grid: each step DMAs
one (TILE_T, D) token tile from a *dynamic* row offset (scalar-prefetched
group starts), multiplies by that expert's (D, F) weight block on the
MXU, and masks rows past the group count.  Empty tiles are skipped with
``pl.when`` — the paper's "skip inactive partitions" applied to experts.

max_rows_per_expert bounds the per-expert tile count (== capacity).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_T = 128


def _kernel(starts_ref, counts_ref, x_ref, zero_ref, w_ref, out_ref,
            x_tile, y_tile, sem):
    del zero_ref  # aliased to out_ref: guarantees untouched rows are zero
    e = pl.program_id(0)
    ti = pl.program_id(1)
    row = starts_ref[e] + ti * TILE_T
    count = counts_ref[e]

    @pl.when(ti * TILE_T < count)
    def _work():
        load = pltpu.make_async_copy(x_ref.at[pl.ds(row, TILE_T)], x_tile, sem)
        load.start()
        load.wait()
        lane = jax.lax.broadcasted_iota(jnp.int32, (TILE_T, 1), 0)
        x = jnp.where(lane + ti * TILE_T < count, x_tile[...], 0)
        y_tile[...] = jax.lax.dot_general(
            x.astype(jnp.float32), w_ref[0].astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(y_tile.dtype)
        store = pltpu.make_async_copy(y_tile, out_ref.at[pl.ds(row, TILE_T)], sem)
        store.start()
        store.wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul_pallas(
    x_sorted: jax.Array,   # (T, D) tokens sorted by expert
    weights: jax.Array,    # (E, D, F)
    starts: jax.Array,     # (E,) int32 group starts
    counts: jax.Array,     # (E,) int32 group sizes
    interpret: bool = True,
) -> jax.Array:
    T, D = x_sorted.shape
    E, _, F = weights.shape
    t_pad = -(-T // TILE_T) * TILE_T
    # lane-dense rows: the row DMAs move whole 128-lane tiles
    d_pad = -(-D // 128) * 128
    f_pad = -(-F // 128) * 128
    x = jnp.pad(x_sorted, ((0, t_pad - T + TILE_T), (0, d_pad - D)))
    w = jnp.pad(weights, ((0, 0), (0, d_pad - D), (0, f_pad - F)))
    n_tiles = t_pad // TILE_T

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(E, n_tiles),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, d_pad, f_pad), lambda e, ti, starts, counts: (e, 0, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((TILE_T, d_pad), x_sorted.dtype),
                        pltpu.VMEM((TILE_T, f_pad), x_sorted.dtype),
                        pltpu.SemaphoreType.DMA(())],
    )
    zeros = jnp.zeros((t_pad + TILE_T, f_pad), x_sorted.dtype)
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_pad + TILE_T, f_pad), x_sorted.dtype),
        # rows outside every group keep the zero initialization
        input_output_aliases={3: 0},
        interpret=interpret,
    )(starts.astype(jnp.int32), counts.astype(jnp.int32), x, zeros, w)
    return out[:T, :F]
