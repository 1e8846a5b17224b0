"""Fused embedding-bag Pallas kernel — the DLRM lookup hot spot.

JAX has no ``nn.EmbeddingBag``; the jnp substrate builds it from take +
segment_sum (models/embedding.py).  This kernel fuses the two against the
HBM-resident table: per grid step it processes one bag tile, issuing one
row-DMA per (bag, slot) lookup (``make_async_copy`` of a dynamic row
slice — the zero-copy access pattern) and reducing in a VMEM accumulator,
so the gathered rows never round-trip through HBM.

Indices are scalar-prefetched (they drive the DMA descriptors).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_B = 8  # bags per grid step


def _kernel(idx_ref, table_ref, out_ref, row_ref, sem, *, bag_size, mode):
    bi = pl.program_id(0)

    def bag_body(b, _):
        def slot_body(s, acc):
            row_id = idx_ref[(bi * TILE_B + b) * bag_size + s]
            copy = pltpu.make_async_copy(
                table_ref.at[pl.ds(row_id, 1)], row_ref, sem)   # one DMA
            copy.start()
            copy.wait()
            row = row_ref[...].astype(jnp.float32)
            if mode == "max":
                return jnp.maximum(acc, row)
            return acc + row

        init = jnp.full((1, table_ref.shape[1]), -jnp.inf if mode == "max" else 0.0, jnp.float32)
        acc = jax.lax.fori_loop(0, bag_size, slot_body, init)
        if mode == "mean":
            acc = acc / bag_size
        out_ref[pl.ds(b, 1), :] = acc.astype(out_ref.dtype)
        return _

    jax.lax.fori_loop(0, TILE_B, bag_body, 0)


@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def embedding_bag_pallas(
    table: jax.Array,     # (V, D)
    indices: jax.Array,   # (B, L) int32
    mode: str = "sum",
    interpret: bool = True,
) -> jax.Array:
    B, L = indices.shape
    V, D = table.shape
    b_pad = -(-B // TILE_B) * TILE_B
    d_pad = -(-D // 128) * 128
    idx = jnp.pad(indices, ((0, b_pad - B), (0, 0))).reshape(-1)
    tbl = jnp.pad(table, ((0, 0), (0, d_pad - D)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b_pad // TILE_B,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((TILE_B, d_pad), lambda bi, idx: (bi, 0)),
        scratch_shapes=[pltpu.VMEM((1, d_pad), table.dtype),
                        pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bag_size=L, mode=mode),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b_pad, d_pad), table.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), tbl)
    return out[:B, :D]
