"""Stream-compaction Pallas kernel — the COMPACTION engine.

The paper's ExpTM-compaction removes inactive edges on the CPU before the
PCIe transfer.  On TPU the pass runs on-device.  The wrapper computes
each kept lane's destination with one XLA prefix sum, plus the offset at
which every (TILE,)-lane tile starts writing (scalar-prefetched).  The
rows ride lane-major: an (R, m) int32 array, one row per 32-bit field.
A single sequential sweep over (R, TILE) tiles then

  1. moves kept lanes to their destinations with a one-hot matmul
     (scatter as MXU compute — no atomics needed).  The matmul moves the
     field *bytes*: each byte is a bf16-exact integer in [0, 255] and the
     one-hot has one 1 per column, so any 32-bit pattern (ids, ±inf,
     NaN, -0.0) copies bit-exact;
  2. lays the moved lanes into a VMEM window that starts at the tile's
     offset rounded down to 128 lanes, keeping the partial leading
     lane block that the previous tile wrote (carried in VMEM);
  3. DMAs the window into the HBM output at that 128-aligned offset
     (the data-dependent destination).

Each tile's DMA completes before the next one starts: the next window
overwrites this one's padding, so the output is the dense compacted
stream.  The count is the prefix sum's total.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 512
LANES = 128


def _kernel(offs_ref, dest_ref, bits_ref, out_ref, tail_ref, win_ref, sem):
    bi = pl.program_id(0)

    @pl.when(bi == 0)
    def _init():
        tail_ref[...] = jnp.zeros(tail_ref.shape, tail_ref.dtype)

    off = offs_ref[bi]
    base = off // LANES * LANES
    # route[j, i]: input lane i lands on window lane j (dropped lanes: -1)
    local = jnp.where(dest_ref[...] >= 0, dest_ref[...] - base, -1)   # (1, TILE)
    route = (jax.lax.broadcasted_iota(jnp.int32, (TILE + LANES, TILE), 0)
             == local).astype(jnp.bfloat16)
    bits = bits_ref[...]                                             # (R, TILE)
    moved = jnp.zeros(win_ref.shape, jnp.int32)
    for shift in (0, 8, 16, 24):
        byte = ((bits >> shift) & 0xFF).astype(jnp.float32).astype(jnp.bfloat16)
        part = jax.lax.dot_general(
            byte, route, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                      # (R, TILE + LANES)
        moved = moved | (part.astype(jnp.int32) << shift)

    lead = jax.lax.broadcasted_iota(jnp.int32, tail_ref.shape, 1) < off - base
    win_ref[:, :LANES] = jnp.where(lead, tail_ref[...], moved[:, :LANES])
    win_ref[:, LANES:] = moved[:, LANES:]
    copy = pltpu.make_async_copy(
        win_ref, out_ref.at[:, pl.ds(pl.multiple_of(base, LANES), TILE + LANES)],
        sem)
    copy.start()
    nxt = pl.multiple_of(offs_ref[bi + 1] // LANES * LANES - base, LANES)
    tail_ref[...] = win_ref[:, pl.ds(nxt, LANES)]
    copy.wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def frontier_compact_pallas(
    values: jax.Array,   # (m, c) packed edge fields, any 32-bit dtype
    mask: jax.Array,     # (m,) bool
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    m, c = values.shape
    if values.dtype.itemsize != 4:
        raise ValueError(f"frontier_compact moves 32-bit fields, got {values.dtype}")
    m_pad = -(-m // TILE) * TILE
    n_tiles = m_pad // TILE
    r = -(-c // 8) * 8

    keep = jnp.pad(mask, (0, m_pad - m))
    dest = jnp.where(keep, jnp.cumsum(keep, dtype=jnp.int32) - 1, -1)[None, :]
    per_tile = jnp.sum(keep.reshape(n_tiles, TILE), axis=1, dtype=jnp.int32)
    offs = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(per_tile)])
    bits = jax.lax.bitcast_convert_type(values, jnp.int32).T
    bits = jnp.pad(bits, ((0, r - c), (0, m_pad - m)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((1, TILE), lambda i, offs: (0, i)),
            pl.BlockSpec((r, TILE), lambda i, offs: (0, i)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),   # whole output: DMA appends
        scratch_shapes=[
            pltpu.VMEM((r, LANES), jnp.int32),
            pltpu.VMEM((r, TILE + LANES), jnp.int32),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, m_pad + LANES), jnp.int32),
        interpret=interpret,
    )(offs, dest, bits)
    out = jax.lax.bitcast_convert_type(out[:c, :m].T, values.dtype)
    return out, offs[-1]
