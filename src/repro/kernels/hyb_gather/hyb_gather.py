"""Per-vertex neighbour-segment gather Pallas kernel — the ZEROCOPY engine.

EMOGI's zero-copy issues one fine-grained memory request per (vertex,
cache line); the TPU analogue is one DMA descriptor per neighbour
segment, issued straight against the HBM-resident edge array.  The edge
fields ride lane-major, an (R, m) int32 array with one row per 32-bit
field, and the kernel:

* scalar-prefetches the active vertices' segment starts/degrees (the
  compacted frontier produced by `frontier_compact` or the scheduler),
* per grid step, DMAs the two 128-lane blocks that hold one vertex's
  window ``edges[:, start : start + PAD]`` from HBM into VMEM (the DMA
  source must start on a 128-lane boundary; a start inside a block is
  the extra transaction the cost model's am(v) term charges),
* rotates the window to lane 0 and masks lanes past the vertex's true
  degree — pure data movement, so every 32-bit pattern copies bit-exact.

Output is the (n_active, PAD, c) padded neighbour tensor the downstream
relax consumes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PAD = 128  # neighbour window per vertex (one lane width)


def _kernel(starts_ref, degs_ref, edges_ref, out_ref, buf_ref, sem):
    vi = pl.program_id(0)
    start = starts_ref[vi]
    base = start // PAD * PAD
    copy = pltpu.make_async_copy(
        edges_ref.at[:, pl.ds(pl.multiple_of(base, PAD), 2 * PAD)], buf_ref, sem)
    copy.start()
    copy.wait()
    # lane (start - base) -> lane 0; shift 2 * PAD == 0 is normalised away
    shift = (2 * PAD - (start - base)) % (2 * PAD)
    window = pltpu.roll(buf_ref[...], shift, 1)[:, :PAD]
    lane = jax.lax.broadcasted_iota(jnp.int32, window.shape, 1)
    out_ref[0] = jnp.where(lane < degs_ref[vi], window, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def hyb_gather_pallas(
    edges: jax.Array,       # (m, c) edge fields, any 32-bit dtype
    seg_start: jax.Array,   # (a,) int32 segment starts of active vertices
    degree: jax.Array,      # (a,) int32
    interpret: bool = True,
) -> jax.Array:
    m, c = edges.shape
    if edges.dtype.itemsize != 4:
        raise ValueError(f"hyb_gather moves 32-bit fields, got {edges.dtype}")
    a = seg_start.shape[0]
    r = -(-c // 8) * 8
    # one spare block keeps the two-block window DMA in bounds
    m_pad = (-(-m // PAD) + 1) * PAD
    bits = jax.lax.bitcast_convert_type(edges, jnp.int32).T
    bits = jnp.pad(bits, ((0, r - c), (0, m_pad - m)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(a,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, r, PAD), lambda vi, starts, degs: (vi, 0, 0)),
        scratch_shapes=[pltpu.VMEM((r, 2 * PAD), jnp.int32),
                        pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((a, r, PAD), jnp.int32),
        interpret=interpret,
    )(seg_start.astype(jnp.int32), degree.astype(jnp.int32), bits)
    return jax.lax.bitcast_convert_type(
        jnp.swapaxes(out[:, :c], 1, 2), edges.dtype)
