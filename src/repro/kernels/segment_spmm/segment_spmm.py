"""Blocked segment-combine Pallas kernel — the FILTER engine's compute core.

The paper's filter engine streams whole partitions over the slow link and
masks inactive edges in compute.  TPU adaptation (DESIGN.md §2):

* the edges reach the fold *routed*: grouped by destination block, so
  each (TILE_N,)-segment output block has a range of 128-edge rows,
  scalar-prefetched into SMEM.  ``segment_spmm_fold`` takes a block
  routed ahead of time (``core.partition.route_partitions`` stores every
  partition that way when the runtime is built) and goes straight to
  the fold; ``segment_spmm_pallas`` routes on the device first: masked
  edges get a sentinel block, one XLA sort groups the stream by
  destination block, and a ``searchsorted`` over the block bounds gives
  the row ranges;
* the routed stream is lane-dense and VMEM-resident for the call:
  destination ids as an (R, 128) int32 array, messages as (d, R, 128);
* destination combining cannot use atomics (TPU has none); instead each
  edge row of a block's range is tested against a (TILE_N, 128) tile of
  output segment ids (segments on sublanes, edges on lanes) and the
  routed messages are folded elementwise into a (TILE_N, 128) per-lane
  fp32 partial — scatter re-expressed as a masked select on the VPU,
  with one cross-lane reduction per output block.

The grid is one step per output block, and a block visits only the rows
that hold its edges, so a call does O(B + n) work for a block of B edges
and n segments.  The whole block sits in VMEM: about 8·(d + 1) bytes per
edge with double buffering, well inside the scoped-VMEM limit for the
partition blocks the engines build (B ≈ 94 k at 2^17 vertices / 64
partitions of a Kronecker graph).

Both combiners share the body: ``sum`` folds with ``+`` from 0, ``min``
with ``minimum`` from ``+inf``.  The select keeps ±inf messages intact
(the 0 * inf = NaN trap rules a matmul out for min).  ``min`` of a fixed
value multiset is order-independent, which is what makes the
kernel-backed FILTER engine *bit-identical* to ``jax.ops.segment_min``
(the engine oracle): segments receiving no valid message flush the
``+inf`` identity, exactly like the oracle.  ``sum`` reassociates the
float additions, so it matches ``segment_sum`` within tolerance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs.scopes import FILTER_ORDER, scope

LANES = 128    # edges per row (one lane width)
TILE_N = 128   # output segments per block (sublanes of the routing tile)
TILE_LANES = 8 * LANES  # edges of one (8, 128) int32 tile: a routed block's width unit


def _kernel(first_ref, last_ref, seg_ref, msg_ref, out_ref, acc_ref, *, combine):
    oi = pl.program_id(0)   # output block index
    d = msg_ref.shape[0]
    identity = jnp.inf if combine == "min" else 0.0
    fold = jnp.minimum if combine == "min" else jnp.add

    acc_ref[...] = jnp.full(acc_ref.shape, identity, acc_ref.dtype)
    ids = jax.lax.broadcasted_iota(jnp.int32, (TILE_N, LANES), 0) + oi * TILE_N

    def row(r, carry):
        hit = seg_ref[pl.ds(r, 1), :] == ids              # (TILE_N, LANES)
        for k in range(d):
            acc_ref[k] = fold(
                acc_ref[k], jnp.where(hit, msg_ref[k, pl.ds(r, 1), :], identity))
        return carry

    jax.lax.fori_loop(first_ref[oi], last_ref[oi], row, 0)

    reduce = jnp.min if combine == "min" else jnp.sum
    for k in range(d):
        out_ref[:, k:k + 1] = reduce(acc_ref[k], axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("n_segments", "combine", "interpret"))
def segment_spmm_fold(
    messages: jax.Array,   # (d, rows, LANES), in routed lane order
    seg: jax.Array,        # (rows, LANES) int32 destinations; pads >= n_blocks·TILE_N
    first: jax.Array,      # (n_blocks,) int32: output block's first row
    last: jax.Array,       # (n_blocks,) int32: one past its last row
    n_segments: int,
    combine: str = "sum",
    interpret: bool = True,
) -> jax.Array:
    """The fold alone over an already routed block: (n_segments, d).

    Lanes are grouped by destination block ``seg // TILE_N`` in ascending
    order, and output block ``b`` finds all of its edges in rows
    ``[first[b], last[b])``; a row may hold edges of neighbouring blocks
    and pads, whose ids match no segment of ``b``."""
    if combine not in ("sum", "min"):
        raise ValueError(f"combine must be 'sum' or 'min', got {combine!r}")
    d, rows, _ = messages.shape
    n_blocks = -(-n_segments // TILE_N)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda oi, first, last: (0, 0)),
            pl.BlockSpec((d, rows, LANES), lambda oi, first, last: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_N, d), lambda oi, first, last: (oi, 0)),
        scratch_shapes=[pltpu.VMEM((d, TILE_N, LANES), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, combine=combine),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_blocks * TILE_N, d), jnp.float32),
        interpret=interpret,
        name="segment_spmm_pallas",
    )(first, last, seg, messages.astype(jnp.float32))
    return out[:n_segments].astype(messages.dtype)


@functools.partial(jax.jit, static_argnames=("n_segments", "combine", "interpret"))
def segment_spmm_pallas(
    messages: jax.Array,   # (m, d)
    seg_ids: jax.Array,    # (m,) int32
    valid: jax.Array,      # (m,) bool
    n_segments: int,
    combine: str = "sum",
    interpret: bool = True,
) -> jax.Array:
    """Route an unordered block on the device, then the same fold as
    ``segment_spmm_fold``: (n_segments, d)."""
    m, d = messages.shape
    n_blocks = -(-n_segments // TILE_N)
    n_pad = n_blocks * TILE_N
    m_pad = -(-m // TILE_LANES) * TILE_LANES
    rows = m_pad // LANES

    # One single-key sort groups the edges by output block: the key packs
    # (block, edge index), and masked or out-of-range edges take the
    # sentinel block n_blocks, past every real one.  (A sort that carries
    # the messages as extra operands compiles for tens of seconds.)
    idx_bits = max(1, (m - 1).bit_length())
    if n_blocks.bit_length() + idx_bits > 31:
        raise ValueError(
            f"segment_spmm packs (block, edge) into a 31-bit key; got "
            f"{n_blocks} blocks x {m} edges")
    with scope(FILTER_ORDER):  # all but the Pallas call
        seg_ids = seg_ids.astype(jnp.int32)
        routed = valid & (seg_ids >= 0) & (seg_ids < n_segments)
        block = jnp.where(routed, seg_ids // TILE_N, n_blocks)
        # the keys are unique, so an unstable sort (much faster to compile) is exact
        key = jax.lax.sort((block << idx_bits) | jnp.arange(m, dtype=jnp.int32),
                           is_stable=False)
        order = key & ((1 << idx_bits) - 1)
        bounds = jnp.searchsorted(
            key >> idx_bits, jnp.arange(n_blocks + 1, dtype=jnp.int32)).astype(jnp.int32)
        first = bounds[:-1] // LANES
        last = jnp.where(bounds[1:] > bounds[:-1], -(-bounds[1:] // LANES), first)

        seg = jnp.where(routed, seg_ids, n_pad)[order]
        seg = jnp.pad(seg, (0, m_pad - m), constant_values=n_pad).reshape(rows, LANES)
        msg = messages.astype(jnp.float32)[order].T
        msg = jnp.pad(msg, ((0, 0), (0, m_pad - m))).reshape(d, rows, LANES)

    out = segment_spmm_fold(msg, seg, first, last, n_segments=n_segments,
                            combine=combine, interpret=interpret)
    return out.astype(messages.dtype)
