"""The three transfer/processing engines (paper §II-B/C, Fig. 2).

All three engines relax the *same* active edges and must produce identical
results; they differ in how the edge bytes travel from the big memory to
the compute units:

* ``FILTER``   — stream the whole partition block contiguously (cudaMemcpy
  analogue; on TPU: dense (8,128)-tiled HBM->VMEM DMA, `kernels/segment_spmm`).
  Inactive edges ride along and are masked in compute.
* ``COMPACT``  — first squeeze the active edges to the front of the block
  (prefix-sum stream compaction; the paper's CPU pass becomes an on-device
  pass, `kernels/frontier_compact`), then stream only the dense prefix.
* ``ZEROCOPY`` — fine-grained per-vertex gathers of neighbour segments
  straight from the big memory (`kernels/hyb_gather`): no redundancy, no
  extra pass, but request-granular bandwidth.

Each engine has TWO implementations behind the static ``use_kernels``
flag (threaded from ``HyTMConfig.use_kernels`` — ``"auto"`` resolves via
``kernels.runtime``: on for TPU backends, off elsewhere):

* ``use_kernels=False`` — the pure-JAX *oracles* below: `filter` is a
  masked dense block, `compact` really sorts active edges to the front
  and relaxes the prefix, `zerocopy` gathers edge ids through a take
  (random access).
* ``use_kernels=True`` — the Pallas kernel path: FILTER combines through
  the blocked ``segment_spmm`` (destination-routed masked-select
  scatter-add / scatter-min), COMPACT squeezes the active edges through the
  ``frontier_compact`` stream-compaction kernel and relaxes the dense
  prefix, ZEROCOPY re-fetches the block as per-window DMA descriptors
  through ``hyb_gather`` before combining.

Equivalence contract (tests/test_engines.py, tests/test_kernels.py): the
kernel path is **bit-identical** to the oracle for MIN combiners (min is
order-independent; the compaction prefix is stable in both paths) and
tolerance-bounded for SUM (the tiled accumulation reassociates float
addition).  Both paths trace under ``vmap`` (service lanes),
``shard_map`` (the mesh sweep), and ``lax.while_loop`` (the chunked
driver).  ``lax.switch`` executes exactly one engine per partition.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.graph.algorithms import MIN, VertexProgram
from repro.obs.scopes import ENGINE_SCOPES, scope


class EdgeBlock(NamedTuple):
    """One partition's (padded) edge block.

    A block read from ``Runtime.route`` is routed: its lanes are grouped
    by destination block, and ``first``/``last`` give each output
    block's rows, so FILTER's fold needs no sort.  Without them the
    kernel routes the block on every call."""

    src: jax.Array      # (B,) int32
    dst: jax.Array      # (B,) int32
    weight: jax.Array   # (B,) float32
    active: jax.Array   # (B,) bool — source active AND edge in partition
    first: jax.Array | None = None  # (n_blocks,) int32, routed blocks only
    last: jax.Array | None = None   # (n_blocks,) int32


class RelaxOut(NamedTuple):
    agg: jax.Array       # (n,) combined messages
    touched: jax.Array   # (n,) bool — destinations receiving any message


def _messages(block: EdgeBlock, operand: jax.Array, program: VertexProgram) -> jax.Array:
    """Per-edge messages; inactive lanes emit the combiner identity."""
    src_op = operand[block.src]
    msg = program.edge_message(src_op, block.weight)
    identity = jnp.inf if program.combine == MIN else 0.0
    return jnp.where(block.active, msg, identity)


def _combine(block: EdgeBlock, msg: jax.Array, n: int, program: VertexProgram) -> RelaxOut:
    if program.combine == MIN:
        agg = jax.ops.segment_min(msg, block.dst, num_segments=n)
        touched = jnp.isfinite(agg)
    else:
        agg = jax.ops.segment_sum(msg, block.dst, num_segments=n)
        got = jax.ops.segment_sum(
            block.active.astype(jnp.float32), block.dst, num_segments=n
        )
        touched = got > 0
    return RelaxOut(agg=agg, touched=touched)


def _combine_spmm(block: EdgeBlock, msg: jax.Array, n: int, program: VertexProgram) -> RelaxOut:
    """Destination combine through the blocked ``segment_spmm`` kernel.

    MIN: the scatter-min kernel over the identity-masked messages —
    bit-identical to ``jax.ops.segment_min`` (order-free).  SUM: one
    kernel call over the packed [message, active] columns — the value
    column is tolerance-bounded (tiled reassociation), the 0/1 activity
    column sums exactly, so ``touched`` stays bit-exact.  A routed block
    goes straight to the fold, its message columns already in place as
    (d, rows, 128) tiles; any other block is routed by the kernel on
    every call.
    """
    from repro.kernels.segment_spmm.ops import segment_spmm, segment_spmm_routed
    from repro.kernels.segment_spmm.segment_spmm import LANES

    cols = [msg] if program.combine == MIN else [msg, block.active.astype(msg.dtype)]
    combine = "min" if program.combine == MIN else "sum"
    if block.first is None:
        out = segment_spmm(jnp.stack(cols, axis=-1), block.dst, n, combine=combine)
    else:
        tiles = (block.dst.shape[0] // LANES, LANES)
        out = segment_spmm_routed(jnp.stack(cols).reshape(len(cols), *tiles),
                                  block.dst.reshape(tiles), block.first,
                                  block.last, n, combine=combine)
    if program.combine == MIN:
        return RelaxOut(agg=out[:, 0], touched=jnp.isfinite(out[:, 0]))
    return RelaxOut(agg=out[:, 0], touched=out[:, 1] > 0)


def _pack_fields(block: EdgeBlock) -> jax.Array:
    """(B, 4) int32 [src, dst, weight bits, active] for the data-movement
    kernels.  Int32 all the way: a float lane holding an id's bit pattern
    is a denormal, and TPU arithmetic flushes denormals to zero."""
    return jnp.stack([
        block.src,
        block.dst,
        jax.lax.bitcast_convert_type(block.weight, jnp.int32),
        block.active.astype(jnp.int32),
    ], axis=-1)


def _unpack_fields(packed: jax.Array):
    return (packed[:, 0], packed[:, 1],
            jax.lax.bitcast_convert_type(packed[:, 2], jnp.float32),
            packed[:, 3] != 0)


# ------------------------------------------------------------------ engines

def relax_filter(
    block: EdgeBlock, operand: jax.Array, n: int, program: VertexProgram,
    use_kernels: bool = False,
) -> RelaxOut:
    """Whole-block masked relax (dense stream)."""
    msg = _messages(block, operand, program)
    if use_kernels:
        return _combine_spmm(block, msg, n, program)
    return _combine(block, msg, n, program)


def relax_compact(
    block: EdgeBlock, operand: jax.Array, n: int, program: VertexProgram,
    use_kernels: bool = False,
) -> RelaxOut:
    """Compact active edges to the front (stable), then relax the prefix.

    The compaction is the on-device analogue of the paper's CPU pass:
    after it, the active edges occupy a dense prefix, which is what the
    downstream dense kernel would stream.  Correctness is unaffected by
    the permutation (combiners are commutative).  The kernel path runs
    the real ``frontier_compact`` stream-compaction kernel over the
    packed int32 (src, dst, weight bits, active) columns; both paths
    keep kept lanes in original order (stable), so even the SUM
    summation order matches the oracle on the dense prefix.
    """
    if use_kernels:
        from repro.kernels.frontier_compact.ops import frontier_compact

        B = block.src.shape[0]
        # the kernel moves 32-bit patterns: ids ride as int32 and the
        # weight as its bits, so every field copies exactly
        comp, cnt = frontier_compact(_pack_fields(block), block.active)
        lane_valid = jnp.arange(B, dtype=jnp.int32) < cnt
        src, dst, weight, _ = _unpack_fields(comp)
        compacted = EdgeBlock(
            src=jnp.where(lane_valid, src, 0),
            dst=jnp.where(lane_valid, dst, 0),
            weight=jnp.where(lane_valid, weight, 0.0),
            active=lane_valid,
        )
    else:
        order = jnp.argsort(~block.active, stable=True)
        compacted = EdgeBlock(
            src=block.src[order],
            dst=block.dst[order],
            weight=block.weight[order],
            active=block.active[order],
        )
    return _combine(compacted, _messages(compacted, operand, program), n, program)


def relax_zerocopy(
    block: EdgeBlock, operand: jax.Array, n: int, program: VertexProgram,
    use_kernels: bool = False,
) -> RelaxOut:
    """Fine-grained gather relax: edge fields are re-fetched through
    random access (per-request pattern), then combined.  Semantically
    identical; access pattern is the ZC one.  The kernel path issues the
    block as per-window ``hyb_gather`` DMA descriptors (one descriptor
    per PAD-lane window — the fine-grained request stream Eq. 3 charges)
    instead of the oracle's ``take``; the fields ride as int32 lanes
    (the weight as its bit pattern) through pure data movement, so the
    relax result is bit-identical to the oracle for both combiners.
    """
    if use_kernels:
        from repro.kernels.hyb_gather.hyb_gather import PAD
        from repro.kernels.hyb_gather.ops import hyb_gather

        B = block.src.shape[0]
        n_win = -(-B // PAD)
        starts = jnp.arange(n_win, dtype=jnp.int32) * PAD
        degs = jnp.minimum(jnp.int32(B) - starts, PAD)
        flat = hyb_gather(_pack_fields(block), starts, degs)
        src, dst, weight, active = _unpack_fields(flat.reshape(n_win * PAD, 4)[:B])
        gathered = EdgeBlock(src=src, dst=dst, weight=weight, active=active)
    else:
        idx = jnp.arange(block.src.shape[0], dtype=jnp.int32)
        gathered = EdgeBlock(
            src=jnp.take(block.src, idx),
            dst=jnp.take(block.dst, idx),
            weight=jnp.take(block.weight, idx),
            active=jnp.take(block.active, idx),
        )
    return _combine(gathered, _messages(gathered, operand, program), n, program)


ENGINE_FNS = (relax_filter, relax_compact, relax_zerocopy)


def relax_with_engine(
    engine_id: jax.Array,  # scalar int32: 0 filter / 1 compact / 2 zerocopy
    block: EdgeBlock,
    operand: jax.Array,
    n: int,
    program: VertexProgram,
    use_kernels: bool = False,
) -> RelaxOut:
    def branch(b):
        def relax():
            with scope(ENGINE_SCOPES[b]):
                return ENGINE_FNS[b](block, operand, n, program, use_kernels)
        return relax

    return jax.lax.switch(jnp.clip(engine_id, 0, 2), [branch(b) for b in range(3)])
