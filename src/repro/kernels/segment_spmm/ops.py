"""Public wrapper: interpret=True on CPU (this container), compiled
Pallas on TPU backends (backend policy: ``repro.kernels.runtime``)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.runtime import interpret_mode, map_over_lanes
from repro.kernels.segment_spmm.segment_spmm import segment_spmm_fold, segment_spmm_pallas


def segment_spmm(
    messages: jax.Array,
    seg_ids: jax.Array,
    n_segments: int,
    valid: jax.Array | None = None,
    combine: str = "sum",
) -> jax.Array:
    """Segment-combine (m, d) messages into (n_segments, d) — the filter
    engine's blocked aggregation.

    ``combine``: ``"sum"`` (scatter-add as MXU matmul) or ``"min"``
    (traversal combiners; segments receiving no valid message hold
    ``+inf``, the min identity, exactly like ``jax.ops.segment_min``).
    ``n_segments`` may exceed every observed ``seg_ids`` entry — the
    extra segments come back as the combiner identity.
    """
    if valid is None:
        valid = jnp.ones(messages.shape[0], dtype=bool)
    squeeze = False
    if messages.ndim == 1:
        messages, squeeze = messages[:, None], True
    if messages.shape[0] == 0:
        # zero edges: the tiled grid would need a 0-row block slice
        # (degenerate BlockSpec); the combine identity is the answer.
        identity = jnp.inf if combine == "min" else 0.0
        out = jnp.full((n_segments, messages.shape[1]), identity,
                       messages.dtype)
    else:
        combine_fn = functools.partial(
            segment_spmm_pallas, n_segments=n_segments, combine=combine,
            interpret=interpret_mode())
        out = map_over_lanes(combine_fn)(messages, seg_ids, valid)
    return out[:, 0] if squeeze else out


def segment_spmm_routed(
    messages: jax.Array,
    seg: jax.Array,
    first: jax.Array,
    last: jax.Array,
    n_segments: int,
    combine: str = "sum",
) -> jax.Array:
    """``segment_spmm`` of a block whose lanes are already routed: grouped
    by destination block, with each output block's row range in
    ``first``/``last`` (``core.partition.route_partitions`` builds such
    blocks).  No sort, no ``searchsorted``, no gather: the call is the
    fold alone.

    ``messages`` is (d, rows, 128) and gives (n_segments, d); ``seg`` is
    the (rows, 128) destination view, with pad lanes at a sentinel id
    past every output block.
    """
    combine_fn = functools.partial(
        segment_spmm_fold, n_segments=n_segments, combine=combine,
        interpret=interpret_mode())
    return map_over_lanes(combine_fn)(messages, seg, first, last)
