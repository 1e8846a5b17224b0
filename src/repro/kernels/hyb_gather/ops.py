from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.hyb_gather.hyb_gather import PAD, hyb_gather_pallas
from repro.kernels.runtime import interpret_mode, map_over_lanes


def hyb_gather(edges: jax.Array, seg_start: jax.Array, degree: jax.Array):
    """Gather each active vertex's neighbour window (zero-copy engine).
    Returns (a, PAD, c); lanes past the vertex degree are zeroed.
    Vertices with degree > PAD are split by the scheduler upstream.
    An empty frontier (``a == 0``) returns the empty (0, PAD, c) tensor
    without launching the kernel (a 0-step grid has nothing to DMA)."""
    squeeze = False
    if edges.ndim == 1:
        edges, squeeze = edges[:, None], True
    if seg_start.shape[0] == 0:
        out = jnp.zeros((0, PAD, edges.shape[1]), edges.dtype)
    else:
        gather = functools.partial(hyb_gather_pallas, interpret=interpret_mode())
        out = map_over_lanes(gather)(edges, seg_start, degree)
    return out[..., 0] if squeeze else out
