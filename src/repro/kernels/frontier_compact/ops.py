from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.frontier_compact.frontier_compact import frontier_compact_pallas
from repro.kernels.runtime import interpret_mode, map_over_lanes


def frontier_compact(values: jax.Array, mask: jax.Array):
    """Compact rows of ``values`` where ``mask`` is set to a dense prefix.
    Returns (compacted (m, c), count).  ``count == 0`` (empty frontier)
    is well-defined: the output tail is unspecified, the count is 0."""
    squeeze = False
    if values.ndim == 1:
        values, squeeze = values[:, None], True
    if values.shape[0] == 0:
        # zero rows: the (TILE,)-blocked grid cannot slice an empty
        # operand, and a 0-step grid would leave the count uninitialized.
        out, cnt = values, jnp.int32(0)
    else:
        compact = functools.partial(frontier_compact_pallas,
                                    interpret=interpret_mode())
        out, cnt = map_over_lanes(compact)(values, mask)
    return (out[:, 0] if squeeze else out), cnt
