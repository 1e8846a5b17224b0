"""Search keys as the Graph500 spec samples them: distinct vertices with
an edge to another vertex, drawn from ``--seed``."""

import numpy as np

from loadgen import rng_for


def draw(traffic, edges, seed):
    count = traffic["key_pool"] + 1
    loops = edges.src == edges.dst
    candidates = np.unique(np.concatenate([edges.src[~loops], edges.dst[~loops]]))
    picked = rng_for(seed, "keys").choice(
        candidates, min(count, len(candidates)), replace=False)
    return [int(k) for k in picked]
