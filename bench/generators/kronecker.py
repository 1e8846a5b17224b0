"""GAP "kron": the Graph500 Kronecker generator (``kronecker_generator.m``;
A, B, C = 0.57, 0.19, 0.19 in GAP's configuration), vectorized over
edges.  Level by level every edge picks a quadrant: its source bit is 1
with probability 1 - (a + b), its destination bit with c / (c + d) or
b / (a + b).  Then vertex labels are permuted and the edge list
shuffled."""

import numpy as np


def generate(spec, rng):
    scale, a, b, c = spec["scale"], spec["a"], spec["b"], spec["c"]
    n, m = 1 << scale, spec["edgefactor"] << scale
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for level in range(scale):
        src_bit = rng.random(m, dtype=np.float32) > ab
        dst_bit = rng.random(m, dtype=np.float32) > np.where(src_bit, c_norm, a_norm)
        src |= src_bit.astype(np.int64) << level
        dst |= dst_bit.astype(np.int64) << level
    perm = rng.permutation(n)
    src, dst = perm[src], perm[dst]
    order = rng.permutation(m)
    return src[order], dst[order]
