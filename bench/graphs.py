"""The benchmark's graph generators, kept apart from the program's.

Both follow the GAP Benchmark Suite (Beamer, Asanović, Patterson,
arXiv:1508.03619), whose "kron" and "urand" inputs are generated, not
downloaded:

* ``kronecker`` is the Graph500 Kronecker generator (A, B, C = 0.57,
  0.19, 0.19; edgefactor 16): every edge picks one quadrant per level,
  then vertex labels are permuted and the edge list shuffled.
* ``uniform`` draws both endpoints of every edge uniformly (GAP urand,
  an Erdős–Rényi graph of the same degree).

An ``EdgeList`` holds the *input* edges: ``edgefactor << scale`` tuples,
duplicates and self-loops kept, as Graph500 counts them for TEPS.  The
graph handed to the program is undirected, so every input edge is stored
in both directions with one weight; weights are integers in [1, 255], as
GAP gives its SSSP inputs.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class EdgeList:
    n: int
    src: np.ndarray     # (m,) int64, input edges
    dst: np.ndarray     # (m,) int64
    weight: np.ndarray  # (m,) float32, integers in [wmin, wmax]

    @property
    def m(self) -> int:
        return len(self.src)

    def directed(self):
        """Both directions of every input edge: (src, dst, weight)."""
        return (np.concatenate([self.src, self.dst]),
                np.concatenate([self.dst, self.src]),
                np.concatenate([self.weight, self.weight]))


def kronecker(scale: int, edgefactor: int, a: float, b: float, c: float,
              rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    """Graph500 reference generator (``kronecker_generator.m``), vectorized
    over edges: level by level, the source bit is 1 with probability
    1 - (a + b), and the destination bit with c / (c + d) or b / (a + b)."""
    n, m = 1 << scale, edgefactor << scale
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


def uniform(scale: int, edgefactor: int, rng: np.random.Generator):
    n, m = 1 << scale, edgefactor << scale
    return rng.integers(0, n, m), rng.integers(0, n, m)


def generate(spec: dict) -> EdgeList:
    """The input edges a configuration's ``generator`` entry describes."""
    rng = np.random.default_rng(spec["seed"])
    kind, scale, ef = spec["kind"], spec["scale"], spec["edgefactor"]
    if kind == "kronecker":
        src, dst = kronecker(scale, ef, spec["a"], spec["b"], spec["c"], rng)
    elif kind == "uniform":
        src, dst = uniform(scale, ef, rng)
    else:
        raise ValueError(f"unknown generator kind {kind!r}")
    wmin, wmax = spec["weights"]
    weight = rng.integers(wmin, wmax + 1, len(src)).astype(np.float32)
    return EdgeList(n=1 << scale, src=src, dst=dst, weight=weight)
