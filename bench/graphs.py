"""The benchmark's graphs, generated apart from the program's code.

A configuration's ``generator`` entry names its generator,
``generators/<kind>.py``; both of today's follow the GAP Benchmark
Suite (Beamer, Asanović, Patterson, arXiv:1508.03619), whose "kron"
and "urand" inputs are generated, not downloaded.

An ``EdgeList`` holds the *input* edges: ``edgefactor << scale`` tuples,
duplicates and self-loops kept, as Graph500 counts them for TEPS.  The
graph handed to the program is undirected, so every input edge is stored
in both directions with one weight; weights are integers in [1, 255], as
GAP gives its SSSP inputs.
"""

from __future__ import annotations

from dataclasses import dataclass
from pathlib import Path

import numpy as np

from named import BENCH, load


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


def generate(spec: dict, bench: Path = BENCH) -> EdgeList:
    """The input edges a configuration's ``generator`` entry describes:
    its generator's edges, then the weights, from one generator seeded
    by ``spec["seed"]``."""
    rng = np.random.default_rng(spec["seed"])
    src, dst = load("generators", spec["kind"], bench).generate(spec, rng)
    wmin, wmax = spec["weights"]
    weight = rng.integers(wmin, wmax + 1, len(src)).astype(np.float32)
    return EdgeList(n=1 << spec["scale"], src=src, dst=dst, weight=weight)
