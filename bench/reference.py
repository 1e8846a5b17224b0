"""Plain NumPy references for the benchmark's programs, and their controls.

Each reference is built from the generator's input edges (``EdgeList``)
and nothing the program made.  The graph is undirected: every input edge
is relaxed in both directions.

* ``distances``: synchronous Bellman-Ford in float64, relaxing only the
  out-edges of vertices that improved in the previous round.  With unit
  weights the rounds are BFS levels; with GAP's integer weights every
  distance is an integer, exact in float32 as well.
* ``pagerank``: the fixpoint of r = (1 - d) + d·Aᵀ D⁻¹ r that Δ-PageRank
  converges to (unnormalized, pushed along out-edges, no dangling
  redistribution), iterated in float64 until no rank moves by 1e-9.

The controls are the same computations a step below what the
configuration states: ``rounding=to_bfloat16`` keeps every distance or
rank in bfloat16 (float32 is the program's precision), and
``drop_last_level`` leaves the deepest BFS level unreached, which breaks
the guarantee that a search reaches its whole component (BFS levels are
small integers, exact in any float format, so precision alone cannot
break them).
"""

from __future__ import annotations

from functools import cached_property

import numpy as np

from graphs import EdgeList


def to_bfloat16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return x.astype(ml_dtypes.bfloat16).astype(np.float64)


class Reference:
    def __init__(self, edges: EdgeList):
        self.edges = edges  # for a check that builds a reference of its own
        src, dst, w = edges.directed()
        order = np.argsort(dst, kind="stable")
        self.n = edges.n
        self.src = src[order]
        self.dst = dst[order]
        self.w = w[order].astype(np.float64)
        self.out_degree = np.bincount(src, minlength=self.n)
        # first edge of each destination's run, and that destination
        self.heads = np.flatnonzero(np.r_[True, np.diff(self.dst) != 0])
        self.head_ids = self.dst[self.heads]

    def distances(self, key: int, unit: bool = False, rounding=None) -> np.ndarray:
        dist = np.full(self.n, np.inf)
        dist[key] = 0.0
        changed = np.zeros(self.n, bool)
        changed[key] = True
        while changed.any():
            idx = np.flatnonzero(changed[self.src])
            cand = dist[self.src[idx]] + (1.0 if unit else self.w[idx])
            if rounding is not None:
                cand = rounding(cand)
            heads_at = np.flatnonzero(np.r_[True, np.diff(self.dst[idx]) != 0])
            best = np.minimum.reduceat(cand, heads_at)
            heads = self.dst[idx][heads_at]
            better = best < dist[heads]
            dist[heads[better]] = best[better]
            changed[:] = False
            changed[heads[better]] = True
        return dist

    def push(self, rank: np.ndarray, damping: float) -> np.ndarray:
        """One application of the PageRank operator: (1 - d) + d·Aᵀ D⁻¹ rank."""
        out = np.full(self.n, 1.0 - damping)
        out[self.head_ids] += damping * np.add.reduceat(
            (rank / np.maximum(self.out_degree, 1))[self.src], self.heads)
        return out

    def pagerank(self, damping: float, rounding=None, tol: float = 1e-9,
                 max_iters: int = 2000) -> np.ndarray:
        rank = np.full(self.n, 1.0 - damping)
        for _ in range(max_iters):
            nxt = self.push(rank, damping)
            if rounding is not None:
                nxt = rounding(nxt)
            moved = np.max(np.abs(nxt - rank))
            rank = nxt
            if moved < tol:
                break
        return rank

    @cached_property
    def components(self) -> np.ndarray:
        """Connected-component label of every vertex (its least vertex id)."""
        label = np.arange(self.n)
        while True:
            pulled = np.minimum.reduceat(label[self.src], self.heads)
            nxt = label.copy()
            nxt[self.head_ids] = np.minimum(label[self.head_ids], pulled)
            nxt = nxt[nxt]  # pointer jumping
            if np.array_equal(nxt, label):
                return label
            label = nxt

    @cached_property
    def component_size(self) -> np.ndarray:
        return np.bincount(self.components, minlength=self.n)

    @cached_property
    def component_edges(self) -> np.ndarray:
        """Input edges per component: each is stored in both directions."""
        return np.bincount(self.components[self.src], minlength=self.n) // 2


def drop_last_level(levels: np.ndarray) -> np.ndarray:
    out = levels.copy()
    finite = np.isfinite(out)
    if finite.any():
        out[out == out[finite].max()] = np.inf
    return out
