"""Edge-update batches over the input graph, in KickStarter's streaming
model (Vora, Gupta, Xu, ASPLOS 2017): each key is one batch of the
traffic's ``batch.deletes`` deletions and ``batch.inserts`` insertions
of undirected input edges, each applied as its two directed ops, in an
order shuffled from the seed.  A key carries its position in the stream
and the stream, whose ``source`` (one vertex of the input's largest
component) stays fixed for every batch, the warm-up's included.

Every batch is drawn up front from ``--seed`` and the input edges alone:

* deletions: uniform over the input edges that are not self-loops and
  whose unordered pair occurs once in the input, none twice in the
  stream, so "delete the first live (u, v)" names one edge;
* insertions: each endpoint drawn from the 2m endpoint slots of the
  input edges (degree-proportional, so hubs get their share), weights
  integers in ``weights``; no self-loop, and no pair the stream deletes.

``Stream.live(i)`` replays the graph that batches 0..i leave, in NumPy,
and ``source_component(i)`` sizes the source's component there; the
stream's check and coverage read both.  (No ``from __future__ import
annotations``: the dataclasses below are made in a module that
``named.load`` does not enter in ``sys.modules``.)"""

from dataclasses import dataclass, field

import numpy as np

from graphs import EdgeList
from loadgen import rng_for


def components(n: int, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """Connected-component label of every vertex of an undirected graph."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    adj = coo_matrix((np.ones(len(src), np.int8), (src, dst)), shape=(n, n)).tocsr()
    return connected_components(adj, directed=False)[1]


def pair_codes(n: int, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """One number per unordered vertex pair."""
    return np.minimum(src, dst) * n + np.maximum(src, dst)


@dataclass(eq=False)
class Stream:
    edges: EdgeList      # the input edges
    source: int
    deleted: np.ndarray  # (batches, deletes) input edge indices
    ins_src: np.ndarray  # (batches, inserts)
    ins_dst: np.ndarray
    ins_w: np.ndarray
    order: np.ndarray    # (batches, 2 * (deletes + inserts)) directed ops
    _components: dict = field(default_factory=dict, repr=False)

    def ops(self, i: int):
        """Batch ``i`` as directed ops in stream order: (insert flag, src,
        dst, weight); a deletion's weight is 0."""
        e, d = self.edges, self.deleted[i]
        u, v, w = self.ins_src[i], self.ins_dst[i], self.ins_w[i]
        insert = np.r_[np.zeros(2 * len(d), bool), np.ones(2 * len(u), bool)]
        src = np.r_[e.src[d], e.dst[d], u, v]
        dst = np.r_[e.dst[d], e.src[d], v, u]
        weight = np.r_[np.zeros(2 * len(d), np.float32), w, w]
        o = self.order[i]
        return insert[o], src[o], dst[o], weight[o]

    def live(self, i: int) -> EdgeList:
        """The input edges less the deletions and plus the insertions of
        batches 0..i (the input itself for i = -1)."""
        e, k = self.edges, i + 1
        keep = np.ones(e.m, bool)
        keep[self.deleted[:k].ravel()] = False
        return EdgeList(n=e.n,
                        src=np.r_[e.src[keep], self.ins_src[:k].ravel()],
                        dst=np.r_[e.dst[keep], self.ins_dst[:k].ravel()],
                        weight=np.r_[e.weight[keep], self.ins_w[:k].ravel()])

    def source_component(self, i: int) -> tuple[int, int]:
        """(vertices, input edges with duplicates and self-loops) of the
        source's component in ``live(i)``."""
        if i not in self._components:
            g = self.live(i)
            label = components(g.n, g.src, g.dst)
            mine = label == label[self.source]
            self._components[i] = (int(mine.sum()), int(mine[g.src].sum()))
        return self._components[i]


@dataclass(frozen=True)
class Batch:
    stream: Stream
    index: int

    @property
    def source(self) -> int:
        return self.stream.source


def draw(traffic, edges, seed):
    rng = rng_for(seed, "stream")
    count = traffic["key_pool"] + 1
    n_del, n_ins = traffic["batch"]["deletes"], traffic["batch"]["inserts"]
    n, src, dst = edges.n, edges.src, edges.dst

    label = components(n, src, dst)
    largest = np.flatnonzero(label == np.argmax(np.bincount(label)))
    source = int(rng.choice(largest))

    pair = pair_codes(n, src, dst)
    _, at, times = np.unique(pair, return_inverse=True, return_counts=True)
    eligible = np.flatnonzero((times[at] == 1) & (src != dst))
    if len(eligible) < count * n_del:
        raise ValueError(f"the stream deletes {count * n_del} edges; the input "
                         f"has {len(eligible)} that may be deleted")
    deleted = rng.choice(eligible, count * n_del, replace=False).reshape(count, n_del)

    dead = np.unique(pair[deleted.ravel()])
    slots = np.r_[src, dst]
    u = np.empty(count * n_ins, np.int64)
    v = np.empty(count * n_ins, np.int64)
    todo = np.arange(count * n_ins)
    while len(todo):
        a = slots[rng.integers(0, len(slots), len(todo))]
        b = slots[rng.integers(0, len(slots), len(todo))]
        ok = (a != b) & ~np.isin(pair_codes(n, a, b), dead)
        u[todo[ok]], v[todo[ok]] = a[ok], b[ok]
        todo = todo[~ok]
    wmin, wmax = traffic["weights"]
    w = rng.integers(wmin, wmax + 1, count * n_ins).astype(np.float32)

    width = 2 * (n_del + n_ins)
    order = rng.permuted(np.tile(np.arange(width, dtype=np.int32), (count, 1)), axis=1)
    stream = Stream(edges, source, deleted, u.reshape(count, n_ins),
                    v.reshape(count, n_ins), w.reshape(count, n_ins), order)
    return [Batch(stream, i) for i in range(count)]
