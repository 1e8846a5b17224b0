"""Versioned dynamic-graph container over ``CSRGraph``/``DeviceCSR``.

Streaming workloads mutate the edge set in small batches; rebuilding the
CSR + partition layout + device buffers per batch would cost more than
the recomputation it unlocks.  ``DeltaCSR`` instead keeps the partition
edge-block layout (core/partition.py) *fixed between merges* and treats
each partition's edge range as a log-structured segment:

* every partition gets ``slack`` spare lanes at build time — its live
  edges occupy a dense prefix of a fixed-capacity block (the per-partition
  edge log);
* **insert** appends into the partition of the edge's source vertex
  (partition boundaries are vertex-aligned, so the source's partition is
  the only legal home);
* **delete** swap-removes within the block (combiners are commutative, so
  intra-partition edge order is free) — the live prefix stays dense and
  the sweep's ``local < part_edges[p]`` masking needs no tombstones;
* **reweight** patches the weight lane in place.

Device buffers are *patched* (one scatter over the touched lanes + the
(P,) live-count and (n,) degree vectors), never rebuilt — shapes are
static between merges so ``hytm_chunk`` keeps its compiled sweep.
When a partition's block overflows, a **merge-compaction** folds the log
into a fresh CSR, re-partitions, and re-uploads (``layout_version`` bump).

Versioning contract (consumed by repro.stream.service's result cache):
``version`` bumps once per applied batch; a result computed at version v
is valid iff the container is still at v.  ``dirty_partitions`` in each
``UpdateReport`` names the blocks a batch touched — the granularity at
which Totem-style hybrid systems track what an update dirties.

Host cost of a batch: ``apply`` reads the live prefix of each block that
holds one of the batch's sources once, in NumPy, and keeps from it a
batch-local lane index (``_LaneIndex``): every lane whose source the
batch names, and for each (src, dst) pair the batch names its live
lanes in ascending order.  Validation, the per-op slot lookups and the
``pre_adj``/``post_adj`` snapshots all read that index, so the Python
work per batch is per op, not per lane of a hub's adjacency.

Tracing (``repro.obs``): ``apply`` is the host span ``stream.apply``
(stats ``ops``, ``touched`` lanes, ``merged``, ``scanned`` log lanes
read), with ``stream.merge`` around a merge-compaction; the scatter of
the touched lanes runs in the device scope ``stream.patch``.
"""

from __future__ import annotations

import bisect
import dataclasses
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cost_model import zc_request_counts
from repro.core.hytm import HyTMConfig, Runtime
from repro.core.partition import DevicePartitions, PartitionTable, partition_graph
from repro.graph.algorithms import VertexProgram
from repro.graph.csr import CSRGraph, DeviceCSR, csr_from_edges
from repro.obs import scopes
from repro.obs.trace import set_stats, span

OP_INSERT, OP_DELETE, OP_REWEIGHT = 0, 1, 2

# the recorder track of the container's host spans
_TRACK = "stream"


@jax.jit
def _scatter_lanes(edge_src, edge_dst, edge_weight, edge_valid,
                   idx, src, dst, w, valid):
    """Write a batch's touched lanes into the four device edge arrays."""
    with scopes.scope(scopes.STREAM_PATCH):
        return (edge_src.at[idx].set(src), edge_dst.at[idx].set(dst),
                edge_weight.at[idx].set(w), edge_valid.at[idx].set(valid))


class InvalidBatchError(ValueError):
    """An ``EdgeBatch`` failed validation; the whole batch was rejected
    atomically — no host-log or device-buffer mutation happened and
    ``version`` did not move.  ``index`` is the offending entry."""

    def __init__(self, msg: str, index: int | None = None):
        super().__init__(msg if index is None
                         else f"batch entry {index}: {msg}")
        self.index = index


@dataclass
class EdgeBatch:
    """One update batch: parallel arrays of (op, src, dst, weight).

    ``weight`` is the new weight for INSERT/REWEIGHT and ignored for
    DELETE.  Ops apply in order (multigraph semantics: INSERT always adds
    a parallel edge; DELETE/REWEIGHT match the first live (src, dst))."""

    op: np.ndarray
    src: np.ndarray
    dst: np.ndarray
    weight: np.ndarray

    def __post_init__(self):
        self.op = np.asarray(self.op, dtype=np.int32)
        self.src = np.asarray(self.src, dtype=np.int64)
        self.dst = np.asarray(self.dst, dtype=np.int64)
        self.weight = np.asarray(self.weight, dtype=np.float32)
        if not (self.op.shape == self.src.shape == self.dst.shape
                == self.weight.shape):
            # raised (not asserted): a ragged batch under ``python -O``
            # would silently pair ops with the wrong endpoints
            raise ValueError(
                "EdgeBatch fields must be parallel arrays; got shapes "
                f"op={self.op.shape} src={self.src.shape} "
                f"dst={self.dst.shape} weight={self.weight.shape}")

    def __len__(self) -> int:
        return len(self.op)

    @classmethod
    def inserts(cls, src, dst, weight) -> "EdgeBatch":
        src = np.asarray(src)
        return cls(np.full(len(src), OP_INSERT), src, dst, weight)

    @classmethod
    def deletes(cls, src, dst) -> "EdgeBatch":
        src = np.asarray(src)
        return cls(
            np.full(len(src), OP_DELETE), src, dst, np.zeros(len(src), np.float32)
        )


@dataclass
class UpdateReport:
    """What one ``apply`` did — everything the incremental layer needs.

    REWEIGHT is reported as delete(old weight) + insert(new weight) so the
    seeding rules (repro.stream.incremental) see one uniform op algebra.
    ``pre_adj``/``post_adj`` snapshot the out-adjacency (dsts, weights) of
    every affected source vertex before/after the batch — the SUM-program
    correction deltas are computed from exactly these."""

    version: int
    dirty_partitions: np.ndarray
    merged: bool
    ins_src: np.ndarray
    ins_dst: np.ndarray
    ins_w: np.ndarray
    del_src: np.ndarray
    del_dst: np.ndarray
    del_w: np.ndarray
    pre_adj: dict[int, tuple[np.ndarray, np.ndarray]] = field(default_factory=dict)
    post_adj: dict[int, tuple[np.ndarray, np.ndarray]] = field(default_factory=dict)

    @property
    def affected_vertices(self) -> np.ndarray:
        """Sources/destinations of changed edges (frontier seed set)."""
        return np.unique(
            np.concatenate([self.ins_src, self.ins_dst, self.del_src, self.del_dst])
        )


@dataclass
class _LaneIndex:
    """One batch's view of the edge log, read before any mutation.

    ``lanes`` holds every live block lane whose source is one of the
    batch's ``sources`` (sorted, unique; ``is_src`` marks them), grouped
    by that source (``src``, ascending) with ascending lanes within a
    source; ``scanned`` counts the live lanes read to find them.
    ``codes`` names each op's (src, dst) pair as ``src * n + dst``, and
    ``slots`` maps each named pair to its live block lanes in ascending
    order — the first is the pair's "first live" copy — which ``apply``
    keeps true as it inserts and swap-removes."""

    sources: np.ndarray
    is_src: np.ndarray
    scanned: int
    lanes: np.ndarray
    src: np.ndarray
    codes: np.ndarray
    slots: dict[int, list[int]]


class DeltaCSR:
    """Mutable, versioned graph with a ``hytm_chunk``-compatible runtime.

    The vertex set is fixed at construction (updates are edge-only).
    Invariants between merge-compactions:

      * partition p's live edges are ``_src/_dst/_w[p*B : p*B + counts[p]]``
        (B = ``block_size``, uniform block capacity);
      * device arrays mirror the host log exactly (patched per batch) —
        including every registered *sharded* view
        (``sharded_runtime_for``): the same lanes scatter into the
        device-sharded (P_pad, B) grid, so the mesh sees the same edge
        multiset as the single device at every version;
      * ``seg_start`` (per-vertex segment starts, feeding the zero-copy
        alignment term of Eq. 3): with ``refresh_seg_start=True`` (the
        default) dirty partitions re-derive it on every patch from the
        live-degree prefix-sum, tracking the layout the next merge will
        realize; ``refresh_seg_start=False`` keeps the historical
        frozen-at-last-merge approximation, whose alignment term drifts
        as deletes accumulate (the request-count base uses the live
        out-degrees and stays exact either way).
    """

    def __init__(self, g: CSRGraph, config: HyTMConfig | None = None,
                 slack: float = 0.5, min_slack: int = 128,
                 refresh_seg_start: bool = True):
        self.config = config if config is not None else HyTMConfig()
        self.n_nodes = g.n_nodes
        self.slack = slack
        self.min_slack = min_slack
        # True (default): recompute the per-vertex ``seg_start`` of dirty
        # partitions on every patch (a prefix-sum over live degrees), so
        # the Eq. 3 zero-copy alignment term tracks the layout the next
        # merge-compaction will realize instead of drifting as deletes
        # accumulate.  False keeps the historical frozen-at-last-merge
        # approximation (tests/test_stream.py quantifies the drift).
        self.refresh_seg_start = refresh_seg_start
        self.version = 0
        self.layout_version = 0
        self.dirty: set[int] = set()  # dirty partitions since last merge
        # bounded batch_id -> UpdateReport memory for idempotent
        # redelivery (exactly-once apply under at-least-once delivery)
        self._applied: dict = {}
        self.dedup_window = 64
        self._inv_deg_cache: dict[bool, jnp.ndarray] = {}
        # shared across the Runtime views runtime_for builds, so the
        # chunked driver's per-(program, config, shapes) eval_shape
        # results survive across queries (keys carry the shapes — safe
        # through merge-compaction re-blocking)
        self._info_shape_cache: dict = {}
        # sharded (P, B) grid views (graph_shard.ShardedRuntime), keyed by
        # (axis, device ids, weighted-norm flag); patched in lock-step
        # with the single-device buffers and rebuilt on merge-compaction
        self._sharded_views: dict[tuple, Any] = {}
        self._build_layout(g)

    # ------------------------------------------------------------ construction
    @classmethod
    def from_graph(cls, g: CSRGraph, config: HyTMConfig | None = None,
                   **kw) -> "DeltaCSR":
        return cls(g, config, **kw)

    def _build_layout(self, g: CSRGraph) -> None:
        cfg = self.config
        table: PartitionTable = partition_graph(
            g, n_partitions=cfg.n_partitions,
            partition_bytes=cfg.partition_bytes, d1=cfg.link.d1,
        )
        P = table.n_partitions
        epp = table.edges_per_partition
        max_epp = int(epp.max(initial=1))
        B = max_epp + max(self.min_slack, int(np.ceil(max_epp * self.slack)))
        B = max(128, -(-B // 128) * 128)
        cap = P * B

        src = np.zeros(cap, np.int32)
        dst = np.zeros(cap, np.int32)
        w = np.full(cap, np.float32(np.inf), np.float32)
        valid = np.zeros(cap, bool)
        src_all = g.edge_sources()
        dst_all = g.indices
        w_all = g.weights if g.weights is not None else np.ones(g.n_edges, np.float32)
        counts = epp.astype(np.int64)
        for p in range(P):
            e0, e1 = int(table.edge_start[p]), int(table.edge_start[p + 1])
            k = e1 - e0
            src[p * B:p * B + k] = src_all[e0:e1]
            dst[p * B:p * B + k] = dst_all[e0:e1]
            w[p * B:p * B + k] = w_all[e0:e1]
            valid[p * B:p * B + k] = True

        part_id = np.repeat(
            np.arange(P, dtype=np.int32), table.vertices_per_partition
        )
        # per-vertex segment start relocated into the blocked layout
        seg_start = (
            part_id.astype(np.int64) * B
            + g.indptr[:-1] - table.edge_start[part_id]
        )

        self._src, self._dst, self._w, self._valid = src, dst, w, valid
        self.counts = counts
        self.block_size = B
        self.n_partitions = P
        self.vertex_start = table.vertex_start
        self.vertex_part = part_id
        self.out_deg = g.out_degrees.copy()
        self._seg_start_host = seg_start

        cap_start = np.arange(P + 1, dtype=np.int64) * B
        self.parts = DevicePartitions(
            vertex_start=jnp.asarray(table.vertex_start, jnp.int32),
            edge_start=jnp.asarray(cap_start, jnp.int32),
            part_edges=jnp.asarray(counts, jnp.int32),
            vertex_part_id=jnp.asarray(part_id),
            n_partitions=P,
            block_size=B,
        )
        self.csr = DeviceCSR(
            edge_src=jnp.asarray(src),
            edge_dst=jnp.asarray(dst),
            edge_weight=jnp.asarray(w),
            edge_valid=jnp.asarray(valid),
            out_degree=jnp.asarray(self.out_deg, jnp.int32),
            seg_start=jnp.asarray(seg_start, jnp.int32),
            n_nodes=self.n_nodes,
            n_edges=int(counts.sum()),  # live count at last merge
        )
        self.zc_req = zc_request_counts(
            self.csr.out_degree, self.csr.seg_start, self.config.link
        )
        self._inv_deg_cache.clear()
        # merge-compaction re-blocks the grid: re-upload every sharded
        # view from the fresh layout (per device, via the row sharding)
        # and drop its compiled sweeps — the static partition grid the
        # cached closures were built around may have moved.  Owner-layout
        # views also rebuild their halo plan here (the layout_version
        # bump moved the edge blocks, so the boundary sets moved too).
        for key, rt in self._sharded_views.items():
            self._refill_sharded_view(rt, key[2])
            rt.iteration_cache.clear()

    # ------------------------------------------------------------- inspection
    @property
    def n_edges(self) -> int:
        return int(self.counts.sum())

    def live_edges(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(src, dst, weight) of the current edge multiset (host views)."""
        mask = self._valid
        return self._src[mask], self._dst[mask], self._w[mask]

    def to_host_graph(self) -> CSRGraph:
        """Materialize the current edge set as a fresh ``CSRGraph`` (the
        from-scratch oracle the equivalence tests recompute on)."""
        s, d, w = self.live_edges()
        return csr_from_edges(self.n_nodes, s.astype(np.int64),
                              d.astype(np.int64), w)

    def _source_lanes(self, parts: np.ndarray,
                      is_src: np.ndarray) -> np.ndarray:
        """The live lanes of ``parts``' blocks whose source ``is_src``
        marks, ascending: one NumPy pass over each block's live prefix."""
        B = self.block_size
        found = [np.zeros(0, np.int64)]
        for p in parts.tolist():
            lo = p * B
            live = self._src[lo:lo + int(self.counts[p])]
            found.append(lo + np.flatnonzero(is_src[live]))
        return np.concatenate(found)

    def _lane_index(self, batch: EdgeBatch) -> _LaneIndex:
        """Read the log once for ``batch`` (its endpoints in range): the
        lanes of its sources, grouped, and the live lanes of each pair it
        names."""
        n = self.n_nodes
        sources = np.unique(batch.src)
        is_src = np.zeros(n, bool)
        is_src[sources] = True
        parts = np.unique(self.vertex_part[sources])
        lanes = self._source_lanes(parts, is_src)
        src = self._src[lanes]
        order = np.argsort(src, kind="stable")
        lanes, src = lanes[order], src[order]
        dst = self._dst[lanes]
        codes = batch.src * n + batch.dst
        named = np.unique(codes)
        pair_src, pair_dst = np.divmod(named, n)
        pair_src = pair_src.astype(src.dtype)  # no cast of the haystack
        lo = np.searchsorted(src, pair_src, "left").tolist()
        hi = np.searchsorted(src, pair_src, "right").tolist()
        # within a source's run the lanes ascend, so each list is sorted
        slots = {c: lanes[a:b][dst[a:b] == v].tolist()
                 for c, v, a, b in zip(named.tolist(), pair_dst.tolist(),
                                       lo, hi)}
        return _LaneIndex(sources, is_src, int(self.counts[parts].sum()),
                          lanes, src, codes, slots)

    def _regrouped(self, index: _LaneIndex, touched: set[int]
                   ) -> tuple[np.ndarray, np.ndarray]:
        """``index``'s grouped (lanes, sources) as the log now holds them:
        only ``touched`` lanes changed, so drop those and merge back the
        ones that now hold a live edge of one of the batch's sources."""
        t = np.fromiter(sorted(touched), np.int64, len(touched))
        hit = np.zeros(len(self._src), bool)
        hit[t] = True
        keep = ~hit[index.lanes]
        lanes, src = index.lanes[keep], index.src[keep]
        t = t[self._valid[t] & index.is_src[self._src[t]]]
        ts = self._src[t]
        order = np.argsort(ts, kind="stable")
        t, ts = t[order], ts[order]
        cap = len(self._src)
        at = np.searchsorted(src.astype(np.int64) * cap + lanes,
                             ts.astype(np.int64) * cap + t)
        return np.insert(lanes, at, t), np.insert(src, at, ts)

    def _adjacency(self, lanes: np.ndarray, src: np.ndarray,
                   sources: np.ndarray, extra=None
                   ) -> dict[int, tuple[np.ndarray, np.ndarray]]:
        """Out-adjacency (dsts, weights) of each of ``sources`` (sorted)
        from ``lanes`` grouped by their sources ``src`` (ascending lanes
        within a source): its block lanes in lane order, then its spilled
        ``extra`` edges in the order they spilled."""
        dst, w = self._dst[lanes], self._w[lanes]
        keys = sources.astype(src.dtype)
        lo = np.searchsorted(src, keys, "left").tolist()
        hi = np.searchsorted(src, keys, "right").tolist()
        adj = {u: (dst[a:b], w[a:b])
               for u, a, b in zip(sources.tolist(), lo, hi)}
        spill: dict[int, list] = defaultdict(list)
        for lst in (extra or {}).values():
            for u, v, wt in lst:
                spill[u].append((v, wt))
        for u, ex in spill.items():
            d, ws = adj[u]
            adj[u] = (
                np.concatenate([d, np.array([v for v, _ in ex], d.dtype)]),
                np.concatenate([ws, np.array([x for _, x in ex], np.float32)]))
        return adj

    # ---------------------------------------------------------------- updates
    def validate_batch(self, batch: EdgeBatch) -> _LaneIndex:
        """Reject a malformed batch *before any mutation*: unknown ops,
        negative/out-of-range endpoints, non-finite weights on
        INSERT/REWEIGHT, and delete-of-absent-edge (checked against the
        live multiset with the batch's own earlier entries applied, so
        insert-then-delete within one batch is legal).  Raises
        :class:`InvalidBatchError`; on return ``apply`` is guaranteed to
        succeed without partial effects.  Returns the batch's lane index,
        which ``apply`` goes on to use."""
        n = self.n_nodes
        bad = np.nonzero(~np.isin(batch.op, (OP_INSERT, OP_DELETE,
                                             OP_REWEIGHT)))[0]
        if bad.size:
            i = int(bad[0])
            raise InvalidBatchError(f"unknown op {int(batch.op[i])}", i)
        bad = np.nonzero((batch.src < 0) | (batch.src >= n)
                         | (batch.dst < 0) | (batch.dst >= n))[0]
        if bad.size:
            i = int(bad[0])
            raise InvalidBatchError(
                f"edge endpoint out of range: ({int(batch.src[i])}, "
                f"{int(batch.dst[i])}) with n_nodes={n} (vertex set is "
                "fixed)", i)
        writes = (batch.op == OP_INSERT) | (batch.op == OP_REWEIGHT)
        bad = np.nonzero(writes & ~np.isfinite(batch.weight))[0]
        if bad.size:
            i = int(bad[0])
            raise InvalidBatchError(
                f"non-finite weight {float(batch.weight[i])}", i)
        index = self._lane_index(batch)
        # delete-of-absent: walk the batch against the live multiset
        # counts of the (u, v) pairs it names — mirrors apply's multigraph
        # semantics (DELETE matches one live parallel copy; REWEIGHT of an
        # absent edge degenerates to an insert)
        counts = {c: len(lanes) for c, lanes in index.slots.items()}
        for i, (o, c) in enumerate(zip(batch.op.tolist(),
                                       index.codes.tolist())):
            if o == OP_INSERT:
                counts[c] += 1
            elif o == OP_DELETE:
                if counts[c] <= 0:
                    raise InvalidBatchError(
                        f"delete of absent edge ({int(batch.src[i])}, "
                        f"{int(batch.dst[i])})", i)
                counts[c] -= 1
            elif counts[c] == 0:
                counts[c] = 1  # reweight-of-absent inserts
        return index

    def apply(self, batch: EdgeBatch, batch_id=None,
              faults=None, obs=None) -> UpdateReport:
        """Apply one batch; patch device buffers (or merge-compact on
        overflow); bump ``version``; return the report.

        Sharded equivalence guarantee: registered sharded views
        (``sharded_runtime_for``) are patched in the same step — inserts,
        deletes, and reweights scatter into the device-sharded (P_pad, B)
        grid without re-blocking, and a merge-compaction re-partitions
        and re-uploads them per device.  After any sequence of ``apply``
        calls, a warm-started sharded run over the view is bit-identical
        to the warm-started single-device ``async_sweep=False`` run for
        min-combine programs (values, iterations, transfer accounting,
        engine picks) and tolerance-bounded for sum-combine — the
        contract ``tests/test_stream_sharded.py`` enforces.

        Atomicity: :meth:`validate_batch` runs first, so a batch that
        would fail (bad op, out-of-range endpoint, NaN weight,
        delete-of-absent) raises :class:`InvalidBatchError` with **zero
        side effects** — no host-log entry, no device patch, no version
        bump.

        ``batch_id`` (optional) makes delivery idempotent: an id seen
        before returns the original :class:`UpdateReport` without
        re-applying (redelivered batches must not double-apply — the
        ``resilience.supervisor.deliver_update`` contract).  ``faults``
        injects delivery drops (site ``update_delivery``): a dropped
        batch raises ``UpdateLost`` before validation, exactly as if it
        never arrived.

        The call is the host span ``stream.apply`` (stats: ``ops``, the
        batch's length; ``touched``, the lanes written; ``merged``;
        ``scanned``, the live lanes of the blocks that hold the batch's
        sources, which its lane index read), with ``stream.merge``
        around a merge-compaction.  ``obs``, an optional
        ``repro.obs.TraceRecorder``, records them too; ``obs=None``
        changes nothing else."""
        with span("stream.apply", obs, track=_TRACK, ops=len(batch)) as opened:
            report, touched, scanned = self._apply(batch, batch_id, faults, obs)
            set_stats(opened, touched=touched, merged=report.merged,
                      scanned=scanned)
        return report

    def _apply(self, batch, batch_id, faults, obs
               ) -> tuple[UpdateReport, int, int]:
        """``apply``'s body: the report, the number of lanes written and
        the number of live lanes the lane index read."""
        if batch_id is not None and batch_id in self._applied:
            return self._applied[batch_id], 0, 0
        if faults is not None and faults.fire("update_delivery") == "drop":
            from repro.resilience.faults import UpdateLost

            raise UpdateLost("update_delivery", 0,
                             f"injected drop of batch {batch_id!r}")
        index = self.validate_batch(batch)
        pre_adj = self._adjacency(index.lanes, index.src, index.sources)

        slots = index.slots
        touched: set[int] = set()
        dirty: set[int] = set()
        extra: dict[int, list] = defaultdict(list)
        ins_rec: list[tuple] = []
        del_rec: list[tuple] = []

        for o, u, v, wt, c in zip(batch.op.tolist(), batch.src.tolist(),
                                  batch.dst.tolist(), batch.weight.tolist(),
                                  index.codes.tolist()):
            p = int(self.vertex_part[u])
            dirty.add(p)
            if o == OP_INSERT:
                self._insert(u, v, wt, p, touched, extra, slots[c])
                ins_rec.append((u, v, wt))
            elif o == OP_DELETE:
                old = self._delete(u, v, p, touched, extra, slots, c)
                if old is not None:
                    del_rec.append((u, v, old))
            elif o == OP_REWEIGHT:
                old = self._reweight(u, v, wt, p, touched, extra, slots[c])
                if old is None:  # absent edge: reweight degenerates to insert
                    self._insert(u, v, wt, p, touched, extra, slots[c])
                else:
                    del_rec.append((u, v, old))
                ins_rec.append((u, v, wt))
            else:
                raise ValueError(f"unknown op {o}")

        post_adj = self._adjacency(*self._regrouped(index, touched),
                                   index.sources, extra)

        merged = any(extra.values())
        if merged:
            with span("stream.merge", obs, track=_TRACK):
                s, d, w = self.live_edges()
                for p, lst in extra.items():
                    if not lst:
                        continue
                    es = np.array([e[0] for e in lst], np.int64)
                    ed = np.array([e[1] for e in lst], np.int64)
                    ew = np.array([e[2] for e in lst], np.float32)
                    s = np.concatenate([s.astype(np.int64), es])
                    d = np.concatenate([d.astype(np.int64), ed])
                    w = np.concatenate([w, ew])
                self._build_layout(csr_from_edges(self.n_nodes, s, d, w))
            self.layout_version += 1
            self.dirty = set()
            dirty = set(range(self.n_partitions))
        else:
            self._patch_device(touched, dirty)
            self.dirty |= dirty

        self.version += 1

        def _cols(rec, j, dt):
            return np.array([r[j] for r in rec], dtype=dt)

        report = UpdateReport(
            version=self.version,
            dirty_partitions=np.array(sorted(dirty), np.int64),
            merged=merged,
            ins_src=_cols(ins_rec, 0, np.int64),
            ins_dst=_cols(ins_rec, 1, np.int64),
            ins_w=_cols(ins_rec, 2, np.float32),
            del_src=_cols(del_rec, 0, np.int64),
            del_dst=_cols(del_rec, 1, np.int64),
            del_w=_cols(del_rec, 2, np.float32),
            pre_adj=pre_adj,
            post_adj=post_adj,
        )
        if batch_id is not None:
            self._applied[batch_id] = report
            while len(self._applied) > self.dedup_window:
                self._applied.pop(next(iter(self._applied)))
        return report, len(touched), index.scanned

    def _insert(self, u, v, wt, p, touched, extra, lanes):
        """Append (u, v, wt) to p's block, or spill it; ``lanes`` is the
        pair's slot list."""
        B = self.block_size
        if int(self.counts[p]) < B and not extra.get(p):
            slot = p * B + int(self.counts[p])
            self._src[slot], self._dst[slot] = u, v
            self._w[slot], self._valid[slot] = wt, True
            self.counts[p] += 1
            touched.add(slot)
            lanes.append(slot)  # the block's new highest live lane
        else:
            # block full (or already spilling): spill to the merge log
            extra[p].append((u, v, wt))
        self.out_deg[u] += 1

    def _delete(self, u, v, p, touched, extra, slots, code) -> float | None:
        lanes = slots[code]
        if not lanes:
            for j, (eu, ev, ew) in enumerate(extra.get(p, ())):
                if eu == u and ev == v:
                    extra[p].pop(j)
                    self.out_deg[u] -= 1
                    return float(ew)
            # unreachable after validate_batch (delete-of-absent is
            # rejected up front); kept as a defensive no-op
            return None
        slot = lanes.pop(0)  # the first live (u, v)
        old = float(self._w[slot])
        last = p * self.block_size + int(self.counts[p]) - 1
        if last != slot:
            # ``last`` is the block's highest live lane, so it ends its
            # pair's slot list; it moves down to ``slot``
            moved = slots.get(int(self._src[last]) * self.n_nodes
                              + int(self._dst[last]))
            if moved is not None:
                moved.pop()
                bisect.insort(moved, slot)
        # swap-remove keeps the live prefix dense (edge order is free)
        self._src[slot], self._dst[slot] = self._src[last], self._dst[last]
        self._w[slot] = self._w[last]
        self._src[last], self._dst[last] = 0, 0
        self._w[last], self._valid[last] = np.float32(np.inf), False
        self.counts[p] -= 1
        touched.add(slot)
        touched.add(last)
        self.out_deg[u] -= 1
        return old

    def _reweight(self, u, v, wt, p, touched, extra, lanes) -> float | None:
        if not lanes:
            for j, (eu, ev, ew) in enumerate(extra.get(p, ())):
                if eu == u and ev == v:
                    extra[p][j] = (u, v, wt)
                    return float(ew)
            return None
        slot = lanes[0]  # the first live (u, v)
        old = float(self._w[slot])
        self._w[slot] = wt
        touched.add(slot)
        return old

    def _patch_device(self, touched: set[int], dirty: set[int] = frozenset()) -> None:
        """Scatter the touched lanes + refresh the (P,)/(n,) vectors —
        the 'patched, not rebuilt' contract (shapes never change here).
        Registered sharded views are patched in the same step, so the
        (P, B) grid on the mesh mirrors the single-device buffers at
        every version."""
        idx = None
        if touched:
            idx = np.fromiter(sorted(touched), np.int64, len(touched))
            # pad the scatter index to a power-of-two bucket (repeating the
            # last lane — idempotent for .set) so successive batches of
            # similar size reuse one compiled scatter instead of retracing
            bucket = 1 << int(np.ceil(np.log2(len(idx))))
            idx = np.pad(idx, (0, bucket - len(idx)), mode="edge")
            c = self.csr
            lanes = (c.edge_src, c.edge_dst, c.edge_weight, c.edge_valid,
                     idx.astype(np.int32), self._src[idx], self._dst[idx],
                     self._w[idx], self._valid[idx])
            scopes.register((scopes.STREAM_PATCH, bucket, len(self._src)),
                            _scatter_lanes, *lanes)
            src, dst, w, valid = _scatter_lanes(*lanes)
            self.csr = dataclasses.replace(
                c, edge_src=src, edge_dst=dst, edge_weight=w, edge_valid=valid,
                out_degree=jnp.asarray(self.out_deg, jnp.int32),
            )
        else:
            self.csr = dataclasses.replace(
                self.csr, out_degree=jnp.asarray(self.out_deg, jnp.int32)
            )
        self.parts = dataclasses.replace(
            self.parts, part_edges=jnp.asarray(self.counts, jnp.int32)
        )
        if self.refresh_seg_start:
            # re-derive the ZC alignment base of dirty partitions from the
            # live degree prefix-sum (what the next merge will realize)
            self._refresh_seg_start(dirty)
        # request-count base tracks the live degrees; the alignment term
        # uses the refreshed seg_start (or, with refresh_seg_start=False,
        # the last-merge snapshot — the historical approximation)
        self.zc_req = zc_request_counts(
            self.csr.out_degree, self.csr.seg_start, self.config.link
        )
        self._inv_deg_cache.clear()
        for key, rt in self._sharded_views.items():
            self._patch_sharded_view(rt, key[2], idx)

    def precompile_patch(self, lanes: int) -> None:
        """Compile the patch scatter for a batch that touches ``lanes``
        lanes (its power-of-two bucket), as a long-running service has
        before its first update, by writing the first ``lanes`` lanes
        with what they hold: the edge log, counts and ``version`` stay
        as they are."""
        self._patch_device(set(range(min(lanes, len(self._src)))))

    def _refresh_seg_start(self, dirty) -> None:
        """Recompute ``seg_start`` for ``dirty`` partitions: vertex v's
        segment starts at the partition base plus the summed live degrees
        of the vertices before it — exactly the dense layout the next
        merge-compaction materializes, so the Eq. 3 alignment flags stop
        drifting as swap-removes scramble the block interior.  O(vertices
        of the dirty partitions) on host; uploaded as one (n,) vector."""
        changed = False
        B = self.block_size
        for p in sorted(dirty):
            v0, v1 = int(self.vertex_start[p]), int(self.vertex_start[p + 1])
            if v1 <= v0:
                continue
            deg = self.out_deg[v0:v1].astype(np.int64)
            seg = p * B + np.concatenate(([0], np.cumsum(deg[:-1])))
            if not np.array_equal(seg, self._seg_start_host[v0:v1]):
                self._seg_start_host[v0:v1] = seg
                changed = True
        if changed:
            self.csr = dataclasses.replace(
                self.csr,
                seg_start=jnp.asarray(self._seg_start_host, jnp.int32),
            )

    # ---------------------------------------------------------------- runtime
    def _inv_deg(self, weighted: bool) -> jnp.ndarray:
        inv = self._inv_deg_cache.get(weighted)
        if inv is None:
            if weighted:
                wsum = np.zeros(self.n_nodes, np.float64)
                s, _, w = self.live_edges()
                np.add.at(wsum, s, w.astype(np.float64))
                inv = jnp.asarray(1.0 / np.maximum(wsum, 1e-30), jnp.float32)
            else:
                inv = 1.0 / jnp.maximum(
                    self.csr.out_degree.astype(jnp.float32), 1.0
                )
            self._inv_deg_cache[weighted] = inv
        return inv

    def runtime_for(self, program: VertexProgram) -> Runtime:
        """A ``core.hytm.Runtime`` view of the current version (shared
        device buffers — do not mutate between ``apply`` calls)."""
        weighted = bool(program.use_delta and program.weighted)
        return Runtime(
            csr=self.csr, parts=self.parts, zc_req=self.zc_req,
            inv_deg=self._inv_deg(weighted), n_hub_partitions=0,
            info_shape_cache=self._info_shape_cache,
        )

    # --------------------------------------------------------- sharded runtime
    def sharded_runtime_for(self, program: VertexProgram, mesh=None,
                            axis: str | None = None):
        """A ``graph_shard.ShardedRuntime`` view of the current version:
        the blocked edge log as a (P_pad, B) grid sharded over
        ``config.mesh_axis`` (P_pad pads the partition count up to a
        multiple of the mesh size with empty, accounting-neutral rows).

        The view is registered: every subsequent ``apply`` patches its
        device-sharded buffers by scatter in lock-step with the
        single-device buffers (insert/delete/reweight land without
        re-blocking), and a merge-compaction re-partitions and re-uploads
        it per device (``layout_version`` bump, compiled sweeps dropped).
        Because the partition structure, live counts, and ``seg_start``
        base are *shared* with ``runtime_for``'s view, a sharded run over
        this grid selects the same engines and charges the same transfer
        bytes as the single-device run at every version — the sharded
        warm-start equivalence contract (tests/test_stream_sharded.py).
        """
        axis = axis if axis is not None else self.config.mesh_axis
        if axis is None:
            raise ValueError(
                "no mesh axis: set config.mesh_axis or pass axis= — use "
                "runtime_for() for the single-device view")
        if mesh is None:
            from repro.launch.mesh import make_graph_mesh

            mesh = make_graph_mesh(axis=axis)
        if axis not in mesh.axis_names:
            raise ValueError(
                f"config.mesh_axis={axis!r} is not an axis of the mesh "
                f"(axes: {mesh.axis_names})")
        weighted = bool(program.use_delta and program.weighted)
        # the layout is part of the view identity: owner and replicated
        # views of the same mesh hold differently-padded vectors and
        # differently-placed state, so they specialize separately
        key = (axis, tuple(int(d.id) for d in mesh.devices.flat), weighted,
               self.config.vertex_sharding)
        rt = self._sharded_views.get(key)
        if rt is None:
            from repro.dist.graph_shard import (
                ShardedRuntime, _check_vertex_sharding)

            rt = ShardedRuntime(
                mesh=mesh, axis=axis, blocks=None, parts=None,
                out_degree=None, zc_req=None, inv_deg=None,
                n_nodes=self.n_nodes, n_partitions=0, n_hub_partitions=0,
                vertex_sharding=_check_vertex_sharding(
                    self.config.vertex_sharding),
            )
            self._refill_sharded_view(rt, weighted)
            self._sharded_views[key] = rt
        return rt

    def _padded_vertex_vecs(self, rt, weighted: bool):
        """(out_degree, zc_req, inv_deg) for a sharded view — padded from
        (n,) to (n_pad,) with inert fills under the owner layout (pads
        carry no edges: degree 0, zc 0, inv_deg 1)."""
        out_degree = self.csr.out_degree
        zc_req = self.zc_req
        inv_deg = self._inv_deg(weighted)
        if rt.vertex_sharding == "owner":
            from repro.dist.graph_shard import _pad_vertex_vec

            out_degree = _pad_vertex_vec(out_degree, rt.n_pad, 0)
            zc_req = _pad_vertex_vec(zc_req, rt.n_pad, 0.0)
            inv_deg = _pad_vertex_vec(inv_deg, rt.n_pad, 1.0)
        return out_degree, zc_req, inv_deg

    def _padded_part_id(self, rt, P_pad: int) -> jnp.ndarray:
        """Per-vertex partition ids for a sharded view, padded to
        (n_pad,) under the owner layout (pads park in the last padded
        partition — empty, so stats never count them)."""
        part_id = self.vertex_part
        if rt.vertex_sharding == "owner" and rt.n_pad > self.n_nodes:
            part_id = np.concatenate(
                [part_id,
                 np.full(rt.n_pad - self.n_nodes, P_pad - 1, np.int32)])
        return jnp.asarray(part_id)

    def _grid_arrays(self, n_dev: int):
        """Padded (P_pad, B) host grids of the blocked edge log."""
        P_real, B = self.n_partitions, self.block_size
        P_pad = -(-P_real // n_dev) * n_dev

        def grid(a: np.ndarray, fill) -> np.ndarray:
            out = a.reshape(P_real, B)
            if P_pad != P_real:
                out = np.concatenate(
                    [out, np.full((P_pad - P_real, B), fill, a.dtype)])
            return out

        return P_pad, grid

    def _refill_sharded_view(self, rt, weighted: bool) -> None:
        """(Re-)upload a sharded view from the current host layout — the
        build path and the merge-compaction path (full re-upload per
        device; between merges ``_patch_sharded_view`` scatters)."""
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.dist.graph_shard import BlockedEdges, build_halo_plan

        n_dev = int(rt.mesh.shape[rt.axis])
        P_pad, grid = self._grid_arrays(n_dev)
        src_g, dst_g = grid(self._src, 0), grid(self._dst, 0)
        valid_g = grid(self._valid, False)
        row = NamedSharding(rt.mesh, PartitionSpec(rt.axis, None))
        rep = NamedSharding(rt.mesh, PartitionSpec())
        rt.blocks = BlockedEdges(
            src=jax.device_put(src_g, row),
            dst=jax.device_put(dst_g, row),
            weight=jax.device_put(grid(self._w, np.float32(np.inf)), row),
            in_range=jax.device_put(valid_g, row),
        )
        owner = rt.vertex_sharding == "owner"
        if owner:
            rt.halo = build_halo_plan(src_g, dst_g, valid_g, self.n_nodes,
                                      n_dev)
            rt.n_pad = rt.halo.n_pad
        else:
            rt.halo, rt.n_pad = None, self.n_nodes
        pad = P_pad - self.n_partitions
        vstart = np.concatenate(
            [self.vertex_start, np.full(pad, self.vertex_start[-1])])
        counts = np.concatenate([self.counts, np.zeros(pad, np.int64)])
        cap_start = np.arange(P_pad + 1, dtype=np.int64) * self.block_size
        rt.parts = DevicePartitions(
            vertex_start=jax.device_put(
                jnp.asarray(vstart, jnp.int32), rep),
            edge_start=jax.device_put(jnp.asarray(cap_start, jnp.int32), rep),
            part_edges=jax.device_put(jnp.asarray(counts, jnp.int32), rep),
            vertex_part_id=jax.device_put(
                self._padded_part_id(rt, P_pad), rep),
            n_partitions=P_pad,
            block_size=self.block_size,
        )
        rt.out_degree, rt.zc_req, rt.inv_deg = (
            jax.device_put(v, rep)
            for v in self._padded_vertex_vecs(rt, weighted))
        rt.n_partitions = P_pad

    def _patch_sharded_view(self, rt, weighted: bool,
                            idx: np.ndarray | None) -> None:
        """Scatter the touched lanes into the device-sharded (P_pad, B)
        grid and refresh the replicated (P,)/(n,) vectors — no
        re-blocking, no re-upload of untouched rows.  ``idx`` is the
        (bucket-padded) flat lane index ``_patch_device`` used.  An
        owner-layout view also refreshes its halo plan from the host log
        (moved lanes can add/remove boundary vertices — the plan only
        steers the ICI cost accounting, but it must track the live edge
        set for ``halo_level_cost`` to charge the real boundary)."""
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.dist.graph_shard import BlockedEdges, build_halo_plan

        row = NamedSharding(rt.mesh, PartitionSpec(rt.axis, None))
        rep = NamedSharding(rt.mesh, PartitionSpec())
        if idx is not None:
            B = self.block_size
            rows_, cols_ = idx // B, idx % B
            b = rt.blocks
            rt.blocks = BlockedEdges(
                src=jax.device_put(
                    b.src.at[rows_, cols_].set(self._src[idx]), row),
                dst=jax.device_put(
                    b.dst.at[rows_, cols_].set(self._dst[idx]), row),
                weight=jax.device_put(
                    b.weight.at[rows_, cols_].set(self._w[idx]), row),
                in_range=jax.device_put(
                    b.in_range.at[rows_, cols_].set(self._valid[idx]), row),
            )
        pad = rt.n_partitions - self.n_partitions
        counts = np.concatenate([self.counts, np.zeros(pad, np.int64)])
        rt.parts = dataclasses.replace(
            rt.parts,
            part_edges=jax.device_put(jnp.asarray(counts, jnp.int32), rep),
        )
        if rt.vertex_sharding == "owner" and idx is not None:
            n_dev = int(rt.mesh.shape[rt.axis])
            _, grid = self._grid_arrays(n_dev)
            rt.halo = build_halo_plan(
                grid(self._src, 0), grid(self._dst, 0),
                grid(self._valid, False), self.n_nodes, n_dev)
        rt.out_degree, rt.zc_req, rt.inv_deg = (
            jax.device_put(v, rep)
            for v in self._padded_vertex_vecs(rt, weighted))


def random_batch(
    dcsr: DeltaCSR,
    rng: np.random.Generator,
    n_insert: int = 0,
    n_delete: int = 0,
    n_reweight: int = 0,
    max_weight: float = 64.0,
) -> EdgeBatch:
    """Sample a plausible batch against the current edge set: deletions and
    reweights pick live edges, insertions pick uniform endpoints."""
    ls, ld, _ = dcsr.live_edges()
    ops, src, dst, w = [], [], [], []
    if n_delete or n_reweight:
        k = min(n_delete + n_reweight, len(ls))
        pick = rng.choice(len(ls), size=k, replace=False) if k else []
        for j, e in enumerate(pick):
            is_del = j < min(n_delete, k)
            ops.append(OP_DELETE if is_del else OP_REWEIGHT)
            src.append(int(ls[e]))
            dst.append(int(ld[e]))
            w.append(float(rng.integers(1, max_weight)))
    for _ in range(n_insert):
        ops.append(OP_INSERT)
        src.append(int(rng.integers(0, dcsr.n_nodes)))
        dst.append(int(rng.integers(0, dcsr.n_nodes)))
        w.append(float(rng.integers(1, max_weight)))
    return EdgeBatch(np.array(ops), np.array(src), np.array(dst),
                     np.array(w, np.float32))
