"""repro.stream — container semantics + the incremental equivalence
contract: warm-start recomputation after random insert/delete batches
matches from-scratch ``run_hytm`` on the post-update graph (bit-exact
for MIN programs, tolerance-bounded for SUM), across ≥3 sequential
update batches."""

import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.hytm import HyTMConfig, run_hytm
from repro.graph.algorithms import ALGORITHMS, PAGERANK, SSSP
from repro.graph.generators import rmat_graph
from repro.stream import (
    DeltaCSR,
    EdgeBatch,
    InvalidBatchError,
    random_batch,
    run_incremental,
)

CFG = HyTMConfig(n_partitions=6)
PR = dataclasses.replace(PAGERANK, tolerance=1e-7)


# --------------------------------------------------------------------------
# DeltaCSR container semantics
# --------------------------------------------------------------------------

def _edge_multiset(g_or_dcsr):
    if isinstance(g_or_dcsr, DeltaCSR):
        s, d, w = g_or_dcsr.live_edges()
    else:
        s, d, w = (
            g_or_dcsr.edge_sources(),
            g_or_dcsr.indices,
            g_or_dcsr.weights,
        )
    return sorted(zip(s.tolist(), d.tolist(), w.tolist()))


def test_delta_csr_patch_and_versioning():
    g = rmat_graph(200, 1600, seed=4)
    dc = DeltaCSR(g, CFG)
    assert dc.version == 0 and dc.layout_version == 0
    assert _edge_multiset(dc) == _edge_multiset(g)

    ref = _edge_multiset(g)
    # insert two edges, delete one known edge, reweight another — pick
    # (src, dst) pairs without parallel duplicates so the reference
    # multiset model is unambiguous about which edge the op matched
    from collections import Counter
    pair_counts = Counter((s, d) for s, d, _ in ref)
    uniq = [t for t in ref if pair_counts[(t[0], t[1])] == 1]
    s0, d0, w0 = uniq[0]
    s1, d1, _ = uniq[1]
    batch = EdgeBatch(
        op=np.array([0, 0, 1, 2]),
        src=np.array([5, 9, s0, s1]),
        dst=np.array([6, 2, d0, d1]),
        weight=np.array([3.0, 4.0, 0.0, 9.5], np.float32),
    )
    rep = dc.apply(batch)
    assert dc.version == 1 and not rep.merged and dc.layout_version == 0
    assert set(rep.dirty_partitions) <= set(range(dc.n_partitions))
    ref.remove((s0, d0, w0))
    old = next(t for t in ref if t[0] == s1 and t[1] == d1)
    ref.remove(old)
    ref += [(5, 6, 3.0), (9, 2, 4.0), (s1, d1, 9.5)]
    assert _edge_multiset(dc) == sorted(ref)
    # device mirror agrees with the host log
    assert _edge_multiset(dc.to_host_graph()) == sorted(ref)
    np.testing.assert_array_equal(
        np.asarray(dc.parts.part_edges), dc.counts
    )
    # degrees track the live multiset
    assert int(np.asarray(dc.csr.out_degree)[5]) == sum(
        1 for t in ref if t[0] == 5
    )

    # deleting a non-existent edge is rejected atomically: typed error,
    # no version bump, edge multiset untouched
    with pytest.raises(InvalidBatchError):
        dc.apply(EdgeBatch.deletes([s0], [d0]))
    assert dc.version == 1
    assert _edge_multiset(dc) == sorted(ref)


def test_delta_csr_overflow_merges():
    g = rmat_graph(100, 800, seed=5)
    dc = DeltaCSR(g, HyTMConfig(n_partitions=2), slack=0.0, min_slack=1)
    # flood one source vertex until its partition block overflows
    k = dc.block_size + 8
    batch = EdgeBatch.inserts(
        np.zeros(k, np.int64), np.arange(k) % 100, np.ones(k, np.float32)
    )
    rep = dc.apply(batch)
    assert rep.merged and dc.layout_version == 1
    assert dc.n_edges == 800 + k
    assert len(rep.dirty_partitions) == dc.n_partitions
    # converges correctly on the rebuilt layout
    res = run_hytm(None, SSSP, source=0, config=CFG,
                   runtime=dc.runtime_for(SSSP))
    fs = run_hytm(dc.to_host_graph(), SSSP, source=0, config=CFG)
    np.testing.assert_array_equal(res.values, fs.values)


# --------------------------------------------------------------------------
# apply's batch-local lane index against the per-op scan it replaced
# --------------------------------------------------------------------------

class _ScanLog:
    """The per-op algorithm ``DeltaCSR.apply`` replaced, kept as the
    oracle, on a copy of a container's host log: validation seeds each
    source's (u, v) counts from a walk over its whole live adjacency,
    every DELETE/REWEIGHT scans the block for the first live (u, v), and
    the adjacency snapshots scan the block once per source."""

    def __init__(self, dc):
        self.n, self.B = dc.n_nodes, dc.block_size
        self.part = dc.vertex_part
        self.src, self.dst, self.w, self.valid = (
            a.copy() for a in (dc._src, dc._dst, dc._w, dc._valid))
        self.counts, self.out_deg = dc.counts.copy(), dc.out_deg.copy()

    def out_edges(self, u, extra=None):
        p = int(self.part[u])
        lo = p * self.B
        hi = lo + int(self.counts[p])
        m = self.src[lo:hi] == u
        dsts, ws = self.dst[lo:hi][m].copy(), self.w[lo:hi][m].copy()
        ex = [(v, ew) for (eu, v, ew) in (extra or {}).get(p, ()) if eu == u]
        if ex:
            dsts = np.concatenate([dsts, np.array([v for v, _ in ex], dsts.dtype)])
            ws = np.concatenate([ws, np.array([ew for _, ew in ex], np.float32)])
        return dsts, ws

    def validate(self, batch):
        n = self.n
        bad = np.nonzero(~np.isin(batch.op, (0, 1, 2)))[0]
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
        writes = (batch.op == 0) | (batch.op == 2)
        bad = np.nonzero(writes & ~np.isfinite(batch.weight))[0]
        if bad.size:
            i = int(bad[0])
            raise InvalidBatchError(
                f"non-finite weight {float(batch.weight[i])}", i)
        counts, seeded = {}, set()
        for i in range(len(batch)):
            u, v = int(batch.src[i]), int(batch.dst[i])
            if u not in seeded:
                seeded.add(u)
                for d in self.out_edges(u)[0]:
                    counts[(u, int(d))] = counts.get((u, int(d)), 0) + 1
            o = int(batch.op[i])
            if o == 0:
                counts[(u, v)] = counts.get((u, v), 0) + 1
            elif o == 1:
                c = counts.get((u, v), 0)
                if c <= 0:
                    raise InvalidBatchError(f"delete of absent edge ({u}, {v})", i)
                counts[(u, v)] = c - 1
            elif counts.get((u, v), 0) == 0:
                counts[(u, v)] = 1

    def find_slot(self, u, v, p):
        lo = p * self.B
        hi = lo + int(self.counts[p])
        hits = np.nonzero((self.src[lo:hi] == u) & (self.dst[lo:hi] == v))[0]
        return int(lo + hits[0]) if len(hits) else None

    def insert(self, u, v, wt, p, touched, extra):
        if int(self.counts[p]) < self.B and not extra.get(p):
            slot = p * self.B + int(self.counts[p])
            self.src[slot], self.dst[slot] = u, v
            self.w[slot], self.valid[slot] = wt, True
            self.counts[p] += 1
            touched.add(slot)
        else:
            extra[p].append((u, v, wt))
        self.out_deg[u] += 1

    def delete(self, u, v, p, touched, extra):
        slot = self.find_slot(u, v, p)
        if slot is None:
            for j, (eu, ev, ew) in enumerate(extra.get(p, ())):
                if eu == u and ev == v:
                    extra[p].pop(j)
                    self.out_deg[u] -= 1
                    return float(ew)
            raise AssertionError("validated batches delete live edges")
        old = float(self.w[slot])
        last = p * self.B + int(self.counts[p]) - 1
        self.src[slot], self.dst[slot] = self.src[last], self.dst[last]
        self.w[slot] = self.w[last]
        self.src[last], self.dst[last] = 0, 0
        self.w[last], self.valid[last] = np.float32(np.inf), False
        self.counts[p] -= 1
        touched.update((slot, last))
        self.out_deg[u] -= 1
        return old

    def reweight(self, u, v, wt, p, touched, extra):
        slot = self.find_slot(u, v, p)
        if slot is None:
            for j, (eu, ev, ew) in enumerate(extra.get(p, ())):
                if eu == u and ev == v:
                    extra[p][j] = (u, v, wt)
                    return float(ew)
            return None
        old = float(self.w[slot])
        self.w[slot] = wt
        touched.add(slot)
        return old

    def apply(self, batch):
        """What ``apply`` must do to the log: the report's fields, the
        touched lanes, the spilled edges and the dirty partitions."""
        from collections import defaultdict

        self.validate(batch)
        affected = np.unique(batch.src)
        pre = {int(u): self.out_edges(int(u)) for u in affected}
        touched, dirty, extra = set(), set(), defaultdict(list)
        ins, dels = [], []
        for i in range(len(batch)):
            o, u, v = int(batch.op[i]), int(batch.src[i]), int(batch.dst[i])
            wt, p = float(batch.weight[i]), int(self.part[u])
            dirty.add(p)
            if o == 0:
                self.insert(u, v, wt, p, touched, extra)
                ins.append((u, v, wt))
            elif o == 1:
                dels.append((u, v, self.delete(u, v, p, touched, extra)))
            else:
                old = self.reweight(u, v, wt, p, touched, extra)
                if old is None:
                    self.insert(u, v, wt, p, touched, extra)
                else:
                    dels.append((u, v, old))
                ins.append((u, v, wt))
        post = {int(u): self.out_edges(int(u), extra) for u in affected}
        return {"pre_adj": pre, "post_adj": post, "ins": ins, "dels": dels,
                "touched": touched, "dirty": dirty, "extra": extra}

    def merged_graph(self, extra):
        """The graph a merge-compaction rebuilds from: live log, then spills."""
        from repro.graph.csr import csr_from_edges

        s, d, w = (a[self.valid] for a in (self.src, self.dst, self.w))
        s, d = s.astype(np.int64), d.astype(np.int64)
        for lst in extra.values():
            if lst:
                s = np.concatenate([s, np.array([e[0] for e in lst], np.int64)])
                d = np.concatenate([d, np.array([e[1] for e in lst], np.int64)])
                w = np.concatenate([w, np.array([e[2] for e in lst], np.float32)])
        return csr_from_edges(self.n, s, d, w)


def _same_array(got, want):
    assert got.dtype == want.dtype, (got.dtype, want.dtype)
    np.testing.assert_array_equal(got, want)


def _apply_against_scan(dc, batches):
    """Apply each batch to ``dc`` and to a ``_ScanLog`` of its log; the
    host log, counts, degrees, versions, report and touched lanes (or,
    on a merge, the graph it rebuilds from) must be identical."""
    seen = {}
    patch, build = dc._patch_device, dc._build_layout

    def recording_patch(touched, dirty=frozenset()):
        seen["touched"] = set(touched)
        return patch(touched, dirty)

    def recording_build(g):
        seen["merged_from"] = g
        return build(g)

    dc._patch_device, dc._build_layout = recording_patch, recording_build
    merges = 0
    for batch in batches:
        ref = _ScanLog(dc)
        want = ref.apply(batch)
        version, layout, dirty = dc.version, dc.layout_version, set(dc.dirty)
        seen.clear()
        rep = dc.apply(batch)
        for side in ("pre_adj", "post_adj"):
            got, exp = getattr(rep, side), want[side]
            assert list(got) == list(exp)
            for u in exp:
                for a, b in zip(got[u], exp[u]):
                    _same_array(a, b)
        for name, rec, j, dt in (("ins_src", "ins", 0, np.int64),
                                 ("ins_dst", "ins", 1, np.int64),
                                 ("ins_w", "ins", 2, np.float32),
                                 ("del_src", "dels", 0, np.int64),
                                 ("del_dst", "dels", 1, np.int64),
                                 ("del_w", "dels", 2, np.float32)):
            _same_array(getattr(rep, name),
                        np.array([r[j] for r in want[rec]], dt))
        merged = any(want["extra"].values())
        assert rep.merged == merged and dc.version == version + 1 == rep.version
        if merged:
            merges += 1
            assert "touched" not in seen and dc.layout_version == layout + 1
            g, exp = seen["merged_from"], ref.merged_graph(want["extra"])
            for f in ("indptr", "indices", "weights"):
                _same_array(getattr(g, f), getattr(exp, f))
            _same_array(rep.dirty_partitions, np.arange(dc.n_partitions))
            assert dc.dirty == set()
        else:
            assert seen["touched"] == want["touched"]
            assert dc.layout_version == layout
            for got, exp in ((dc._src, ref.src), (dc._dst, ref.dst),
                             (dc._w, ref.w), (dc._valid, ref.valid),
                             (dc.counts, ref.counts), (dc.out_deg, ref.out_deg)):
                _same_array(got, exp)
            _same_array(rep.dirty_partitions,
                        np.array(sorted(want["dirty"]), np.int64))
            assert dc.dirty == dirty | want["dirty"]
            np.testing.assert_array_equal(np.asarray(dc.csr.edge_src), dc._src)
            np.testing.assert_array_equal(np.asarray(dc.csr.edge_weight), dc._w)
    return merges


def _graph(n, edges):
    from repro.graph.csr import csr_from_edges

    s, d, w = (np.array(c) for c in zip(*edges))
    return csr_from_edges(n, s, d, w.astype(np.float32))


def _ops(*rows):
    """An ``EdgeBatch`` from (op, src, dst, weight) rows."""
    op, s, d, w = zip(*rows)
    return EdgeBatch(np.array(op), np.array(s), np.array(d),
                     np.array(w, np.float32))


def _background(n, k, seed):
    rng = np.random.default_rng(seed)
    return [(int(a), int(b), float(c)) for a, b, c in zip(
        rng.integers(0, n, k), rng.integers(0, n, k), rng.integers(1, 9, k))]


def _parallel_copies():
    # (0, 1) three times with different weights: the lowest lane goes first
    g = _graph(12, [(0, 1, 5.0), (0, 1, 3.0), (0, 1, 7.0), (0, 2, 1.0)]
               + _background(12, 40, 1))
    batches = [_ops((1, 0, 1, 0), (2, 0, 1, 2.0), (1, 0, 1, 0), (0, 0, 1, 9.0),
                    (1, 0, 1, 0), (2, 0, 1, 4.0)),
               _ops((1, 0, 1, 0), (1, 0, 2, 0), (0, 0, 2, 6.0))]
    return DeltaCSR(g, HyTMConfig(n_partitions=2)), batches


def _swap_moves_named_pair():
    # the delete of the block's first lane moves the just-inserted copy
    # of another named pair below that pair's original copy
    dc = DeltaCSR(_graph(16, _background(16, 60, 2)), HyTMConfig(n_partitions=2))
    first = (int(dc._src[0]), int(dc._dst[0]))
    other = next((int(s), int(d)) for s, d in zip(dc._src[1:dc.counts[0]],
                                                  dc._dst[1:dc.counts[0]])
                 if (int(s), int(d)) != first)
    batch = _ops((0, *other, 99.0), (1, *first, 0), (2, *other, 42.0),
                 (1, *other, 0), (1, *other, 0), (0, *other, 11.0),
                 (1, *other, 0))
    return dc, [batch]


def _insert_then_delete():
    dc = DeltaCSR(_graph(16, _background(16, 60, 3)), HyTMConfig(n_partitions=3))
    s, d = int(dc._src[0]), int(dc._dst[0])
    batch = _ops((0, 3, 15, 4.0), (0, 3, 15, 5.0), (1, 3, 15, 0), (1, s, d, 0),
                 (0, s, d, 8.0), (1, s, d, 0), (1, 3, 15, 0))
    return dc, [batch]


def _reweight_absent():
    dc = DeltaCSR(_graph(16, _background(16, 60, 4)), HyTMConfig(n_partitions=2))
    absent = next((u, v) for u in range(16) for v in range(16)
                  if not np.any((dc._src == u) & (dc._dst == v) & dc._valid))
    batch = _ops((2, *absent, 3.0), (2, *absent, 6.0), (0, *absent, 1.0),
                 (1, *absent, 0), (2, *absent, 2.0))
    return dc, [batch]


def _overflow_spills_and_merges():
    # no slack: one source floods its block, spills, deletes and reweights
    # among its spilled edges, and the batch merges
    dc = DeltaCSR(_graph(20, _background(20, 80, 5)), HyTMConfig(n_partitions=2),
                  slack=0.0, min_slack=1)
    room = dc.block_size - int(dc.counts[0])
    u = int(dc._src[0])
    rows = [(0, u, j % 20, float(1 + j % 7)) for j in range(room + 6)]
    rows += [(1, int(dc._src[1]), int(dc._dst[1]), 0), (0, u, 19, 13.0),
             (2, u, 19, 14.0), (1, u, 19, 0), (1, u, 19, 0), (0, u, 3, 2.0)]
    return dc, [_ops(*rows), _ops((1, u, 0, 0), (0, u, 1, 3.0))]


def _spill_undone():
    # a spill deleted within its batch leaves nothing to merge; the
    # block takes inserts again once it has room
    dc = DeltaCSR(_graph(20, _background(20, 80, 6)), HyTMConfig(n_partitions=2),
                  slack=0.0, min_slack=1)
    room = dc.block_size - int(dc.counts[0])
    u, s0, d0 = int(dc._src[0]), int(dc._src[1]), int(dc._dst[1])
    rows = [(0, u, 7, 1.0)] * room + [(0, u, 8, 2.0), (1, s0, d0, 0),
                                      (0, u, 9, 3.0), (1, u, 8, 0), (1, u, 9, 0),
                                      (0, u, 10, 4.0)]
    return dc, [_ops(*rows)]


def _hub_source():
    # vertex 0 holds most of its block: three copies of an edge to every
    # other vertex, so each of its pairs has parallel copies far apart
    n = 64
    hub = [(0, v, float(1 + (v + r) % 5)) for r in range(3) for v in range(1, n)]
    dc = DeltaCSR(_graph(n, hub + _background(n, 30, 7)),
                  HyTMConfig(n_partitions=2), slack=0.1, min_slack=1)
    rng = np.random.default_rng(7)
    batches = []
    for _ in range(3):
        rows = []
        for v in rng.integers(1, n, 12).tolist():
            rows += [(1, 0, v, 0), (0, 0, int(rng.integers(1, n)), 9.0),
                     (2, 0, v, float(rng.integers(1, 9)))]
        batches.append(_ops(*rows))
    return dc, batches


def _random_multigraph(seed):
    """Mixed batches on a small dense multigraph: many parallel copies,
    deletes and reweights of live pairs, reweights of absent ones."""
    n = 24
    rng = np.random.default_rng(seed)
    dc = DeltaCSR(_graph(n, _background(n, 300, seed)),
                  HyTMConfig(n_partitions=3), slack=0.0, min_slack=1)
    batches = []
    live = [(int(s), int(d)) for s, d in zip(*dc.live_edges()[:2])]
    for _ in range(4):
        rows = []
        for _ in range(int(rng.integers(20, 120))):
            kind = int(rng.integers(0, 4))
            if kind == 0 or not live:
                e = (int(rng.integers(0, n)), int(rng.integers(0, 4)))
                rows.append((0, *e, float(rng.integers(1, 9))))
                live.append(e)
            elif kind == 1:
                rows.append((1, *live.pop(int(rng.integers(len(live)))), 0))
            elif kind == 2:
                rows.append((2, *live[int(rng.integers(len(live)))],
                             float(rng.integers(1, 9))))
            else:
                e = (int(rng.integers(0, n)), int(rng.integers(0, n)))
                rows.append((2, *e, float(rng.integers(1, 9))))
                if e not in live:
                    live.append(e)
        batches.append(_ops(*rows))
    return dc, batches


@pytest.mark.parametrize("case", [
    _parallel_copies, _swap_moves_named_pair, _insert_then_delete,
    _reweight_absent, _overflow_spills_and_merges, _spill_undone, _hub_source,
    *(lambda s=s: _random_multigraph(s) for s in range(3)),
], ids=["parallel_copies", "swap_moves_named_pair", "insert_then_delete",
        "reweight_absent", "overflow_spills_and_merges", "spill_undone",
        "hub_source", "random_0", "random_1", "random_2"])
def test_apply_matches_the_per_op_scan(case):
    dc, batches = case()
    merges = _apply_against_scan(dc, batches)
    if case is _overflow_spills_and_merges:
        assert merges == 1
    if case in (_spill_undone, _parallel_copies, _hub_source):
        assert merges == 0


def _hub_graph_container():
    n = 64
    hub = [(0, v, 1.0) for v in range(2, n)] * 2
    return DeltaCSR(_graph(n, hub + _background(n, 30, 8)),
                    HyTMConfig(n_partitions=2))


@pytest.mark.parametrize("rows", [
    [(0, 1, 2, 1.0), (1, 1, 2, 0), (7, 0, 1, 1.0)],
    [(0, 1, 2, 1.0), (0, 1, 64, 1.0), (1, -1, 2, 0)],
    [(0, 1, 2, 1.0), (2, 1, 3, 1.0), (0, 4, 5, float("nan"))],
    [(0, 5, 6, 1.0), (1, 5, 6, 0), (1, 5, 6, 0)],
    [(1, 0, 2, 0), (1, 0, 2, 0), (0, 0, 3, 2.0), (1, 0, 1, 0)],
    [(2, 0, 1, 4.0), (1, 0, 1, 0), (1, 0, 1, 0)],
], ids=["unknown_op", "endpoint_out_of_range", "nan_weight",
        "delete_absent_after_insert_delete", "hub_delete_absent",
        "hub_reweight_absent_then_double_delete"])
def test_invalid_batch_matches_the_per_op_scan(rows):
    """A bad batch raises where the per-op scan raises, with its message,
    and leaves the log, version, device buffers and dirty set alone."""
    dc = _hub_graph_container()
    dc.apply(_ops((1, 0, 5, 0), (0, 0, 5, 3.0), (0, 9, 10, 2.0)))
    batch = _ops(*rows)
    with pytest.raises(InvalidBatchError) as want:
        _ScanLog(dc).apply(batch)
    before = ([a.copy() for a in (dc._src, dc._dst, dc._w, dc._valid,
                                  dc.counts, dc.out_deg)],
              dc.version, dc.layout_version, set(dc.dirty), dc.csr, dc.parts)
    with pytest.raises(InvalidBatchError) as got:
        dc.apply(batch)
    assert got.value.index == want.value.index
    assert str(got.value) == str(want.value)
    for a, b in zip(before[0], (dc._src, dc._dst, dc._w, dc._valid,
                                dc.counts, dc.out_deg)):
        _same_array(b, a)
    assert (dc.version, dc.layout_version, dc.dirty) == before[1:4]
    assert dc.csr is before[4] and dc.parts is before[5]


@pytest.mark.parametrize("rows,blocks", [
    ([(0, 0, 3, 1.0)], 1),
    ([(0, 0, 3, 1.0), (1, 0, 2, 0), (0, 40, 41, 2.0), (2, 63, 1, 5.0)], 2),
], ids=["one_op", "both_blocks"])
def test_apply_scanned_counts_the_blocks_of_its_sources(rows, blocks):
    """``stream.apply``'s ``scanned`` stat is the live lanes of the blocks
    that hold the batch's sources, as the log stood before the batch."""
    from repro.obs import TraceRecorder

    dc = _hub_graph_container()
    batch = _ops(*rows)
    parts = np.unique(dc.vertex_part[batch.src])
    assert len(parts) == blocks
    want = int(dc.counts[parts].sum())
    rec = TraceRecorder()
    dc.apply(batch, obs=rec)
    (e,) = [e for e in rec.events if e.ph == "X" and e.name == "stream.apply"]
    assert e.args["scanned"] == want


# --------------------------------------------------------------------------
# Incremental equivalence (property)
# --------------------------------------------------------------------------

def _sequential_batches(dc, program, source, seed, n_batches=3, scale=10):
    """Apply ``n_batches`` random batches; after each, incremental must
    match from-scratch on the post-update graph."""
    rng = np.random.default_rng(seed)
    warm = run_hytm(None, program, source=source, config=CFG,
                    runtime=dc.runtime_for(program))
    for _ in range(n_batches):
        rep = dc.apply(random_batch(
            dc, rng,
            n_insert=int(rng.integers(1, scale)),
            n_delete=int(rng.integers(1, scale)),
            n_reweight=int(rng.integers(0, scale // 2 + 1)),
        ))
        inc = run_incremental(dc, program, [rep], warm.values, warm.delta,
                              source=source, config=CFG)
        fs = run_hytm(dc.to_host_graph(), program, source=source, config=CFG)
        if program.combine == 0:
            np.testing.assert_array_equal(inc.values, fs.values)
        else:
            np.testing.assert_allclose(
                inc.values + inc.delta, fs.values + fs.delta, atol=1e-3
            )
        warm = inc


@settings(max_examples=3, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=10_000),
    prog=st.sampled_from(["sssp", "bfs"]),
)
def test_incremental_matches_scratch_min(seed, prog):
    g = rmat_graph(300, 2400, seed=seed % 3)
    dc = DeltaCSR(g, CFG)
    _sequential_batches(dc, ALGORITHMS[prog], 0, seed)


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_incremental_matches_scratch_sum(seed):
    g = rmat_graph(300, 2400, seed=seed % 3)
    dc = DeltaCSR(g, CFG)
    _sequential_batches(dc, PR, None, seed)


# --------------------------------------------------------------------------
# Regression: small batches must win
# --------------------------------------------------------------------------

# --------------------------------------------------------------------------
# seg_start refresh: Eq. 3 alignment drift
# --------------------------------------------------------------------------

def _aligned_zc_req(dc):
    """The zero-copy request counts of the layout the next
    merge-compaction would realize: every partition's segments packed
    dense in vertex order (live-degree prefix-sum) — the drift oracle."""
    import jax.numpy as jnp

    from repro.core.cost_model import zc_request_counts

    seg = np.empty(dc.n_nodes, np.int64)
    B = dc.block_size
    for p in range(dc.n_partitions):
        v0, v1 = int(dc.vertex_start[p]), int(dc.vertex_start[p + 1])
        if v1 <= v0:
            continue
        deg = dc.out_deg[v0:v1].astype(np.int64)
        seg[v0:v1] = p * B + np.concatenate(([0], np.cumsum(deg[:-1])))
    return np.asarray(zc_request_counts(
        jnp.asarray(dc.out_deg, jnp.int32), jnp.asarray(seg, jnp.int32),
        dc.config.link,
    ))


def test_seg_start_refresh_removes_cost_model_drift():
    """Delete-heavy batch sequences drift the frozen seg_start away from
    the live layout: the Eq. 3 alignment term then mispredicts zero-copy
    requests.  ``refresh_seg_start=True`` (default) re-derives dirty
    partitions per patch and must track the aligned oracle exactly;
    the frozen flag reproduces (and quantifies) the historical drift."""
    g = rmat_graph(400, 3200, seed=6)
    cfg = HyTMConfig(n_partitions=6)
    fresh = DeltaCSR(g, cfg)  # refresh_seg_start=True
    frozen = DeltaCSR(g, cfg, refresh_seg_start=False)
    rng_a, rng_b = np.random.default_rng(6), np.random.default_rng(6)
    drift_fresh = drift_frozen = 0.0
    for _ in range(4):
        ba = random_batch(fresh, rng_a, n_insert=2, n_delete=60)
        bb = random_batch(frozen, rng_b, n_insert=2, n_delete=60)
        np.testing.assert_array_equal(ba.src, bb.src)  # same sequence
        ra, rb = fresh.apply(ba), frozen.apply(bb)
        assert not ra.merged and not rb.merged
        drift_fresh += float(np.abs(
            np.asarray(fresh.zc_req) - _aligned_zc_req(fresh)).sum())
        drift_frozen += float(np.abs(
            np.asarray(frozen.zc_req) - _aligned_zc_req(frozen)).sum())
    # identical edge multisets — only the alignment model differs
    assert _edge_multiset(fresh) == _edge_multiset(frozen)
    assert drift_fresh == 0.0, drift_fresh
    assert drift_frozen > 0.0  # the drift the refresh removes


def test_incremental_fewer_iterations_on_small_batches():
    """On update batches of <=1% of the edges, the warm-started run must
    take strictly fewer sweep iterations than from-scratch."""
    g = rmat_graph(800, 8000, seed=9)
    dc = DeltaCSR(g, HyTMConfig(n_partitions=8))
    cfg = dc.config
    rng = np.random.default_rng(9)
    warm = run_hytm(None, SSSP, source=0, config=cfg,
                    runtime=dc.runtime_for(SSSP))
    for _ in range(3):
        rep = dc.apply(random_batch(dc, rng, n_insert=40, n_delete=40))
        assert len(rep.ins_src) + len(rep.del_src) <= 0.01 * 2 * g.n_edges
        inc = run_incremental(dc, SSSP, [rep], warm.values, warm.delta,
                              source=0, config=cfg)
        fs = run_hytm(dc.to_host_graph(), SSSP, source=0, config=cfg)
        np.testing.assert_array_equal(inc.values, fs.values)
        assert inc.iterations < fs.iterations, (inc.iterations, fs.iterations)
        warm = inc


# --------------------------------------------------------------------------
# Tracing: host spans, recorder counters, the patch scatter's device scope
# --------------------------------------------------------------------------

def _traced_stream(obs, slack=0.0):
    """A small batch, then one that floods vertex 0 past its block (a
    merge-compaction), each applied and converged from the last answer;
    with ``obs`` given to both entry points.  Returns the container and
    per batch (batch, report, result)."""
    g = rmat_graph(300, 2400, seed=2)
    dc = DeltaCSR(g, CFG, slack=slack, min_slack=1)
    warm = run_hytm(None, SSSP, source=0, config=CFG, runtime=dc.runtime_for(SSSP))
    small = random_batch(dc, np.random.default_rng(2), n_insert=6, n_delete=6)
    k = dc.block_size + 8
    flood = EdgeBatch.inserts(np.zeros(k, np.int64), np.arange(k) % 300,
                              np.full(k, 7.0, np.float32))
    out = []
    for batch in (small, flood):
        rep = dc.apply(batch, obs=obs)
        warm = run_incremental(dc, SSSP, [rep], warm.values, warm.delta,
                               source=0, config=CFG, obs=obs)
        out.append((batch, rep, warm))
    return dc, out


def test_stream_spans_and_counters_are_recorded():
    from repro.obs import TraceRecorder

    rec = TraceRecorder()
    _, steps = _traced_stream(rec)
    spans = [e for e in rec.events if e.ph == "X" and e.name.startswith("stream.")]
    assert [e.name for e in spans] == [
        "stream.apply", "stream.seed", "stream.merge", "stream.apply", "stream.seed"]
    applies = [e for e in spans if e.name == "stream.apply"]
    merge = next(e for e in spans if e.name == "stream.merge")
    for e, (batch, rep, _) in zip(applies, steps):
        assert e.track == "stream"
        assert e.args["ops"] == len(batch) and e.args["merged"] == rep.merged
        assert e.args["touched"] > 0
    assert [rep.merged for _, rep, _ in steps] == [False, True]
    assert applies[1].wall <= merge.wall
    assert merge.wall + merge.wall_dur <= applies[1].wall + applies[1].wall_dur
    seeds = [e for e in spans if e.name == "stream.seed"]
    counters = {}
    for e in rec.events:
        if e.ph == "C" and e.name.startswith("stream."):
            counters.setdefault(e.name, []).append(e.args["value"])
    assert counters["stream.reset"] == [float(e.args["reset"]) for e in seeds]
    assert counters["stream.seeded"] == [float(e.args["seeded"]) for e in seeds]
    assert all(e.args["seeded"] > 0 for e in seeds)


def test_traced_and_untraced_stream_runs_are_bit_identical():
    from repro.obs import TraceRecorder

    dc_a, traced = _traced_stream(TraceRecorder())
    dc_b, bare = _traced_stream(None)
    for (_, rep_a, res_a), (_, rep_b, res_b) in zip(traced, bare):
        np.testing.assert_array_equal(res_a.values, res_b.values)
        np.testing.assert_array_equal(res_a.delta, res_b.delta)
        assert res_a.iterations == res_b.iterations
        np.testing.assert_array_equal(rep_a.dirty_partitions, rep_b.dirty_partitions)
    assert _edge_multiset(dc_a) == _edge_multiset(dc_b)
    for f in ("edge_src", "edge_dst", "edge_weight", "edge_valid", "out_degree"):
        np.testing.assert_array_equal(np.asarray(getattr(dc_a.csr, f)),
                                      np.asarray(getattr(dc_b.csr, f)))


def test_stream_patch_maps_to_its_ops():
    """A patch (no merge) registers its scatter, and ``op_scopes()`` maps
    the scatter's instructions to ``stream.patch``; the device arrays
    mirror the host log after it."""
    from repro.obs import scopes

    g = rmat_graph(300, 2400, seed=3)
    dc = DeltaCSR(g, CFG)
    rep = dc.apply(random_batch(dc, np.random.default_rng(3), n_insert=9, n_delete=9))
    assert not rep.merged
    patch = [line for line, s in scopes.op_scopes() if s == scopes.STREAM_PATCH]
    assert sum("scatter" in line.split(" = ", 1)[0] for line in patch) >= 4
    np.testing.assert_array_equal(np.asarray(dc.csr.edge_src), dc._src)
    np.testing.assert_array_equal(np.asarray(dc.csr.edge_weight), dc._w)
    np.testing.assert_array_equal(np.asarray(dc.csr.edge_valid), dc._valid)


def test_precompile_patch_changes_nothing():
    g = rmat_graph(300, 2400, seed=4)
    dc = DeltaCSR(g, CFG)
    before = _edge_multiset(dc), dc.version, dc.counts.copy()
    for lanes in (16, 64):
        dc.precompile_patch(lanes)
    assert (_edge_multiset(dc), dc.version) == before[:2]
    np.testing.assert_array_equal(dc.counts, before[2])
    np.testing.assert_array_equal(np.asarray(dc.csr.edge_dst), dc._dst)
    np.testing.assert_array_equal(np.asarray(dc.parts.part_edges), dc.counts)


def test_stream_spans_carry_their_stats_on_the_profiler(tmp_path):
    """Without a recorder the spans are profiler annotations, and their
    stats (known only when the work is done) are on them."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _, steps = _traced_stream(None)
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    got = {}
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("stream."):
                    got.setdefault(e.name, []).append(dict(e.stats))
    assert len(got["stream.apply"]) == 2 and len(got["stream.merge"]) == 1
    for stats, (batch, rep, _) in zip(got["stream.apply"], steps):
        assert int(stats["ops"]) == len(batch)
        assert int(stats["touched"]) > 0 and bool(stats["merged"]) == rep.merged
    assert all({"reset", "seeded"} <= set(s) for s in got["stream.seed"])
