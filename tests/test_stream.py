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
