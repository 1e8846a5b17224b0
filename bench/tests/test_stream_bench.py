"""CPU tests of the edge-update stream deployment (``kron17-stream-sssp``):
its batches, the check's replayed reference against ``DeltaCSR``, whole
runs at scale 10 with the device check stubbed out, its controls and its
idle metrics.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import hashlib
import json

import numpy as np
import pytest

import loadgen
import named
import run
import tracereduce
from reference import Reference
from test_bench import altered, cell_inputs, ev, result_of, shift_one_value, tiny_root  # noqa: F401


# the stream's shape at scale 10: the cell's 512 + 512 undirected edges a
# batch and 1,024 batches would delete more edges than a scale-10 graph has
SMALL_STREAM = {"batch": {"deletes": 8, "inserts": 8}, "key_pool": 256}


def stream_inputs(scale=10, **traffic):
    mix, e = cell_inputs("stream-sssp", scale)
    return {**mix, **traffic}, e


@pytest.fixture
def stream_root(tiny_root):
    """``tiny_root`` with the stream's batches cut to ``SMALL_STREAM``."""
    path = tiny_root / "bench/traffic/stream-sssp.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), **SMALL_STREAM}))
    return tiny_root


def stream_arrays(keys):
    s = keys[0].stream
    return (s.source, s.deleted, s.ins_src, s.ins_dst, s.ins_w, s.order)


def test_stream_keys_are_per_seed_and_keep_their_rules():
    mod = named.load("keys", "update_batches")
    mix, e = stream_inputs(**SMALL_STREAM)
    a = loadgen.draw_keys(mix, e, 2**40 + 7)
    b = loadgen.draw_keys(mix, e, 2**40 + 7)
    for x, y in zip(stream_arrays(a), stream_arrays(b)):
        np.testing.assert_array_equal(x, y)
    other = loadgen.draw_keys(mix, e, 8)
    assert not np.array_equal(stream_arrays(a)[1], stream_arrays(other)[1])
    assert len(a) == mix["key_pool"] + 1
    assert [k.index for k in a] == list(range(len(a)))
    s = a[0].stream
    assert all(k.stream is s and k.source == s.source for k in a)
    # the source lies in the input's largest component
    label = mod.components(e.n, e.src, e.dst)
    assert np.sum(label == label[s.source]) == np.bincount(label).max()
    # deletions: distinct input edges, no self-loops, pairs that occur once
    gone = s.deleted.ravel()
    assert len(np.unique(gone)) == len(gone)
    pair = mod.pair_codes(e.n, e.src, e.dst)
    assert np.all(e.src[gone] != e.dst[gone])
    uniq, times = np.unique(pair, return_counts=True)
    assert np.all(times[np.searchsorted(uniq, pair[gone])] == 1)
    # insertions: no self-loops, no pair the stream deletes, weights in range
    ins = mod.pair_codes(e.n, s.ins_src.ravel(), s.ins_dst.ravel())
    assert np.all(s.ins_src != s.ins_dst)
    assert not np.isin(ins, pair[gone]).any()
    assert s.ins_w.min() >= 1 and s.ins_w.max() <= 255
    assert np.all(s.ins_w == np.round(s.ins_w))
    # a batch is each edge's two directed ops, in a shuffled order
    insert, src, dst, w = s.ops(3)
    assert len(insert) == 2 * (8 + 8) and insert.sum() == 16
    assert sorted(zip(src[~insert], dst[~insert])) == sorted(
        [(int(e.src[i]), int(e.dst[i])) for i in s.deleted[3]]
        + [(int(e.dst[i]), int(e.src[i])) for i in s.deleted[3]])
    assert not np.array_equal(insert, np.sort(insert))


def test_stream_replay_matches_delta_csr_across_a_merge():
    """The check's replayed edge multiset after batches 0..i equals the
    program's ``DeltaCSR`` after the same batches, through a forced
    merge-compaction (no slack)."""
    from repro.core.hytm import HyTMConfig
    from repro.graph.csr import csr_from_edges
    from repro.stream import OP_DELETE, OP_INSERT, DeltaCSR, EdgeBatch

    mix, e = stream_inputs(batch={"deletes": 8, "inserts": 64}, key_pool=8)
    keys = loadgen.draw_keys(mix, e, 5)
    dc = DeltaCSR(csr_from_edges(e.n, *e.directed()), HyTMConfig(n_partitions=8),
                  slack=0.0, min_slack=1)

    def multiset(src, dst, w):
        return sorted(zip(np.asarray(src).tolist(), np.asarray(dst).tolist(),
                          np.asarray(w).tolist()))

    merged = []
    for key in keys:
        insert, src, dst, w = key.stream.ops(key.index)
        merged.append(dc.apply(EdgeBatch(np.where(insert, OP_INSERT, OP_DELETE),
                                         src, dst, w)).merged)
        assert multiset(*dc.live_edges()) == multiset(*key.stream.live(key.index).directed())
    assert any(merged) and not all(merged)


@pytest.mark.parametrize("fault", [None, "answer-altered", "batch-skipped"])
def test_stream_whole_run(stream_root, capsys, monkeypatch, fault):
    """A whole scale-10 run of the stream cell is correct; it is not when
    one answer is altered, or when the driver skips one batch's apply
    (read-your-writes broken from that batch on)."""
    import repro.stream
    from repro.stream import DeltaCSR, EdgeBatch

    if fault == "answer-altered":
        monkeypatch.setattr(repro.stream, "run_incremental",
                            altered(repro.stream.run_incremental, shift_one_value))
    if fault == "batch-skipped":
        real, calls = DeltaCSR.apply, []

        def skip_second(self, batch, *args, **kwargs):
            calls.append(len(batch))
            if len(calls) == 2:  # the window's first batch
                batch = EdgeBatch(*(np.zeros(0) for _ in range(4)))
            return real(self, batch, *args, **kwargs)

        monkeypatch.setattr(DeltaCSR, "apply", skip_second)
    res = result_of(capsys, stream_root, "kron17-stream-sssp")
    assert res["attempted"] > 1
    assert set(res["metrics"]) == {"edges_per_s", "run_s.p50", "setup_s"}
    assert res["correct"] is (fault is None)
    assert (res["failed"] == 0) is (fault is None)


@pytest.mark.parametrize("kind,want,wrong", [
    ("stale", {"unreached": (0, 0), "mismatched": (25, 0)}, list(range(1, 8))),
    ("bfloat16", {"unreached": (0, 0), "mismatched": (422, 0)}, list(range(8))),
])
def test_pinned_stream_control_checks(kind, want, wrong):
    """The ``[check]`` numbers of each stream control at scale 10, on
    the first ``check_runs`` batches after the warm-up (``SMALL_STREAM``)."""
    mix, e = stream_inputs(**SMALL_STREAM)
    ref = Reference(e)
    keys = loadgen.draw_keys(mix, e, 11)[1:1 + mix["check_runs"]]
    check = named.load("checks", "stream_exact")
    answers = [(k, *check.control(mix, ref, k, kind)) for k in keys]
    checks, found = loadgen.compare(mix, ref, answers, 11)
    assert checks == want
    assert sorted(found) == wrong
    if kind == "stale":
        assert loadgen.control(mix, ref, keys[0])[0].tolist() == answers[0][1].tolist()


@pytest.mark.parametrize("seed,want", [(7, (103695, "e9dcf81540da17e5")),
                                       (2**33 + 5, (117194, "fc5c1c711e14787d"))])
def test_pinned_stream_keys(seed, want):
    """The stream the cell runs at its own size: source, deletions,
    insertions and order, bit for bit."""
    mix, e = stream_inputs(17)
    keys = loadgen.draw_keys(mix, e, seed)
    assert len(keys) == mix["key_pool"] + 1
    got = hashlib.sha256(b"".join(
        np.ascontiguousarray(a).tobytes() for a in stream_arrays(keys)[1:])).hexdigest()[:16]
    assert (keys[0].source, got) == want


def test_stream_metrics_read_idle_under_their_spans():
    """Device-idle time under ``stream.apply`` / ``stream.seed`` inside
    each run, per run; nothing from a program without those spans."""
    ops = [ev("fusion.1 f32[8] fusion", 0, 10), ev("fusion.2 f32[8] fusion", 40, 20),
           ev("fusion.3 f32[8] fusion", 100, 10), ev("fusion.4 f32[8] fusion", 160, 30)]
    host = [ev(tracereduce.RUN_SPAN, 0, 100), ev("stream.apply", 5, 30),
            ev("stream.merge", 10, 5), ev("stream.seed", 35, 15),
            ev(tracereduce.RUN_SPAN, 100, 100), ev("stream.apply", 105, 40),
            ev("stream.seed", 150, 20), ev("stream.apply", 250, 10)]
    ctx = run.Context(runs=[], traffic={}, peaks={},
                      trace=tracereduce.Trace("/device:TPU:0", ops, host))
    # run 1: apply 5..35 idle 10..35 = 25 ns; seed 35..50 idle 35..40 = 5
    # run 2: apply 105..145 idle 110..145 = 35; seed 150..170 idle 150..160 = 10
    assert run.read_metric("stream.update_idle_ms_per_run", ctx) == pytest.approx(30 / 1e6)
    assert run.read_metric("stream.seed_idle_ms_per_run", ctx) == pytest.approx(7.5 / 1e6)
    bare = run.Context(runs=[], traffic={}, peaks={},
                       trace=tracereduce.Trace("/device:TPU:0", ops, host[:1] + host[4:5]))
    for name in ("stream.update_idle_ms_per_run", "stream.seed_idle_ms_per_run"):
        assert run.read_metric(name, bare) is None
        assert run.read_metric(name, run.Context([], {}, {}, None)) is None
