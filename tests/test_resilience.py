"""repro.resilience acceptance contract.

The exactness-under-faults guarantee, property-tested where cheap:

* the fault plane is deterministic (seeded per-site RNG streams) and
  zero-overhead when absent — an *empty* plan threaded through every
  guarded path still yields bit-identical runs;
* ``guarded_dispatch`` retries injected dispatch failures with bounded
  backoff and deadline-aware timeout accounting (injectable clock);
* checkpoints round-trip ``HyTMState`` + history + calibrator state with
  integrity checksums, and a run killed at any seeded chunk boundary
  resumes bit-identically (values, iterations, transfer bytes, engine
  picks) — single-device and on 4 forced-host devices;
* a corrupted host-spilled warm-cache entry is detected by checksum,
  counted, evicted, and the request recomputes correctly;
* an invalid update batch is rejected atomically (version, edge log, and
  device buffers bit-identical before/after);
* the degradation ladder (kernels -> oracle, tiered load shedding) and
  exactly-once update delivery keep answers unchanged;
* a corrupt autotune registry profile warns and falls back to shipped
  constants.
"""

import dataclasses
import os
import warnings

import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from _forced_devices import run_forced_devices
from repro.core.cost_model import KEY_ENGINES, KEY_TRANSFER_BYTES
from repro.core.hytm import HyTMConfig, run_hytm
from repro.graph.algorithms import SSSP
from repro.graph.generators import rmat_graph
from repro.resilience import (
    CheckpointError,
    CheckpointHook,
    DispatchFault,
    FaultPlan,
    FaultSpec,
    RetriesExhausted,
    RetryPolicy,
    RunCheckpoint,
    Supervisor,
    deliver_update,
    guarded_dispatch,
    plan_of,
    restore,
    resume_run,
    run_supervised,
    save,
)
from repro.serve import Request, RequestQueue
from repro.stream import (
    EdgeBatch,
    GraphService,
    InvalidBatchError,
    random_batch,
)
from repro.stream.delta_csr import OP_DELETE, OP_INSERT, OP_REWEIGHT

CFG = HyTMConfig(n_partitions=6, sync_every=2)
_G = {}


def _graph():
    if "g" not in _G:
        _G["g"] = rmat_graph(300, 2400, seed=7)
        _G["base"] = run_hytm(_G["g"], SSSP, source=0, config=CFG)
    return _G["g"], _G["base"]


# --------------------------------------------------------------------------
# fault plane: determinism + zero overhead
def test_fault_plan_deterministic():
    spec = FaultSpec("chunk_dispatch", "fail", p=0.5)
    a = [plan_of(spec, seed=3).fire("chunk_dispatch") for _ in range(1)]
    p1, p2 = plan_of(spec, seed=3), plan_of(spec, seed=3)
    seq1 = [p1.fire("chunk_dispatch") for _ in range(50)]
    seq2 = [p2.fire("chunk_dispatch") for _ in range(50)]
    assert seq1 == seq2
    assert any(k == "fail" for k in seq1) and any(k is None for k in seq1)
    # sites draw independent streams: firing another site between calls
    # must not perturb the first site's schedule
    p3 = plan_of(spec, FaultSpec("lane_alloc", "oom", p=0.5), seed=3)
    seq3 = []
    for _ in range(50):
        p3.fire("lane_alloc")
        seq3.append(p3.fire("chunk_dispatch"))
    assert seq3 == seq1


def test_fault_plan_at_and_when():
    plan = plan_of(FaultSpec("s", "fail", at=(1, 3)), seed=0)
    assert [plan.fire("s") for _ in range(5)] == [
        None, "fail", None, "fail", None]
    gated = plan_of(FaultSpec("s", "fail", p=1.0, when={"kernels": True}),
                    seed=0)
    assert gated.fire("s", kernels=False) is None
    assert gated.fire("s", kernels=True) == "fail"


def test_empty_plan_zero_overhead():
    g, base = _graph()
    res = run_hytm(g, SSSP, source=0, config=CFG, faults=FaultPlan(seed=1),
                   retry=RetryPolicy())
    np.testing.assert_array_equal(base.values, res.values)
    assert res.iterations == base.iterations
    assert res.total_transfer_bytes == base.total_transfer_bytes
    np.testing.assert_array_equal(base.history[KEY_ENGINES],
                                  res.history[KEY_ENGINES])


# --------------------------------------------------------------------------
# guarded_dispatch: retry / backoff / deadline (fake clock, no wall time)
def test_guarded_dispatch_retries_then_succeeds():
    plan = plan_of(FaultSpec("site", "fail", at=(0, 1)), seed=2)
    slept = []
    calls = []
    out = guarded_dispatch(
        lambda: calls.append(1) or 42, site="site", faults=plan,
        policy=RetryPolicy(max_attempts=4, backoff_s=0.5, factor=2.0),
        sleep=slept.append, clock=lambda: 0.0)
    assert out == 42 and len(calls) == 1
    assert slept == [0.5, 1.0]  # exponential backoff per failure


def test_guarded_dispatch_exhausts_attempts():
    plan = plan_of(FaultSpec("site", "fail", p=1.0), seed=2)
    try:
        guarded_dispatch(lambda: 0, site="site", faults=plan,
                         policy=RetryPolicy(max_attempts=3, backoff_s=0.0))
        raise AssertionError("expected RetriesExhausted")
    except RetriesExhausted as e:
        assert e.attempts == 3 and e.reason == "attempts"
        assert isinstance(e.last, DispatchFault)


def test_guarded_dispatch_deadline_counts_timeout_charge():
    plan = plan_of(FaultSpec("site", "timeout", p=1.0), seed=2)
    policy = RetryPolicy(max_attempts=10, backoff_s=0.0, deadline_s=1.0,
                         timeout_charge_s=0.4)
    try:
        guarded_dispatch(lambda: 0, site="site", faults=plan, policy=policy,
                         sleep=lambda s: None, clock=lambda: 0.0)
        raise AssertionError("expected RetriesExhausted")
    except RetriesExhausted as e:
        # 3 timeouts charge 1.2s of simulated elapsed > 1.0s deadline
        assert e.reason == "deadline" and e.attempts == 3


# --------------------------------------------------------------------------
# checkpoint: round trip, integrity, anchors
def test_checkpoint_round_trip(tmp_path):
    g, base = _graph()
    path = tmp_path / "run.ckpt.npz"
    ckpt = RunCheckpoint(
        program=SSSP.name, iterations=int(base.iterations),
        graph_version=3, layout_version=1,
        values=np.asarray(base.values), delta=np.asarray(base.delta),
        frontier=np.zeros(g.n_nodes, bool),
        history={k: np.asarray(v) for k, v in base.history.items()},
    )
    save(ckpt, path)
    back = restore(path, expect_anchor=(3, 1), program=SSSP.name)
    np.testing.assert_array_equal(back.values, np.asarray(base.values))
    assert back.iterations == base.iterations and back.anchor == (3, 1)
    np.testing.assert_array_equal(back.history[KEY_TRANSFER_BYTES],
                                  np.asarray(base.history[KEY_TRANSFER_BYTES]))


def test_checkpoint_rejects_corruption_and_mismatch(tmp_path):
    g, base = _graph()
    path = tmp_path / "run.ckpt.npz"
    save(RunCheckpoint(program="sssp", iterations=4,
                       values=np.asarray(base.values)), path)
    for expect, prog in (((1, 0), None), (None, "bfs")):
        try:
            restore(path, expect_anchor=expect, program=prog)
            raise AssertionError("expected CheckpointError")
        except CheckpointError:
            pass
    blob = bytearray(path.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    path.write_bytes(bytes(blob))
    try:
        restore(path)
        raise AssertionError("expected CheckpointError on corrupt file")
    except CheckpointError:
        pass
    try:
        restore(tmp_path / "absent.npz")
        raise AssertionError("expected CheckpointError on missing file")
    except CheckpointError:
        pass


# --------------------------------------------------------------------------
# crash recovery: kill at a seeded chunk boundary, resume bit-identically
@settings(max_examples=4, deadline=None)
@given(kill_at=st.integers(min_value=1, max_value=3))
def test_kill_resume_bit_identical(kill_at):
    g, base = _graph()
    import tempfile

    ck = os.path.join(tempfile.mkdtemp(prefix="resil_"), "run.ckpt.npz")
    hook = CheckpointHook(ck, program=SSSP.name, anchor=(0, 0))
    plan = plan_of(FaultSpec("chunk_dispatch", "fail", at=(kill_at,)),
                   seed=kill_at)
    try:
        run_hytm(g, SSSP, source=0, config=CFG, faults=plan, on_chunk=hook)
        raise AssertionError("injected kill did not fire")
    except RetriesExhausted:
        pass
    res = resume_run(ck, g, SSSP, config=CFG, source=0,
                     expect_anchor=(0, 0))
    np.testing.assert_array_equal(base.values, res.values)
    assert res.iterations == base.iterations
    assert res.total_transfer_bytes == base.total_transfer_bytes
    np.testing.assert_array_equal(base.history[KEY_ENGINES],
                                  res.history[KEY_ENGINES])
    np.testing.assert_array_equal(base.history[KEY_TRANSFER_BYTES],
                                  res.history[KEY_TRANSFER_BYTES])


def test_on_chunk_fires_per_iteration_at_k1_and_resumes():
    """At ``sync_every=1`` every iteration is a chunk boundary: the hook
    fires once per iteration, and a run killed at one resumes
    bit-identically to the uninterrupted run."""
    import tempfile

    g, _ = _graph()
    cfg1 = dataclasses.replace(CFG, sync_every=1)
    seen = []
    base = run_hytm(g, SSSP, source=0, config=cfg1,
                    on_chunk=lambda **kw: seen.append(kw["iterations"]))
    assert base.iterations > 3
    assert seen == list(range(1, base.iterations + 1))

    ck = os.path.join(tempfile.mkdtemp(prefix="resil_"), "k1.ckpt.npz")
    hook = CheckpointHook(ck, program=SSSP.name, anchor=(0, 0))
    plan = plan_of(FaultSpec("chunk_dispatch", "fail", at=(3,)), seed=3)
    try:
        run_hytm(g, SSSP, source=0, config=cfg1, faults=plan, on_chunk=hook)
        raise AssertionError("injected kill did not fire")
    except RetriesExhausted:
        pass
    assert hook.saved == 3  # one checkpoint per completed iteration
    res = resume_run(ck, g, SSSP, config=cfg1, source=0,
                     expect_anchor=(0, 0))
    np.testing.assert_array_equal(base.values, res.values)
    assert res.iterations == base.iterations
    assert res.total_transfer_bytes == base.total_transfer_bytes
    np.testing.assert_array_equal(base.history[KEY_ENGINES],
                                  res.history[KEY_ENGINES])


_SHARDED_RESUME_SCRIPT = """
import os, tempfile
import numpy as np
from repro.core.hytm import HyTMConfig, run_hytm
from repro.graph.algorithms import SSSP
from repro.graph.generators import rmat_graph
from repro.resilience import (CheckpointHook, FaultSpec, plan_of,
                              resume_run, RetriesExhausted)

g = rmat_graph(300, 2400, seed=7)
cfg = HyTMConfig(n_partitions=6, sync_every=2, async_sweep=False,
                 mesh_axis="graph")
base = run_hytm(g, SSSP, source=0, config=cfg)
ck = os.path.join(tempfile.mkdtemp(), "m.ckpt.npz")
hook = CheckpointHook(ck, program=SSSP.name, anchor=(0, 0))
plan = plan_of(FaultSpec("chunk_dispatch", "fail", at=(2,)), seed=5)
try:
    run_hytm(g, SSSP, source=0, config=cfg, faults=plan, on_chunk=hook)
    raise SystemExit("injected kill did not fire")
except RetriesExhausted:
    pass
res = resume_run(ck, g, SSSP, config=cfg, source=0, expect_anchor=(0, 0))
np.testing.assert_array_equal(base.values, res.values)
assert res.iterations == base.iterations
assert res.total_transfer_bytes == base.total_transfer_bytes
print("OK", base.iterations)
"""


def test_kill_resume_forced_devices():
    out = run_forced_devices(_SHARDED_RESUME_SCRIPT, devices=4)
    assert "OK" in out


_OWNER_RESUME_SCRIPT = """
import dataclasses, os, tempfile
import numpy as np
from repro.core.hytm import HyTMConfig, run_hytm
from repro.graph.algorithms import SSSP
from repro.graph.generators import rmat_graph
from repro.resilience import (CheckpointError, CheckpointHook, FaultSpec,
                              RetriesExhausted, migrate_state_layout,
                              plan_of, restore, resume_run, save)

g = rmat_graph(300, 2400, seed=7)
cfg = HyTMConfig(n_partitions=6, sync_every=2, async_sweep=False,
                 mesh_axis="graph", vertex_sharding="owner")
base = run_hytm(g, SSSP, source=0, config=cfg)
ck = os.path.join(tempfile.mkdtemp(), "m.ckpt.npz")
hook = CheckpointHook(ck, program=SSSP.name, anchor=(0, 0),
                      state_layout="owner", n_nodes=g.n_nodes)
plan = plan_of(FaultSpec("chunk_dispatch", "fail", at=(2,)), seed=5)
try:
    run_hytm(g, SSSP, source=0, config=cfg, faults=plan, on_chunk=hook)
    raise SystemExit("injected kill did not fire")
except RetriesExhausted:
    pass
res = resume_run(ck, g, SSSP, config=cfg, source=0, expect_anchor=(0, 0))
np.testing.assert_array_equal(base.values, res.values)
assert res.iterations == base.iterations
assert res.total_transfer_bytes == base.total_transfer_bytes
print("OK-RESUME", res.iterations)

# layout mismatch is a typed CheckpointError naming the converter, not a
# shape crash deep inside the sharded driver
cfg_rep = dataclasses.replace(cfg, vertex_sharding="replicated")
try:
    resume_run(ck, g, SSSP, config=cfg_rep, source=0, expect_anchor=(0, 0))
    raise SystemExit("expected CheckpointError")
except CheckpointError as e:
    assert "migrate_state_layout" in str(e), e
print("OK-TYPED")

# owner -> replicated -> owner migration round trip is bit-exact (pads
# are deterministic fills), and the migrated replicated checkpoint
# resumes to the same answer
ckpt = restore(ck)
assert ckpt.state_layout == "owner" and ckpt.n_nodes == 300
rep = migrate_state_layout(ckpt, "replicated")
assert rep.values.shape == (300,)
back = migrate_state_layout(rep, "owner", n_devices=4)
np.testing.assert_array_equal(back.values, ckpt.values)
np.testing.assert_array_equal(back.delta, ckpt.delta)
np.testing.assert_array_equal(back.frontier, ckpt.frontier)
ck2 = ck + ".rep.npz"
save(rep, ck2)
res2 = resume_run(ck2, g, SSSP, config=cfg_rep, source=0,
                  expect_anchor=(0, 0))
np.testing.assert_array_equal(base.values, res2.values)
assert res2.iterations == base.iterations
print("OK-MIGRATE")
"""


def test_owner_kill_resume_and_migration_forced_devices():
    """Owner-sharded kill+resume is bit-identical; resuming an
    owner-layout checkpoint into a replicated run raises a typed
    CheckpointError pointing at ``migrate_state_layout``; the migration
    round-trips bit-exactly and the migrated checkpoint resumes to the
    same answer on the replicated path."""
    out = run_forced_devices(_OWNER_RESUME_SCRIPT, devices=4)
    for marker in ("OK-RESUME", "OK-TYPED", "OK-MIGRATE"):
        assert marker in out, out


def test_migrate_state_layout_host_side():
    """The layout converter needs no mesh: replicated -> owner pads with
    the program's inert fills (+inf values / 0 delta / False frontier
    for SSSP's MIN), owner -> replicated slices them back off, real
    vertex bytes untouched; degenerate inputs raise typed errors."""
    from repro.resilience import migrate_state_layout

    n = 10
    rng = np.random.default_rng(0)
    ck = RunCheckpoint(
        program="sssp", iterations=3,
        values=rng.random(n).astype(np.float32),
        delta=rng.random(n).astype(np.float32),
        frontier=rng.random(n) > 0.5, n_nodes=n)
    own = migrate_state_layout(ck, "owner", n_devices=4)
    assert own.state_layout == "owner" and own.n_nodes == n
    assert own.values.shape == (12,)  # ceil(10/4)*4
    np.testing.assert_array_equal(own.values[:n], ck.values)
    assert np.all(np.isinf(own.values[n:]))
    assert not own.delta[n:].any() and not own.frontier[n:].any()
    back = migrate_state_layout(own, "replicated")
    np.testing.assert_array_equal(back.values, ck.values)
    np.testing.assert_array_equal(back.delta, ck.delta)
    np.testing.assert_array_equal(back.frontier, ck.frontier)
    assert migrate_state_layout(ck, "replicated") is ck  # no-op
    try:
        migrate_state_layout(ck, "sharded")
        raise AssertionError("expected ValueError on unknown layout")
    except ValueError:
        pass
    try:
        migrate_state_layout(dataclasses.replace(own, n_nodes=0),
                             "replicated")
        raise AssertionError("expected CheckpointError without n_nodes")
    except CheckpointError:
        pass
    try:
        migrate_state_layout(
            dataclasses.replace(ck, program="nope"), "owner", n_devices=2)
        raise AssertionError("expected CheckpointError on unknown program")
    except CheckpointError:
        pass


def test_checkpoint_schema_v1_still_restores(tmp_path):
    """A pre-owner-sharding (schema 1) checkpoint — no ``state_layout``
    or ``n_nodes`` metadata — still restores, defaulting to the
    replicated layout, so old checkpoints keep resuming on replicated
    runs after the schema bump."""
    import json
    import zlib

    vals = np.arange(5, dtype=np.float32)
    crc = zlib.crc32(np.ascontiguousarray(vals).tobytes())
    meta = {"schema": 1, "program": "sssp", "iterations": 2,
            "graph_version": 0, "layout_version": 0, "calibrator": None,
            "crc": {"values": crc}}
    path = tmp_path / "v1.ckpt.npz"
    np.savez(path, values=vals,
             __meta__=np.frombuffer(json.dumps(meta).encode(), np.uint8))
    back = restore(path, expect_anchor=(0, 0), program="sssp")
    assert back.state_layout == "replicated" and back.n_nodes == 0
    np.testing.assert_array_equal(back.values, vals)


# --------------------------------------------------------------------------
# warm cache: corrupt spilled entry -> detected, evicted, recomputed
def test_warm_cache_bit_flip_detected():
    g, base = _graph()
    n = g.n_nodes
    svc = GraphService(g, CFG, max_lanes=2, device_budget_bytes=2 * 9 * n)
    svc.query(SSSP, [0, 3, 77, 210])
    from repro.serve.warm_cache import HOST

    spilled = [(k, e) for k, e in svc.cache.items() if e.tier == HOST]
    assert spilled, "budget did not force a spill"
    key, entry = spilled[0]
    entry.values = entry.values.copy()
    entry.values.reshape(-1).view(np.uint8)[5] ^= 0x80
    before = svc.cache.stats.corrupt
    r = svc.query(SSSP, [key[1]])[0]
    assert svc.cache.stats.corrupt == before + 1
    assert key not in svc.cache or svc.cache.peek(key).tier != HOST
    solo = run_hytm(g, SSSP, source=key[1], config=CFG)
    np.testing.assert_array_equal(r.values, solo.values)


def test_injected_spill_corruption_recovers():
    g, base = _graph()
    n = g.n_nodes
    plan = plan_of(FaultSpec("host_spill", "corrupt", at=(0,)), seed=9)
    svc = GraphService(g, CFG, max_lanes=2, device_budget_bytes=2 * 9 * n,
                       faults=plan)
    svc.query(SSSP, [0, 3, 77, 210])
    r = svc.query(SSSP, [0])[0]
    np.testing.assert_array_equal(r.values, base.values)
    assert plan.counts().get(("host_spill", "corrupt")) == 1
    assert svc.cache.stats.corrupt + svc.cache.stats.promote_failures >= 0


# --------------------------------------------------------------------------
# delta_csr: atomic rejection of invalid batches
def _snapshot(dcsr):
    return (dcsr.version, dcsr.layout_version,
            dcsr._src.copy(), dcsr._dst.copy(), dcsr._w.copy(),
            dcsr.counts.copy(), set(dcsr.dirty))


def _assert_snapshot_equal(dcsr, snap):
    v, lv, src, dst, w, counts, dirty = snap
    assert dcsr.version == v and dcsr.layout_version == lv
    np.testing.assert_array_equal(dcsr._src, src)
    np.testing.assert_array_equal(dcsr._dst, dst)
    np.testing.assert_array_equal(dcsr._w, w)
    np.testing.assert_array_equal(dcsr.counts, counts)
    assert dcsr.dirty == dirty


@settings(max_examples=8, deadline=None)
@given(bad_kind=st.integers(min_value=0, max_value=4),
       salt=st.integers(min_value=0, max_value=10**6))
def test_invalid_batch_rejected_atomically(bad_kind, salt):
    g, _ = _graph()
    from repro.stream import DeltaCSR

    dcsr = DeltaCSR(g, CFG)
    rng = np.random.default_rng(salt)
    good = random_batch(dcsr, rng, n_insert=4, n_delete=2)
    n = dcsr.n_nodes
    bad = {
        0: EdgeBatch(np.array([OP_INSERT]), np.array([1]),
                     np.array([n + 5]), np.array([1.0], np.float32)),
        1: EdgeBatch(np.array([OP_INSERT]), np.array([-2]),
                     np.array([1]), np.array([1.0], np.float32)),
        2: EdgeBatch(np.array([OP_INSERT]), np.array([0]), np.array([1]),
                     np.array([np.nan], np.float32)),
        3: EdgeBatch(np.array([OP_REWEIGHT]), np.array([0]), np.array([1]),
                     np.array([np.inf], np.float32)),
        4: EdgeBatch(np.array([99]), np.array([0]), np.array([1]),
                     np.array([1.0], np.float32)),
    }[bad_kind]
    mixed = EdgeBatch(
        np.concatenate([good.op, bad.op]),
        np.concatenate([good.src, bad.src]),
        np.concatenate([good.dst, bad.dst]),
        np.concatenate([good.weight, bad.weight]),
    )
    snap = _snapshot(dcsr)
    for batch in (bad, mixed):
        try:
            dcsr.apply(batch)
            raise AssertionError("expected InvalidBatchError")
        except InvalidBatchError as e:
            assert e.index >= 0
        _assert_snapshot_equal(dcsr, snap)
    dcsr.apply(good)  # the good prefix alone still applies
    assert dcsr.version == snap[0] + 1


def test_delete_of_absent_rejected_sequence_aware():
    g, _ = _graph()
    from repro.stream import DeltaCSR

    dcsr = DeltaCSR(g, CFG)
    s, d, _ = dcsr.live_edges()
    live = {(int(u), int(v)) for u, v in zip(s, d)}
    absent = next((u, v) for u in range(g.n_nodes) for v in range(3)
                  if (u, v) not in live and u != v)
    ops = EdgeBatch(np.array([OP_DELETE]), np.array([absent[0]]),
                    np.array([absent[1]]), np.array([0.0], np.float32))
    snap = _snapshot(dcsr)
    try:
        dcsr.apply(ops)
        raise AssertionError("expected InvalidBatchError")
    except InvalidBatchError:
        pass
    _assert_snapshot_equal(dcsr, snap)
    # insert-then-delete of the same absent edge in ONE batch is valid
    ok = EdgeBatch(np.array([OP_INSERT, OP_DELETE]),
                   np.array([absent[0], absent[0]]),
                   np.array([absent[1], absent[1]]),
                   np.array([1.0, 0.0], np.float32))
    dcsr.apply(ok)
    assert dcsr.version == snap[0] + 1


# --------------------------------------------------------------------------
# exactly-once update delivery
def test_deliver_update_drop_and_duplicate():
    g, _ = _graph()
    svc = GraphService(g, CFG, max_lanes=2)
    rng = np.random.default_rng(1)
    batch = random_batch(svc.dcsr, rng, n_insert=6, n_delete=6)
    plan = plan_of(FaultSpec("update_delivery", "drop", at=(0,)),
                   FaultSpec("update_redeliver", "duplicate", at=(0,)),
                   seed=2)
    rep = deliver_update(svc, batch, batch_id="b0", faults=plan,
                         policy=RetryPolicy(max_attempts=3, backoff_s=0.0))
    assert svc.dcsr.version == 1 and rep.version == 1
    assert plan.counts() == {("update_delivery", "drop"): 1,
                             ("update_redeliver", "duplicate"): 1}
    # explicit redelivery of the same batch_id: cached report, no bump
    rep2 = svc.update(batch, batch_id="b0")
    assert rep2.version == 1 and svc.dcsr.version == 1
    # drop with no retry budget surfaces as RetriesExhausted
    plan2 = plan_of(FaultSpec("update_delivery", "drop", p=1.0), seed=3)
    try:
        deliver_update(svc, batch, batch_id="b1", faults=plan2,
                       policy=RetryPolicy(max_attempts=2, backoff_s=0.0))
        raise AssertionError("expected RetriesExhausted")
    except RetriesExhausted as e:
        assert e.site == "update_delivery"
    assert svc.dcsr.version == 1


# --------------------------------------------------------------------------
# degradation ladder + load shedding
def test_supervisor_kernels_rung_degrade():
    g, base = _graph()
    plan = plan_of(FaultSpec("chunk_dispatch", "fail", p=1.0, max_fires=64,
                             when={"kernels": True}), seed=11)
    cfgk = dataclasses.replace(CFG, use_kernels=True)
    sup = Supervisor(policy=RetryPolicy(max_attempts=2, backoff_s=0.0),
                     faults=plan)
    res = run_supervised(g, SSSP, source=0, config=cfgk, supervisor=sup)
    np.testing.assert_array_equal(base.values, res.values)
    assert [r for r, _ in sup.degradations] == ["kernels->oracle"]
    # the when= filter stopped firing once the oracle path took over
    fires = sum(plan.counts().values())
    assert 0 < fires < 64


def test_lane_alloc_oom_sheds_lowest_tier_only():
    g, _ = _graph()
    plan = plan_of(FaultSpec("lane_alloc", "oom", p=1.0, max_fires=100),
                   seed=4)
    sup = Supervisor(policy=RetryPolicy(max_attempts=2, backoff_s=0.0),
                     faults=plan, tenant_tiers={"gold": 2, "bronze": 0},
                     shed_after=2)
    svc = GraphService(g, CFG, max_lanes=4, faults=plan, supervisor=sup)
    q = RequestQueue(quota=1)
    for i, s in enumerate([0, 3, 77, 210, 9, 15]):
        q.submit(Request(tenant=["gold", "bronze"][i % 2], program=SSSP,
                         source=s, deadline=float(i)))
    served = svc.scheduler.pump(q)
    assert len(served) == 6 and q.stats.quota_violations == 0
    shed = [r for r in served if r.mode == "shed"]
    assert shed and all(r.request.tenant == "bronze" for r in shed)
    assert sup.counters["shed"] == len(shed) == q.stats.shed
    for r in served:
        if r.mode != "shed":
            solo = run_hytm(g, SSSP, source=r.request.source, config=CFG)
            np.testing.assert_array_equal(r.values, solo.values)


# --------------------------------------------------------------------------
# autotune registry: corrupt profile falls back to shipped constants
def test_registry_corrupt_profile_falls_back(tmp_path, monkeypatch):
    from repro.autotune.registry import load_profile_or_default
    from repro.core.constants import PCIE3

    monkeypatch.setenv("REPRO_AUTOTUNE_REGISTRY", str(tmp_path))
    kind = "fakedev"
    # missing: silent fallback
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert load_profile_or_default(kind) is PCIE3
    for garbage in ("{not json",
                    '{"schema": 1, "profile": {"name": "x"}}',
                    '{"schema": 99, "profile": {}}',
                    '[]'):
        (tmp_path / f"{kind}.json").write_text(garbage)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            link = load_profile_or_default(kind)
        assert link is PCIE3
        assert any(issubclass(w.category, RuntimeWarning) for w in caught), (
            garbage)
