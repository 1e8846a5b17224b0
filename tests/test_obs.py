"""repro.obs: recorder semantics, chrome-trace schema, zero-overhead
no-op contract, exact metrics-vs-HyTMResult reconciliation, live spans
on the profiler's clock, and the map from compiled ops to device
scopes."""

import json
import re

import numpy as np
import pytest

from repro.core.hytm import HyTMConfig, run_hytm
from repro.graph.algorithms import SSSP
from repro.graph.generators import rmat_graph
from repro.obs import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    TraceRecorder,
    reconcile,
    summary,
    to_chrome_trace,
    validate_chrome_trace,
    write_chrome_trace,
)
from repro.obs import scopes, span

CFG = HyTMConfig(n_partitions=8, sync_every=4)
CFG1 = HyTMConfig(n_partitions=8, sync_every=1)


@pytest.fixture(scope="module")
def graph():
    return rmat_graph(600, 4_800, seed=9)


# --------------------------------------------------------------------------
# recorder primitives
# --------------------------------------------------------------------------

def test_recorder_ring_is_bounded():
    rec = TraceRecorder(capacity=4)
    for i in range(10):
        rec.instant("e", vt=float(i))
    assert len(rec) == 4
    assert rec.dropped == 6
    # oldest events fell off the ring; the survivors are the newest
    assert [e.vt for e in rec.events] == [6.0, 7.0, 8.0, 9.0]


def test_metrics_registry():
    m = MetricsRegistry()
    c = m.counter("bytes", "transferred bytes")
    c.inc(10, engine="filter")
    c.inc(5, engine="filter")
    c.inc(7, engine="compact")
    assert c.value(engine="filter") == 15
    assert c.total() == 22
    g = m.gauge("occ", "occupancy")
    g.set(0.5)
    g.set(0.25)
    assert g.value() == 0.25 and g.max() == 0.5
    h = m.histogram("frontier", "active vertices")
    for v in (1, 10, 100):
        h.observe(v)
    assert h.count() == 3 and h.sum() == 111
    # same name resolves to the same instrument; type mismatch raises
    assert m.counter("bytes", "") is c
    with pytest.raises(TypeError):
        m.gauge("bytes", "")
    snap = m.snapshot()
    assert set(snap) == {"bytes", "occ", "frontier"}
    assert isinstance(Counter("x", ""), Counter)
    assert isinstance(Gauge("x", ""), Gauge)
    assert isinstance(Histogram("x", ""), Histogram)


# --------------------------------------------------------------------------
# chrome trace schema
# --------------------------------------------------------------------------

def test_chrome_trace_schema_and_tracks():
    rec = TraceRecorder()
    rec.span("run", cat="run", track="device0", wall=0.0, wall_dur=1.0,
             vt=0.0, vt_dur=5.0)
    rec.instant("it", cat="iteration", track="device0", vt=1.0)
    rec.counter("frontier", 42.0, track="device0", vt=1.0)
    rec.span("request:batched", cat="serve", track="tenant:gold",
             wall=0.1, wall_dur=0.2)
    doc = to_chrome_trace(rec)
    validate_chrome_trace(doc)
    events = doc["traceEvents"]
    # per-track thread metadata + stable tid assignment
    meta = [e for e in events if e["ph"] == "M"]
    names = {m["args"]["name"] for m in meta if m["name"] == "thread_name"}
    assert {"device0", "tenant:gold"} <= names
    tids = {e["tid"] for e in events if e["ph"] != "M"}
    assert len(tids) == 2
    # ts is microseconds of the wall clock; vt rides in args
    run_ev = next(e for e in events if e["name"] == "run")
    assert run_ev["ts"] == 0.0 and run_ev["dur"] == pytest.approx(1e6)
    assert run_ev["args"]["vt_dur"] == 5.0
    # serialized form is valid JSON end to end
    json.loads(json.dumps(doc))


def test_validate_rejects_malformed():
    doc = to_chrome_trace(TraceRecorder())
    doc["traceEvents"].append({"name": "bad", "ph": "Z", "pid": 1,
                               "tid": 1, "ts": 0.0})
    with pytest.raises(ValueError):
        validate_chrome_trace(doc)
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [
            {"name": "x", "ph": "X", "pid": 1, "tid": 1,
             "ts": float("nan"), "dur": 0.0}]})


def test_write_chrome_trace_and_jsonl(tmp_path):
    rec = TraceRecorder()
    rec.instant("e", vt=1.0, note="hello")
    p = tmp_path / "trace.json"
    write_chrome_trace(rec, str(p))
    doc = json.loads(p.read_text())
    validate_chrome_trace(doc)
    assert [e["name"] for e in doc["traceEvents"] if e["ph"] != "M"] == ["e"]


# --------------------------------------------------------------------------
# engine integration: no-op exactness, nesting, reconciliation
# --------------------------------------------------------------------------

@pytest.mark.parametrize("cfg", [CFG, CFG1], ids=["chunked", "K=1"])
def test_traced_run_bit_identical_and_reconciles(graph, cfg):
    base = run_hytm(graph, SSSP, source=0, config=cfg)
    rec = TraceRecorder()
    traced = run_hytm(graph, SSSP, source=0, config=cfg, obs=rec)
    # obs=None vs obs=recorder: identical jit programs, identical outputs
    np.testing.assert_array_equal(base.values, traced.values)
    assert base.iterations == traced.iterations
    assert base.total_transfer_bytes == traced.total_transfer_bytes
    np.testing.assert_array_equal(base.history["engines"],
                                  traced.history["engines"])
    # exact reconciliation: trace totals == HyTMResult accounting
    rep = reconcile(rec, traced)
    assert rep["ok"], rep
    assert rep["checks"]["iterations"]["trace"] == traced.iterations
    assert (rep["checks"]["transfer_bytes"]["trace"]
            == traced.total_transfer_bytes)


def test_span_nesting_invariants(graph):
    """Chunk spans nest inside the run span on both clocks, and the
    per-iteration instants tile the run's virtual-clock interval."""
    rec = TraceRecorder()
    res = run_hytm(graph, SSSP, source=0, config=CFG, obs=rec)
    runs = [e for e in rec.events if e.name == "hytm_run"]
    assert len(runs) == 1
    run_ev = runs[0]
    eps = 1e-9
    chunks = [e for e in rec.events if e.name == "chunk"]
    assert chunks and all(c.track == run_ev.track for c in chunks)
    for c in chunks:
        assert c.wall >= run_ev.wall - eps
        assert c.wall + c.wall_dur <= run_ev.wall + run_ev.wall_dur + eps
        assert c.vt >= run_ev.vt
        assert c.vt + c.vt_dur <= run_ev.vt + run_ev.vt_dur
    # chunk vt intervals are disjoint and cover exactly [0, iterations)
    ivs = sorted((c.vt, c.vt + c.vt_dur) for c in chunks)
    assert ivs[0][0] == 0 and ivs[-1][1] == res.iterations
    for (_, a_end), (b_start, _) in zip(ivs, ivs[1:]):
        assert a_end == b_start
    its = sorted(e.vt for e in rec.events if e.cat == "iteration")
    assert its == list(np.arange(res.iterations, dtype=float))


def test_metrics_match_result_accounting(graph):
    rec = TraceRecorder()
    res = run_hytm(graph, SSSP, source=0, config=CFG, obs=rec)
    m = rec.metrics
    assert m.get("engine.iterations").total() == res.iterations
    # per-engine byte counters sum to the result's transfer total
    # (float64 row-sum accumulation; exact for these magnitudes)
    assert m.get("engine.modeled_bytes").total() == res.total_transfer_bytes
    assert (m.get("engine.mispredictions").total()
            == res.total_mispredictions)
    picks = m.get("engine.picks")
    assert picks.total() == np.sum(
        np.asarray(res.history["engines"]) >= 0)
    s = summary(rec)
    assert s["events"] == len(rec) and s["dropped"] == 0
    assert "device0" in s["tracks"]


def test_reconcile_detects_mismatch(graph):
    rec = TraceRecorder()
    res = run_hytm(graph, SSSP, source=0, config=CFG, obs=rec)
    # a second run into the same recorder doubles the trace-side totals
    run_hytm(graph, SSSP, source=0, config=CFG, obs=rec)
    rep = reconcile(rec, res)
    assert not rep["ok"]


# --------------------------------------------------------------------------
# live spans on the profiler's clock
# --------------------------------------------------------------------------

PHASES = ("hytm.init", "hytm.compile", "hytm.dispatch", "hytm.wait",
          "hytm.drain", "hytm.result")


def _profiled(tmp_path, body):
    """Host events of the thread that ran ``body`` under a jax.profiler
    trace, as (name, start_ns, end_ns)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("caller"):
            body()
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events]
            if any(name == "caller" for name, _, _ in events):
                return events
    raise AssertionError("no host line holds the caller's annotation")


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("sync_every,mesh_axis", [(4, None), (1, None),
                                                  (4, "graph")],
                         ids=["chunked", "K=1", "mesh"])
def test_profiler_trace_holds_driver_spans(graph, tmp_path, sync_every,
                                           mesh_axis):
    """Both runs' host spans nest in their ``hytm.run``, the first run
    compiles and the second does not — on one device, for chunks of one,
    and on a 1-device mesh, whose runtime (built once, like a cell's)
    keeps its compiled chunk."""
    # a config no other test dispatches, so the first run compiles
    cfg = HyTMConfig(n_partitions=8, sync_every=sync_every, max_iters=9_001,
                     mesh_axis=mesh_axis)
    runtime = None
    if mesh_axis is not None:
        from repro.dist.graph_shard import build_sharded_runtime
        from repro.launch.mesh import make_graph_mesh

        runtime = build_sharded_runtime(graph, cfg, make_graph_mesh(1))
    events = _profiled(tmp_path, lambda: [
        run_hytm(graph, SSSP, source=0, config=cfg, runtime=runtime)
        for _ in range(2)])
    caller = next(e for e in events if e[0] == "caller")
    runs = [e for e in events if e[0] == "hytm.run"]
    assert len(runs) == 2 and all(_inside(r, caller) for r in runs)
    for run, first in zip(runs, (True, False)):
        names = [e[0] for e in events if e[0] in PHASES and _inside(e, run)]
        assert names[0] == "hytm.init" and names[-1] == "hytm.result"
        assert {"hytm.wait", "hytm.drain"} <= set(names)
        assert ("hytm.compile" in names) == first
        assert "hytm.dispatch" in names or first
    # a recorder puts the same spans on the driver's own track
    rec = TraceRecorder()
    run_hytm(graph, SSSP, source=0, config=cfg, runtime=runtime, obs=rec)
    track = "device0" if mesh_axis is None else "mesh"
    assert {"hytm.run", "hytm.init", "chunk", "hytm.dispatch", "hytm.wait",
            "hytm.drain", "hytm.result"} <= {
        e.name for e in rec.events if e.track == track}


def test_run_span_names_its_route(graph, tmp_path):
    """``hytm.run`` carries the route as profiler stats: ``prebuilt`` over
    a built runtime, ``per_call`` over a DeltaCSR view, with the lanes a
    sweep visits per stored edge."""
    import jax

    from repro.core.hytm import build_runtime
    from repro.stream.delta_csr import DeltaCSR

    cfg = HyTMConfig(n_partitions=8)
    runtimes = (build_runtime(graph, cfg), DeltaCSR(graph, cfg).runtime_for(SSSP))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for rt in runtimes:
            run_hytm(graph, SSSP, source=0, config=cfg, runtime=rt)
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    got = [dict(e.stats) for plane in jax.profiler.ProfileData.from_file(str(path)).planes
           for line in plane.lines for e in line.events if e.name == "hytm.run"]
    assert [s["route"] for s in got] == ["prebuilt", "per_call"]
    assert got == [rt.route_args() for rt in runtimes]
    assert got[0]["padding"] >= 1.0


def test_timed_writes_a_profiler_span(tmp_path):
    rec = TraceRecorder()

    def body():
        with rec.timed("probe", track="t", vt=3.0) as ev:
            ev.vt_dur = 2.0
            ev.args["rows"] = 7

    events = _profiled(tmp_path, body)
    probe = [e for e in events if e[0] == "probe"]
    assert len(probe) == 1 and _inside(probe[0], next(
        e for e in events if e[0] == "caller"))
    (recorded,) = rec.events
    assert (recorded.name, recorded.ph, recorded.track) == ("probe", "X", "t")
    assert (recorded.vt, recorded.vt_dur, recorded.args) == (3.0, 2.0, {"rows": 7})
    assert recorded.wall_dur >= 0.0


def test_span_records_only_with_a_recorder():
    with span("bare"):  # a profiler annotation alone
        pass
    rec = TraceRecorder()
    with span("outer", rec, track="t"):
        with span("inner", rec, track="t", n=1):
            pass
    # spans are pushed as they close: the inner one first
    assert [e.name for e in rec.events] == ["inner", "outer"]
    inner, outer = rec.events
    assert inner.args == {"n": 1}
    assert outer.wall <= inner.wall
    assert inner.wall + inner.wall_dur <= outer.wall + outer.wall_dur


def test_modeled_counters_say_so():
    from repro.obs.record import record_ici

    rec = TraceRecorder()
    record_ici(rec, track="mesh", it=0, bytes_=96.0, seconds=1e-6, engine=0,
               merged_entries=12.0, halo_entries=5.0)
    m = rec.metrics
    assert m.get("ici.modeled_bytes").total() == 96.0
    assert m.get("ici.modeled_halo_bytes").total() == 40.0
    assert m.get("ici.bytes") is None and m.get("ici.halo_bytes") is None


# --------------------------------------------------------------------------
# device scopes: compiled op -> innermost scope
# --------------------------------------------------------------------------

# ops that move no data through the device's compute: loop and tuple
# plumbing, and constants
PLUMBING = ("parameter", "get-tuple-element", "tuple", "constant", "bitcast",
            "copy")


def _computation(text, name):
    """The instruction lines of computation ``name`` in HLO text."""
    lines = text.splitlines()
    start = next(i for i, line in enumerate(lines)
                 if re.match(rf"^(ENTRY )?%{re.escape(name)} ", line))
    end = next(i for i in range(start, len(lines)) if lines[i] == "}")
    return [line.strip().removeprefix("ROOT ") for line in lines[start + 1:end]]


@pytest.mark.parametrize("sync_every", [4, 1], ids=["chunked", "K=1"])
def test_op_scopes_cover_the_program(sync_every):
    """At scale 10 with the kernels (interpret mode here), the programs
    runs dispatched map to every scope, and >= 95 % of the work in the
    chunk's while body (a chunk of one for K=1) of a run over a built
    runtime maps to one.  That run's blocks are routed
    when the runtime is built, so its program has no ``filter.order``;
    a runtime without the route sorts under it.  The registered
    signature compiles to the program the concrete arguments compile
    to."""
    import dataclasses

    import jax

    from repro.core.cost_model import init_history_buffers
    from repro.core.hytm import HyTMState, _iteration_impl, build_runtime, hytm_chunk

    g = rmat_graph(1 << 10, 16 << 10, seed=3)
    cfg = HyTMConfig(n_partitions=8, sync_every=sync_every, use_kernels=True,
                     max_iters=9_002)
    rt = build_runtime(g, cfg)
    run_hytm(g, SSSP, source=0, config=cfg, runtime=rt)
    run_hytm(g, SSSP, source=0, config=cfg,
             runtime=dataclasses.replace(rt, route=None))
    got = scopes.op_scopes()
    assert set(scopes.SCOPES) <= {s for _, s in got}

    state = HyTMState(*SSSP.init_state(g.n_nodes, 0))
    args = (rt.csr, rt.parts, rt.zc_req, rt.inv_deg, SSSP, cfg,
            rt.n_hub_partitions)
    info = jax.eval_shape(lambda s: _iteration_impl(s, *args)[1], state)
    text = hytm_chunk.lower(state, init_history_buffers(info, sync_every),
                            *args, sync_every, None, rt.route).compile().as_text()
    entry = _computation(text, re.search(r"ENTRY %(\S+) ", text).group(1))
    outer = next(line for line in entry if " while(" in line)
    body = _computation(text, re.search(r"body=%([\w.-]+)", outer).group(1))
    mine = dict(scopes.instruction_scopes(text))
    assert set(mine.items()) <= set(got)
    assert scopes.FILTER_ORDER not in mine.values()
    work = [line for line in (scopes._TAIL.split(b, maxsplit=1)[0] for b in body)
            if re.search(r"\s([a-z][\w-]*)\(", line.split(" = ", 1)[1]).group(1)
            not in PLUMBING]
    scoped = [line for line in work if mine[line] is not None]
    assert len(work) > 20 and len(scoped) >= 0.95 * len(work)


def test_instruction_scopes_fall_back_to_fused_root_and_users():
    text = """HloModule m

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %sort.1 = f32[8]{0} sort(%p), metadata={op_name="jit(f)/sweep/engine.filter/jit(g)/filter.order/sort"}
}

ENTRY %main.9 (a: f32[8]) -> (f32[8], f32[8]) {
  %a = f32[8]{0} parameter(0)
  %broadcast.2 = f32[8]{0} broadcast(%constant.1), dimensions={}
  %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1
  %add.4 = f32[8]{0} add(%fusion.3, %broadcast.2), metadata={op_name="jit(f)/while/body/update/add"}
  %custom-call.5 = f32[8]{0} custom-call(%add.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/sweep/engine.filter/pallas_call"}
  ROOT %tuple.6 = (f32[8]{0}, f32[8]{0}) tuple(%add.4, %custom-call.5)
}
"""
    got = dict(scopes.instruction_scopes(text))
    assert got == {
        "%a = f32[8]{0} parameter(0)": "filter.order",
        "%broadcast.2 = f32[8]{0} broadcast(%constant.1), dimensions={}": "update",
        "%fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1":
            "filter.order",
        "%add.4 = f32[8]{0} add(%fusion.3, %broadcast.2)": "update",
        "%custom-call.5 = f32[8]{0} custom-call(%add.4)": "engine.filter",
        "%tuple.6 = (f32[8]{0}, f32[8]{0}) tuple(%add.4, %custom-call.5)": None,
    }


def test_scope_names_and_nesting():
    assert scopes.innermost("jit(f)/while/body/sweep/engine.filter/"
                            "jit(segment_spmm_pallas)/filter.order/sort") == "filter.order"
    assert scopes.innermost("jit(f)/while/cond/lt") is None
    assert scopes.within("filter.order", "engine.filter")
    assert scopes.within("filter.order", "sweep")
    assert not scopes.within("sweep.block", "engine.filter")
    assert not scopes.within(None, "sweep")
    assert all(scopes.within(e, "sweep") for e in scopes.ENGINE_SCOPES)
    with pytest.raises(KeyError):
        scopes.scope("sweep.nope")
