"""Observability-layer benchmark + gate (repro.obs).

Measures what tracing costs and proves what it must not change:

* ``obs/emit_cost``   — median cost of one recorder event (span/instant/
  counter), the per-event price every instrumented site pays when a
  recorder is attached;
* ``obs/export``      — Chrome trace-event serialization cost for a
  recorder full of engine events;
* ``obs/overhead``    — traced vs untraced wall time of the same
  ``run_hytm`` sweep (the recorder only consumes already-drained host
  history, so this should be noise).

``--selfcheck`` gates (CI):
  1. **bit-identical** — a traced MIN run (at ``sync_every=4`` and in
     chunks of one, ``sync_every=1``) returns values,
     iterations, and transfer accounting identical to the untraced run;
  2. **exact reconciliation** — the run-summary span totals and the
     per-iteration event count equal the returned ``HyTMResult`` fields
     exactly (``repro.obs.export.reconcile``);
  3. **schema** — the exported Chrome trace-event JSON validates
     (``validate_chrome_trace``) for both the engine trace and a
     serving trace with tenant/cache/scheduler tracks;
  4. **bounded overhead** — the ring honors its capacity (overflow
     increments ``dropped``, never grows the buffer) and the traced
     sweep stays within a generous wall-time ratio of the untraced one.

``--trace <path>`` writes the selfcheck's engine trace for artifact
upload.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from benchmarks.common import emit
from repro.core.hytm import HyTMConfig, run_hytm
from repro.graph.algorithms import SSSP
from repro.graph.generators import rmat_graph
from repro.obs import (
    TraceRecorder,
    reconcile,
    to_chrome_trace,
    validate_chrome_trace,
    write_chrome_trace,
)

# generous: the recorder is host-side and off the jit path, but CPU CI
# wall times are noisy at these (sub-second) scales
OVERHEAD_RATIO = 2.0


def _emit_cost_us(n: int = 20_000) -> float:
    rec = TraceRecorder(capacity=n + 16)
    t0 = time.monotonic()
    for i in range(n):
        rec.instant("e", cat="bench", track="t", vt=float(i), k=i)
    per_event = (time.monotonic() - t0) / n
    assert len(rec) == n
    return per_event * 1e6


def _timed_run(g, cfg, obs=None, repeats: int = 3):
    """Median wall seconds of run_hytm (first call pays compile; the
    compiled executable is shared by the traced and untraced calls, so
    the medians compare recorder overhead only)."""
    res = run_hytm(g, SSSP, source=0, config=cfg, obs=obs)
    times = []
    for _ in range(repeats):
        t0 = time.monotonic()
        res = run_hytm(g, SSSP, source=0, config=cfg, obs=obs)
        times.append(time.monotonic() - t0)
    return res, float(np.median(times))


def run(fast: bool = False, selfcheck: bool = False, seed: int = 5,
        trace_path: str | None = None) -> dict:
    n_nodes, n_edges = (800, 6_400) if fast else (3_000, 36_000)
    g = rmat_graph(n_nodes, n_edges, seed=seed)
    cfg = HyTMConfig(n_partitions=8 if fast else 16, sync_every=4)
    cfg1 = HyTMConfig(n_partitions=cfg.n_partitions, sync_every=1)

    # --- cost of the recorder itself -------------------------------------
    emit("obs/emit_cost", _emit_cost_us(), "per instant event (host-side)")

    # --- traced vs untraced engine sweep ---------------------------------
    base, t_base = _timed_run(g, cfg)
    rec = TraceRecorder()
    traced, t_traced = _timed_run(g, cfg, obs=rec)
    ratio = t_traced / max(t_base, 1e-9)
    emit("obs/overhead", (t_traced - t_base) * 1e6,
         f"ratio={ratio:.2f} untraced_us={t_base * 1e6:.0f} "
         f"events={len(rec)}")

    t0 = time.monotonic()
    doc = to_chrome_trace(rec)
    t_export = time.monotonic() - t0
    emit("obs/export", t_export * 1e6,
         f"chrome_events={len(doc['traceEvents'])}")

    # one-run recorder for the reconciliation gate and the artifact (the
    # timing recorder above holds warmup + repeat runs on one track)
    rec_one = TraceRecorder()
    traced_one = run_hytm(g, SSSP, source=0, config=cfg, obs=rec_one)

    rows = {
        "overhead_ratio": ratio, "events": len(rec),
        "emit_us": _emit_cost_us(2_000), "iterations": traced.iterations,
    }
    if selfcheck:
        _selfcheck(g, cfg, cfg1, base, traced, rec_one, traced_one, doc,
                   rows)
    if trace_path is not None:
        write_chrome_trace(rec_one, trace_path)
        print(f"# trace: {len(rec_one)} events -> {trace_path}")
    return rows


def _selfcheck(g, cfg, cfg1, base, traced, rec_one, traced_one, doc,
               rows) -> None:
    # 1. bit-identical: tracing must not perturb the computation —
    # chunks of four (the repeats above) and chunks of one
    np.testing.assert_array_equal(base.values, traced.values)
    assert base.iterations == traced.iterations
    assert base.total_transfer_bytes == traced.total_transfer_bytes
    rec1 = TraceRecorder()
    base1 = run_hytm(g, SSSP, source=0, config=cfg1)
    traced1 = run_hytm(g, SSSP, source=0, config=cfg1, obs=rec1)
    np.testing.assert_array_equal(base1.values, traced1.values)
    assert base1.iterations == traced1.iterations

    # 2. exact reconciliation at both chunk sizes: span totals == HyTMResult
    for r, result, tag in ((rec_one, traced_one, "chunked"),
                           (rec1, traced1, "K=1")):
        rep = reconcile(r, result)
        assert rep["ok"], (tag, rep)

    # 3. schema: engine trace + a serving trace (tenant/cache tracks)
    validate_chrome_trace(doc)
    validate_chrome_trace(to_chrome_trace(rec1))
    from repro.stream import GraphService

    rec_s = TraceRecorder()
    svc = GraphService(g, cfg, max_lanes=2, obs=rec_s,
                       device_budget_bytes=3 * 9 * g.n_nodes)
    svc.query(SSSP, [0, 1, 2, 3, 4])
    validate_chrome_trace(to_chrome_trace(rec_s))
    tracks = {e.track for e in rec_s.events}
    assert {"scheduler", "cache"} <= tracks, tracks
    assert any(t.startswith("tenant:") for t in tracks), tracks

    # 4. bounded overhead: ring capacity is a hard bound (overflow is
    # counted, not stored) and the traced sweep stays within ratio
    tiny = TraceRecorder(capacity=8)
    for i in range(50):
        tiny.instant("e", vt=float(i))
    assert len(tiny) == 8 and tiny.dropped == 42, (len(tiny), tiny.dropped)
    assert rows["overhead_ratio"] < OVERHEAD_RATIO, rows
    print(f"# SELFCHECK OK: traced == untraced (both chunk sizes); "
          f"reconcile exact over {rows['iterations']} iterations; "
          f"schema valid ({len(doc['traceEvents'])} chrome events); "
          f"overhead ratio {rows['overhead_ratio']:.2f} < {OVERHEAD_RATIO}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller graph (CI mode)")
    ap.add_argument("--selfcheck", action="store_true",
                    help="gate: bit-identical traced runs, exact "
                         "HyTMResult reconciliation, valid chrome "
                         "schema, bounded overhead")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write the selfcheck engine trace (chrome "
                         "trace-event JSON) to PATH")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(fast=args.fast, selfcheck=args.selfcheck, seed=args.seed,
        trace_path=args.trace)


if __name__ == "__main__":
    main()
