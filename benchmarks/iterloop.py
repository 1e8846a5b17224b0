"""Iteration-driver benchmark: chunks of K iterations vs chunks of one.

The workload is deliberately **dispatch-bound**, not transfer-bound: BFS
on a tall, narrow grid graph (diameter ~= height), so the frontier is a
thin wave that needs ~height iterations of almost no per-iteration work.
This is the regime where the driver's fixed costs per chunk — one
``hytm_chunk`` dispatch plus the device->host syncs (loop-exit flag +
history drain) — dominate wall time when every chunk holds one
iteration, and where ``HyTMConfig.sync_every = K`` wins by paying those
costs once per K iterations instead (the high-diameter BFS/SSSP tail of
the EMOGI/Gunrock persistent-kernel comparison).

Rows:

* ``iterloop-periter`` — ``sync_every=1`` (a chunk of one iteration per
  dispatch);
* ``iterloop-chunked`` — ``sync_every=K``; ``derived`` records the
  dispatch counts and the wall-clock speedup.

``--selfcheck`` is the CI gate: it monkeypatch-counts driver dispatches
and asserts the chunked run batches for real — chunk dispatches
<= iterations/K + 1 (vs exactly ``iterations`` chunks of one at K=1),
bit-identical values, and a strictly faster chunked wall time on the
smoke graph.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np

from benchmarks.common import emit
from repro.core.hytm import (
    HyTMConfig,
    build_runtime,
    count_driver_dispatches,
    run_hytm,
)
from repro.graph.algorithms import BFS
from repro.graph.generators import grid_mesh_graph


def _timed_run(g, cfg, runtime, repeats: int = 3):
    """Median wall seconds of ``run_hytm`` over ``repeats`` (after a
    compile warmup), reusing ``runtime`` so partitioning/upload cost is
    out of the measurement — what remains is the convergence loop."""
    res = run_hytm(g, BFS, source=0, config=cfg, runtime=runtime)
    times = []
    for _ in range(repeats):
        t0 = time.monotonic()
        res = run_hytm(g, BFS, source=0, config=cfg, runtime=runtime)
        times.append(time.monotonic() - t0)
    return res, float(np.median(times))


def run(fast: bool = False, height: int | None = None, width: int = 3,
        sync_every: int = 32, repeats: int = 3, selfcheck: bool = False):
    height = height or (300 if fast else 1200)
    g = grid_mesh_graph(height, width, seed=0)
    base = HyTMConfig(n_partitions=8, sync_every=1)
    chunked = dataclasses.replace(base, sync_every=sync_every)
    rt = build_runtime(g, base)

    with count_driver_dispatches() as c1:
        res1, t1 = _timed_run(g, base, rt, repeats=repeats)
    with count_driver_dispatches() as cK:
        resK, tK = _timed_run(g, chunked, rt, repeats=repeats)

    runs = repeats + 1  # + warmup
    emit(
        "iterloop-periter", t1 * 1e6,
        f"iters={res1.iterations};dispatches_per_run={c1['chunk'] // runs}",
    )
    emit(
        "iterloop-chunked", tK * 1e6,
        f"K={sync_every};iters={resK.iterations};"
        f"dispatches_per_run={cK['chunk'] // runs};speedup={t1 / tK:.2f}x",
    )

    np.testing.assert_array_equal(res1.values, resK.values)
    assert res1.iterations == resK.iterations
    if selfcheck:
        # the dispatch-count gate: the chunked loop really batches
        per_run_chunks = cK["chunk"] // runs
        bound = resK.iterations // sync_every + 1
        assert per_run_chunks <= bound, (per_run_chunks, bound)
        assert c1["chunk"] // runs == res1.iterations
        assert tK < t1, f"chunked {tK:.3f}s not faster than chunks of one {t1:.3f}s"
        print(f"OK iterloop selfcheck: {per_run_chunks} chunk dispatches "
              f"<= {bound} for {resK.iterations} iters (K={sync_every}), "
              f"speedup {t1 / tK:.2f}x")
    return res1, resK


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--height", type=int, default=None)
    ap.add_argument("--sync-every", type=int, default=32)
    ap.add_argument("--selfcheck", action="store_true",
                    help="CI gate: assert dispatch count <= iters/K + 1, "
                    "bit-identical values, and chunked strictly faster "
                    "on the smoke graph")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(fast=args.fast or args.selfcheck, height=args.height,
        sync_every=args.sync_every, selfcheck=args.selfcheck)


if __name__ == "__main__":
    main()
