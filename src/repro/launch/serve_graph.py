"""Graph-query serving launcher: a ``repro.stream.GraphService`` driven
by a synthetic query/update trace.

    PYTHONPATH=src python -m repro.launch.serve_graph --nodes 5000 \\
        --edges 80000 --algorithm sssp --queries 32 --update-batches 4

    PYTHONPATH=src python -m repro.launch.serve_graph --selfcheck

``--selfcheck`` runs the serving equivalence contract on a small graph
(batched == independent runs, cached repeat == zero sweeps, incremental
after updates == from-scratch) and exits non-zero on any violation —
CI runs it on 8 forced-host CPU devices.

``--trace <path>`` threads a ``repro.obs.TraceRecorder`` through the
service (engine iterations, cache tier transitions, scheduler spans)
and writes a Chrome trace-event JSON viewable in chrome://tracing or
Perfetto.  ``--algorithm wcc`` runs weakly connected components — the
graph is symmetrized up front (``VertexProgram.symmetrize``).
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np


def selfcheck() -> None:
    import jax

    from repro.core.hytm import HyTMConfig, run_hytm
    from repro.graph.algorithms import PAGERANK, SSSP
    from repro.graph.generators import rmat_graph
    from repro.stream import GraphService, random_batch

    g = rmat_graph(500, 4000, seed=17)
    cfg = HyTMConfig(n_partitions=8)
    svc = GraphService(g, cfg, max_lanes=4)
    rng = np.random.default_rng(17)

    # 1. batched lanes == independent single-source runs (bit-exact)
    sources = [0, 3, 77, 210]
    batched = svc.query(SSSP, sources)
    for s, r in zip(sources, batched):
        solo = run_hytm(g, SSSP, source=s, config=cfg)
        np.testing.assert_array_equal(r.values, solo.values)
    assert all(r.mode == "batched" for r in batched)

    # 2. cached repeat: zero sweep iterations
    again = svc.query(SSSP, sources)
    assert all(r.cache_hit and r.iterations == 0 for r in again)

    # 3. update invalidates the cache; incremental matches from-scratch
    svc.update(random_batch(svc.dcsr, rng, n_insert=16, n_delete=16))
    post = svc.query(SSSP, sources)
    assert all(r.mode == "incremental" for r in post)
    g2 = svc.dcsr.to_host_graph()
    for s, r in zip(sources, post):
        fs = run_hytm(g2, SSSP, source=s, config=cfg)
        np.testing.assert_array_equal(r.values, fs.values)

    # 4. accumulative program: tolerance-bounded incremental equivalence
    pr = dataclasses.replace(PAGERANK, tolerance=1e-7)
    svc.query(pr, None)
    svc.update(random_batch(svc.dcsr, rng, n_insert=8, n_delete=8))
    inc = svc.query(pr, None)[0]
    assert inc.mode == "incremental"
    fs = run_hytm(svc.dcsr.to_host_graph(), pr, source=None, config=cfg)
    assert np.max(np.abs(inc.values - fs.values)) < 1e-3

    # 5. the serving path coexists with the sharded sweep (multi-device
    # hosts): a fresh query equals a mesh-sharded run of the same graph
    if len(jax.devices()) > 1:
        sharded = run_hytm(
            g2, SSSP, source=0,
            config=dataclasses.replace(cfg, async_sweep=False, mesh_axis="graph"),
        )
        np.testing.assert_array_equal(
            sharded.values, run_hytm(g2, SSSP, source=0, config=cfg).values
        )

    # 6. multi-tenant scheduler contract (repro.serve): EDF admission
    # under per-tenant quotas + a device byte budget small enough to
    # force cache spills — answers must still equal solo runs, the
    # budget must hold, and no quota may be exceeded mid-flight
    from repro.graph.algorithms import PPR
    from repro.serve import Request, RequestQueue

    n = svc.dcsr.n_nodes
    tiny = GraphService(svc.dcsr.to_host_graph(), cfg, max_lanes=2,
                        device_budget_bytes=2 * 9 * n)
    q = RequestQueue(quota=2, tenant_quotas={"bronze": 1})
    for i, s in enumerate([0, 3, 77, 210, 3, 9]):
        tenant = ["gold", "silver", "bronze"][i % 3]
        q.submit(Request(tenant=tenant, program=SSSP, source=s,
                         deadline=float(i)))
    served = tiny.scheduler.pump(q)
    assert len(served) == 6 and q.stats.rejected == 0
    g3 = tiny.dcsr.to_host_graph()
    for r in served:
        solo = run_hytm(g3, SSSP, source=r.request.source, config=cfg)
        np.testing.assert_array_equal(r.values, solo.values)
    assert tiny.scheduler.stats.max_device_bytes <= 2 * 9 * n

    # personalized PageRank serves through the same lanes (tolerance
    # program: oracle comparison lives in tests/test_serve.py)
    ppr = dataclasses.replace(PPR, tolerance=1e-7)
    r = tiny.query(ppr, [0])[0]
    assert r.mode == "batched" and r.iterations > 0

    print(f"SELFCHECK OK ({len(jax.devices())} device(s)) — "
          f"stats: {svc.stats}; serve: {tiny.scheduler.stats} "
          f"cache: {tiny.cache.stats.as_dict()}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--selfcheck", action="store_true")
    ap.add_argument("--nodes", type=int, default=5000)
    ap.add_argument("--edges", type=int, default=80_000)
    ap.add_argument("--partitions", type=int, default=32)
    ap.add_argument("--algorithm", default="sssp",
                    choices=["sssp", "bfs", "cc", "wcc", "pagerank", "php",
                             "ppr"])
    ap.add_argument("--queries", type=int, default=16)
    ap.add_argument("--lanes", type=int, default=8)
    ap.add_argument("--update-batches", type=int, default=4)
    ap.add_argument("--update-size", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--device-budget-bytes", type=int, default=None,
                    help="device byte budget for in-flight lane state + "
                         "the warm cache's device tier (overflow spills "
                         "to host RAM; default: unbounded)")
    ap.add_argument("--lane-buckets", default=None,
                    help="comma-separated static lane bucket sizes for "
                         "the serving scheduler (default: powers of two "
                         "up to --lanes); admission never recompiles")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record the run through repro.obs and write a "
                         "Chrome trace-event JSON to PATH "
                         "(chrome://tracing / Perfetto)")
    ap.add_argument("--calibrated", action="store_true",
                    help="use the calibrated LinkModel profile from the "
                         "autotune registry if one exists; a corrupt "
                         "profile warns and falls back to the shipped "
                         "constants (never fatal)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.selfcheck:
        selfcheck()
        return

    from repro.core.hytm import HyTMConfig
    from repro.graph.algorithms import ALGORITHMS
    from repro.graph.generators import rmat_graph
    from repro.stream import GraphService, random_batch

    program = ALGORITHMS[args.algorithm]
    g = rmat_graph(args.nodes, args.edges, seed=args.seed)
    if program.symmetrize:
        # WCC sweeps the undirected edge set; the streaming runtime is
        # built straight from this graph, so symmetrize before serving
        g = g.symmetrize()
    cfg = HyTMConfig(n_partitions=args.partitions)
    if args.calibrated:
        from repro.autotune.registry import load_profile_or_default

        cfg = dataclasses.replace(cfg, link=load_profile_or_default())
    buckets = (tuple(int(b) for b in args.lane_buckets.split(","))
               if args.lane_buckets else None)
    rec = None
    if args.trace:
        from repro.obs import TraceRecorder

        rec = TraceRecorder()
    svc = GraphService(g, cfg, max_lanes=args.lanes,
                       device_budget_bytes=args.device_budget_bytes,
                       lane_buckets=buckets, obs=rec)
    rng = np.random.default_rng(args.seed)

    sources = rng.integers(0, args.nodes, size=args.queries).tolist()
    t0 = time.monotonic()
    svc.query(program, sources)
    t_cold = time.monotonic() - t0

    t0 = time.monotonic()
    for _ in range(args.update_batches):
        svc.update(random_batch(
            svc.dcsr, rng,
            n_insert=args.update_size // 2, n_delete=args.update_size // 2,
        ))
        svc.query(program, sources[: max(1, args.lanes)])
    t_stream = time.monotonic() - t0

    s = svc.stats
    print(f"{args.algorithm}: {args.queries} cold queries in {t_cold:.2f}s "
          f"({args.queries / max(t_cold, 1e-9):.1f} q/s)")
    print(f"streaming: {args.update_batches} update batches "
          f"(x{args.update_size} edges) + warm queries in {t_stream:.2f}s")
    print(f"stats: hits={s.n_cache_hits} incremental={s.n_incremental} "
          f"full={s.n_full} sweeps={s.sweep_iterations} "
          f"updated_edges={s.update_edges} version={svc.version}")
    print(f"cache tiers: {svc.cache.stats.as_dict()} "
          f"(device_bytes={svc.cache.device_bytes})")
    if rec is not None:
        from repro.obs import write_chrome_trace

        write_chrome_trace(rec, args.trace)
        print(f"trace: {len(rec)} events -> {args.trace}")


if __name__ == "__main__":
    main()
