#!/usr/bin/env python3
"""Smoke run of the HyTM engine on a TPU, through its user entry points.

    python chip_smoke.py                # one chip: the default phases
    python chip_smoke.py --four-chips   # four chips: the mesh path only

The deployment is ``configs/hytgraph_paper.py``: an RMAT graph of
100,000 vertices and 1.6 M edges (seed 0) in 64 partitions, with the
Pallas kernels that ``HyTMConfig(use_kernels="auto")`` picks on a TPU.
Default phases, all in this one process:

1. device   — the backend is a TPU, ``use_kernels="auto"`` resolves to
               the kernels, and Pallas interpret mode is off;
2. kernels  — each graph kernel at the deployment's block width against
               its reference (bit-exact, SUM within tolerance), with its
               steady per-call time;
3. engines  — each engine's kernel path against its oracle engine on a
               real partition block (MIN bit-exact, SUM within tolerance);
4. sssp     — ``run_hytm`` with the kernels is bit-identical to the
               oracle engines and matches ``reference_sssp``;
5. pagerank — Δ-PageRank (tolerance 1e-5) with the kernels agrees with
               the oracle engines and with ``reference_pagerank``;
6. service  — ``GraphService``: 8 SSSP sources answered in lanes equal
               solo runs; after one update batch (32 inserts, 32 deletes)
               the incremental answers equal from-scratch runs.

``--four-chips`` runs SSSP over a 4-device ``graph`` mesh, replicated
and owner-sharded, each bit-identical to the single-device
``async_sweep=False`` run, and nothing else.

Every phase prints one line: wall seconds (host clock around work that
ends in ``block_until_ready``), compile seconds and persistent-cache hits
(from ``jax.monitoring``), iterations, engine picks and the device's
peak bytes.  These are single smoke runs, not benchmark numbers.  A
failed check raises; the script then exits non-zero, and only a run in
which every phase passed prints the closing JSON line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.hytgraph_paper import CONFIG  # noqa: E402
from repro.core.cost_model import ENGINE_NAMES  # noqa: E402
from repro.core.hytm import build_runtime, run_hytm  # noqa: E402
from repro.graph.algorithms import (  # noqa: E402
    PAGERANK,
    SSSP,
    reference_pagerank,
    reference_sssp,
)
from repro.graph.generators import rmat_graph  # noqa: E402
from repro.kernels.runtime import interpret_mode, resolve_use_kernels  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

SEED = 0
PR_TOLERANCE = 1e-5
N_LANES = 8
# Δ-PageRank stops once every pending |Δ| is under PR_TOLERANCE, so two
# runs that reassociate their sums differently (kernel vs oracle) and the
# exact fixpoint differ by about the mass left pending; these bounds leave
# room for that on hub ranks in the hundreds.
PR_KERNEL_RTOL = PR_KERNEL_ATOL = 1e-3
PR_REFERENCE_RTOL = PR_REFERENCE_ATOL = 1e-2


class CompileMeter:
    """Seconds spent tracing, lowering and compiling, and persistent-cache
    hits, as ``jax.monitoring`` reports them."""

    def __init__(self):
        self.seconds = 0.0
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.seconds, self.requests, self.hits


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def picks(res) -> dict:
    eng = np.asarray(res.history["engines"])
    return {ENGINE_NAMES[e]: int((eng == e).sum()) for e in sorted(ENGINE_NAMES)}


def phase(name: str, meter: CompileMeter, fn):
    """Run one phase and print its line; ``fn`` returns the fields."""
    c0, r0, h0 = meter.snapshot()
    t0 = time.monotonic()
    fields = fn()
    wall = time.monotonic() - t0
    c1, r1, h1 = meter.snapshot()
    line = {"wall_s": wall, "compile_s": c1 - c0,
            "cache_hits": f"{h1 - h0}/{r1 - r0}", **fields,
            "peak_bytes": peak_bytes()}
    print(f"[{name}] " + " ".join(f"{k}={v}" for k, v in line.items()),
          flush=True)


def per_call_ms(fn, *args, reps: int = 10) -> float:
    jax.block_until_ready(fn(*args))
    t0 = time.monotonic()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.monotonic() - t0) / reps * 1e3


def check_device() -> dict:
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"no TPU: JAX's default backend is {backend!r}")
    if not resolve_use_kernels("auto") or interpret_mode():
        raise SystemExit("the compiled Pallas kernels are not selected")
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def check_kernels(n: int, block: int) -> dict:
    """Each graph kernel at the engine's widths against its reference."""
    import jax.numpy as jnp

    from repro.kernels.frontier_compact.ops import frontier_compact
    from repro.kernels.frontier_compact.ref import frontier_compact_ref
    from repro.kernels.hyb_gather.hyb_gather import PAD
    from repro.kernels.hyb_gather.ops import hyb_gather
    from repro.kernels.hyb_gather.ref import hyb_gather_ref
    from repro.kernels.segment_spmm.ops import segment_spmm
    from repro.kernels.segment_spmm.ref import segment_spmm_ref

    rng = np.random.default_rng(SEED)
    seg = jnp.asarray(rng.integers(0, n, block), jnp.int32)
    valid = jnp.asarray(rng.random(block) < 0.5)
    out = {}

    msg = jnp.asarray(rng.random((block, 1)), jnp.float32)
    got = segment_spmm(msg, seg, n, valid, combine="min")
    want = segment_spmm_ref(msg, seg, n, valid, combine="min")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    out["spmm_min_ms"] = per_call_ms(
        lambda: segment_spmm(msg, seg, n, valid, combine="min"))

    msg = jnp.asarray(rng.random((block, 2)), jnp.float32)
    got = segment_spmm(msg, seg, n, valid)
    want = segment_spmm_ref(msg, seg, n, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    out["spmm_sum_ms"] = per_call_ms(lambda: segment_spmm(msg, seg, n, valid))

    # the engines move int32 fields (ids, weight bits) through these two
    bits = lambda shape: jnp.asarray(
        rng.integers(-2**31, 2**31, shape, dtype=np.int64), jnp.int32)
    vals = bits((block, 4))
    got, cnt = frontier_compact(vals, valid)
    want, wcnt = frontier_compact_ref(vals, valid)
    assert int(cnt) == int(wcnt), (int(cnt), int(wcnt))
    np.testing.assert_array_equal(np.asarray(got)[:int(cnt)],
                                  np.asarray(want)[:int(cnt)])
    out["compact_ms"] = per_call_ms(lambda: frontier_compact(vals, valid))

    edges = bits((block, 4))
    windows = -(-block // PAD)
    starts = jnp.asarray(rng.integers(0, block, windows), jnp.int32)
    degs = jnp.asarray(rng.integers(0, PAD + 1, windows), jnp.int32)
    np.testing.assert_array_equal(np.asarray(hyb_gather(edges, starts, degs)),
                                  np.asarray(hyb_gather_ref(edges, starts, degs)))
    out["gather_ms"] = per_call_ms(lambda: hyb_gather(edges, starts, degs))
    return out


def check_engines(rt) -> dict:
    """Each engine's kernel path against its oracle on the hub partition's
    real edge block, half its sources active, for a MIN and a SUM program."""
    import jax.numpy as jnp

    from repro.core.engines import ENGINE_FNS, EdgeBlock
    from repro.graph.algorithms import MIN

    rng = np.random.default_rng(SEED)
    n, B = rt.csr.n_nodes, rt.parts.block_size
    start, count = int(rt.parts.edge_start[0]), int(rt.parts.part_edges[0])
    src = rt.csr.edge_src[start:start + B]
    frontier = jnp.asarray(rng.random(n) < 0.5)
    block = EdgeBlock(src=src, dst=rt.csr.edge_dst[start:start + B],
                      weight=rt.csr.edge_weight[start:start + B],
                      active=frontier[src] & (jnp.arange(B) < count))
    operand = jnp.asarray(rng.random(n), jnp.float32)
    out = {"active_edges": int(block.active.sum())}
    for prog in (SSSP, PAGERANK):
        for fn in ENGINE_FNS:
            relax = jax.jit(fn, static_argnums=(2, 3, 4))
            got = relax(block, operand, n, prog, True)
            want = relax(block, operand, n, prog, False)
            name = f"{fn.__name__}/{prog.name}"
            np.testing.assert_array_equal(got.touched, want.touched, err_msg=name)
            if prog.combine == MIN:
                np.testing.assert_array_equal(got.agg, want.agg, err_msg=name)
            else:
                np.testing.assert_allclose(got.agg, want.agg, rtol=1e-5,
                                           atol=1e-6, err_msg=name)
            out[f"{name}_ms"] = per_call_ms(
                lambda: relax(block, operand, n, prog, True))
    return out


def run_pair(g, program, source, cfg, runtime):
    """Kernel run (cold, then steady) and the oracle-engine run."""
    cold = run_hytm(g, program, source=source, config=cfg, runtime=runtime)
    steady = run_hytm(g, program, source=source, config=cfg, runtime=runtime)
    oracle = run_hytm(g, program, source=source, runtime=runtime,
                      config=dataclasses.replace(cfg, use_kernels=False))
    np.testing.assert_array_equal(cold.values, steady.values)
    return cold, steady, oracle


def check_sssp(g, cfg, rt) -> dict:
    cold, steady, oracle = run_pair(g, SSSP, 0, cfg, rt)
    np.testing.assert_array_equal(steady.values, oracle.values)
    assert steady.iterations == oracle.iterations
    np.testing.assert_array_equal(steady.history["engines"],
                                  oracle.history["engines"])
    assert np.allclose(steady.values, reference_sssp(g, 0))
    return {"iterations": steady.iterations,
            "cold_run_s": cold.wall_seconds,
            "steady_run_s": steady.wall_seconds,
            "oracle_run_s": oracle.wall_seconds,
            "partition_visits": 2 * cfg.n_partitions * steady.iterations,
            "picks": picks(steady), "reached": int(np.isfinite(steady.values).sum())}


def check_pagerank(g, cfg, rt) -> dict:
    # PageRank is unweighted (no weighted_norm), so the SSSP runtime serves
    prog = dataclasses.replace(PAGERANK, tolerance=PR_TOLERANCE)
    cold, steady, oracle = run_pair(g, prog, None, cfg, rt)
    rank = steady.values + steady.delta
    ref = reference_pagerank(g)
    np.testing.assert_allclose(rank, oracle.values + oracle.delta,
                               rtol=PR_KERNEL_RTOL, atol=PR_KERNEL_ATOL)
    np.testing.assert_allclose(rank, ref, rtol=PR_REFERENCE_RTOL,
                               atol=PR_REFERENCE_ATOL)
    return {"iterations": steady.iterations,
            "oracle_iterations": oracle.iterations,
            "cold_run_s": cold.wall_seconds,
            "steady_run_s": steady.wall_seconds,
            "oracle_run_s": oracle.wall_seconds,
            "max_err_vs_oracle": float(np.max(np.abs(rank - oracle.values - oracle.delta))),
            "max_err_vs_reference": float(np.max(np.abs(rank - ref))),
            "partition_visits": 2 * cfg.n_partitions * steady.iterations,
            "picks": picks(steady)}


def check_service(g, cfg, rt) -> dict:
    """``launch/serve_graph.py --selfcheck`` steps 1-3 at full size."""
    from repro.stream import GraphService, random_batch

    rng = np.random.default_rng(SEED)
    sources = [0, *rng.choice(np.arange(1, g.n_nodes), N_LANES - 1,
                              replace=False).tolist()]
    svc = GraphService(g, cfg, max_lanes=N_LANES)
    out = {}

    t0 = time.monotonic()
    batched = svc.query(SSSP, sources)
    out["lanes_query_s"] = time.monotonic() - t0
    assert all(r.mode == "batched" for r in batched)
    t0 = time.monotonic()
    for s, r in zip(sources, batched):
        solo = run_hytm(g, SSSP, source=s, config=cfg, runtime=rt)
        np.testing.assert_array_equal(r.values, solo.values)
    out["solo_runs_s"] = time.monotonic() - t0

    t0 = time.monotonic()
    svc.update(random_batch(svc.dcsr, rng, n_insert=32, n_delete=32))
    out["update_s"] = time.monotonic() - t0
    t0 = time.monotonic()
    post = svc.query(SSSP, sources)
    out["incremental_query_s"] = time.monotonic() - t0
    assert all(r.mode == "incremental" for r in post)
    g2 = svc.dcsr.to_host_graph()
    rt2 = build_runtime(g2, cfg)
    t0 = time.monotonic()
    for s, r in zip(sources, post):
        fresh = run_hytm(g2, SSSP, source=s, config=cfg, runtime=rt2)
        np.testing.assert_array_equal(r.values, fresh.values)
    out["from_scratch_runs_s"] = time.monotonic() - t0
    out["lane_iterations"] = [r.iterations for r in batched]
    out["incremental_iterations"] = [r.iterations for r in post]
    return out


def check_mesh(g, cfg) -> dict:
    """SSSP over a 4-device mesh, replicated and owner-sharded, against
    the single-device ``async_sweep=False`` run."""
    from repro.launch.mesh import make_graph_mesh

    if len(jax.devices()) != 4:
        raise SystemExit(f"--four-chips needs 4 devices, found {len(jax.devices())}")
    sync = dataclasses.replace(cfg, async_sweep=False)
    single = run_hytm(g, SSSP, source=0, config=sync)
    assert np.allclose(single.values, reference_sssp(g, 0))
    out = {"single_s": single.wall_seconds,
           "iterations": single.iterations}
    mesh = make_graph_mesh(4)
    for layout in ("replicated", "owner"):
        mcfg = dataclasses.replace(sync, mesh_axis="graph", vertex_sharding=layout)
        cold = run_hytm(g, SSSP, source=0, config=mcfg, mesh=mesh)
        steady = run_hytm(g, SSSP, source=0, config=mcfg, mesh=mesh)
        for res in (cold, steady):
            np.testing.assert_array_equal(res.values, single.values)
            assert res.iterations == single.iterations
            np.testing.assert_array_equal(res.history["engines"],
                                          single.history["engines"])
        out[f"{layout}_cold_s"] = cold.wall_seconds
        out[f"{layout}_steady_s"] = steady.wall_seconds
    out["picks"] = picks(single)
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device mesh path and its comparison")
    args = ap.parse_args()

    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    device = check_device()
    print(f"[device] kind={device['kind']} count={device['count']} "
          f"cache_dir={cache_dir}", flush=True)

    t0 = time.monotonic()
    g = rmat_graph(CONFIG.n_nodes, CONFIG.n_edges, seed=SEED)
    cfg = CONFIG.hytm
    rt = build_runtime(g, cfg)
    block = rt.parts.block_size
    print(f"[graph] n={g.n_nodes} edges={g.n_edges} partitions="
          f"{cfg.n_partitions} block={block} "
          f"build_s={time.monotonic() - t0}", flush=True)

    if args.four_chips:
        phase("mesh", meter, lambda: check_mesh(g, cfg))
    else:
        phase("kernels", meter, lambda: check_kernels(g.n_nodes, block))
        phase("engines", meter, lambda: check_engines(rt))
        phase("sssp", meter, lambda: check_sssp(g, cfg, rt))
        phase("pagerank", meter, lambda: check_pagerank(g, cfg, rt))
        phase("service", meter, lambda: check_service(g, cfg, rt))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
