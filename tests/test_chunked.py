"""Chunked device-resident convergence driver (HyTMConfig.sync_every).

The contract under test (core.hytm.hytm_chunk and its consumers):

* ``sync_every = K`` runs K iterations per compiled ``lax.while_loop``
  dispatch and must be **bit-identical** to an independent stepper —
  one jitted ``_iteration_impl`` per dispatch, defined below — for
  min-combine programs (values, iteration count, modeled transfer
  bytes, per-iteration engine picks) and tolerance-bounded for
  sum-combine (XLA may fuse the loop body differently than the
  standalone iteration);
* the early exit (while-condition on the previous iteration's
  ``next_active``) means a converged run never executes an iteration
  past convergence, so iteration counts match at every K, even when
  K >> iterations;
* the loop really batches: driver dispatches drop from O(iterations) to
  O(iterations / K) (monkeypatch-counted regression below);
* the same holds through every consumer: ``run_hytm``,
  ``run_hytm_sharded`` (subprocess on forced-host devices), and
  ``GraphService`` lane sweeps — autotune on and off.
"""

import dataclasses
from functools import partial
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from _forced_devices import run_forced_devices
from repro.core.hytm import (
    HyTMConfig,
    HyTMState,
    _iteration_impl,
    build_runtime,
    run_hytm,
)
from repro.graph.algorithms import BFS, CC, PAGERANK, SSSP
from repro.graph.generators import grid_mesh_graph, rmat_graph

_step = partial(jax.jit, static_argnames=("program", "config",
                                          "n_hub_partitions"))(_iteration_impl)


def _stepped(g, program, source, config):
    """The reference the chunk loop is held to: one jitted iteration per
    dispatch, with a host sync on ``next_active`` after each, until the
    frontier drains."""
    rt = build_runtime(g, config,
                       weighted_norm=program.use_delta and program.weighted)
    state = HyTMState(*program.init_state(g.n_nodes, source))
    rows = []
    while len(rows) < config.max_iters:
        state, info = _step(state, rt.csr, rt.parts, rt.zc_req, rt.inv_deg,
                            program, config, rt.n_hub_partitions, None,
                            rt.route)
        rows.append(jax.device_get(info))
        if int(info["next_active"]) == 0:
            break
    history = {k: np.stack([r[k] for r in rows]) for k in rows[0]}
    return SimpleNamespace(
        values=np.asarray(state.values), delta=np.asarray(state.delta),
        iterations=len(rows), history=history,
        total_transfer_bytes=float(np.sum(history["transfer_bytes"])))


def _assert_min_bit_exact(a, b):
    np.testing.assert_array_equal(a.values, b.values)
    assert a.iterations == b.iterations
    assert a.total_transfer_bytes == b.total_transfer_bytes
    np.testing.assert_array_equal(a.history["engines"], b.history["engines"])
    np.testing.assert_array_equal(
        a.history["transfer_bytes"], b.history["transfer_bytes"])


@pytest.mark.parametrize("cds_mode", ["hub", "delta"])
def test_chunked_min_bit_exact_vs_k1(cds_mode):
    """MIN programs: K in {1, 4, 64} reproduces the per-iteration stepper
    bit-for-bit (values, iterations, transfer bytes, engine picks) —
    including with the 'delta' CDS schedule, whose |Δ| segment-sum the
    iteration skips for min-combine programs (Δ is identically zero)."""
    g = rmat_graph(800, 8_000, seed=3)
    for prog in (SSSP, CC):
        base_cfg = HyTMConfig(n_partitions=8, sync_every=1, cds_mode=cds_mode)
        base = _stepped(g, prog, 0, base_cfg)
        assert base.iterations > 1
        for K in (1, 4, 64):
            chunked = run_hytm(
                g, prog, source=0,
                config=dataclasses.replace(base_cfg, sync_every=K),
            )
            _assert_min_bit_exact(base, chunked)
            # per_engine_time rides in history for the calibrator
            assert chunked.history["per_engine_time"].shape == (
                chunked.iterations, 3)


def test_chunked_sum_tolerance_bounded():
    """SUM programs: chunked results at K in {1, 4, 64} agree with the
    per-iteration stepper within the program tolerance (same iteration
    count on this CPU backend)."""
    g = rmat_graph(800, 8_000, seed=3)
    pr = dataclasses.replace(PAGERANK, tolerance=1e-6)
    base_cfg = HyTMConfig(n_partitions=8, sync_every=1, cds_mode="delta")
    base = _stepped(g, pr, None, base_cfg)
    for K in (1, 4, 64):
        chunked = run_hytm(
            g, pr, source=None,
            config=dataclasses.replace(base_cfg, sync_every=K),
        )
        assert chunked.iterations == base.iterations
        np.testing.assert_allclose(
            base.values + base.delta, chunked.values + chunked.delta,
            rtol=0, atol=1e-5,
        )
        np.testing.assert_allclose(
            base.total_transfer_bytes, chunked.total_transfer_bytes,
            rtol=1e-6,
        )


def test_chunked_autotune_min_values_identical():
    """With online feedback on, corrections may resteer engine choices
    and sweep order, but min-combine fixpoints are unique: final values
    match the untuned K=1 run bit-for-bit at every K."""
    g = rmat_graph(800, 8_000, seed=5)
    base = run_hytm(g, SSSP, source=0,
                    config=HyTMConfig(n_partitions=8, sync_every=1))
    for K in (1, 4, 64):
        tuned = run_hytm(
            g, SSSP, source=0,
            config=HyTMConfig(n_partitions=8, sync_every=K, autotune=True),
        )
        np.testing.assert_array_equal(base.values, tuned.values)
        assert tuned.engine_corrections is not None
        assert tuned.engine_corrections.shape == (3,)


def test_chunked_early_exit_on_empty_frontier():
    """A run that is converged at iteration 1 (source with no out-edges)
    executes exactly one iteration whatever K — the chunk's early exit
    never overshoots convergence."""
    from repro.graph.csr import csr_from_edges

    # directed chain 0 -> 1 -> ... -> 19: the chain *end* has no
    # out-edges by construction, so BFS from it converges immediately
    n = 20
    g = csr_from_edges(n, np.arange(n - 1), np.arange(1, n),
                       np.ones(n - 1, np.float32))
    assert g.out_degrees[n - 1] == 0
    for K in (1, 8):
        res = run_hytm(g, BFS, source=n - 1,
                       config=HyTMConfig(n_partitions=2, sync_every=K))
        assert res.iterations == 1, K
        # ...while a run from the chain head needs the full diameter,
        # identically at any K
        res_head = run_hytm(g, BFS, source=0,
                            config=HyTMConfig(n_partitions=2, sync_every=K))
        if K == 1:
            base_iters = res_head.iterations
        else:
            assert res_head.iterations == base_iters
    assert base_iters > 8  # diameter-bound: the chunked run early-exits


def test_chunked_dispatch_count_regression():
    """The chunked loop really batches: ceil(iters/K) hytm_chunk
    dispatches at K=16, vs exactly ``iterations`` chunks of one at K=1
    (counted through the shared ``count_driver_dispatches`` monkeypatch
    seam)."""
    from repro.core.hytm import count_driver_dispatches

    g = grid_mesh_graph(120, 3, seed=0)  # diameter-bound: many iterations
    K = 16
    with count_driver_dispatches() as counts:
        res1 = run_hytm(g, BFS, source=0,
                        config=HyTMConfig(n_partitions=4, sync_every=1))
    assert counts["chunk"] == res1.iterations
    assert res1.iterations > 2 * K  # the workload is dispatch-bound

    with count_driver_dispatches() as counts:
        resK = run_hytm(g, BFS, source=0,
                        config=HyTMConfig(n_partitions=4, sync_every=K))
    _assert_min_bit_exact(res1, resK)
    assert counts["chunk"] == -(-resK.iterations // K)  # == ceil(iters/K)
    assert counts["chunk"] <= resK.iterations // K + 1  # the CI gate bound


def test_chunked_service_lanes_match_k1():
    """GraphService lane sweeps through the chunked driver: query results
    (batched lanes, cache, incremental after an update) are bit-identical
    to a sync_every=1 service, autotune on or off."""
    from repro.stream import GraphService, random_batch

    g = rmat_graph(500, 4_000, seed=9)
    sources = [0, 7, 33]
    results = {}
    for K in (1, 8):
        for tuned in (False, True):
            svc = GraphService(
                g, HyTMConfig(n_partitions=8, sync_every=K, autotune=tuned),
                max_lanes=4,
            )
            first = svc.query(SSSP, sources)
            rng = np.random.default_rng(9)
            svc.update(random_batch(svc.dcsr, rng, n_insert=24, n_delete=24))
            second = svc.query(SSSP, sources)
            assert all(r.mode == "incremental" for r in second)
            results[(K, tuned)] = (first, second)
    ref_first, ref_second = results[(1, False)]
    for key, (first, second) in results.items():
        for a, b in zip(ref_first, first):
            np.testing.assert_array_equal(a.values, b.values, err_msg=str(key))
        for a, b in zip(ref_second, second):
            np.testing.assert_array_equal(a.values, b.values, err_msg=str(key))


_SHARDED_CHUNK_SCRIPT = """
    import dataclasses
    import numpy as np
    from repro.core.hytm import HyTMConfig, run_hytm
    from repro.graph.algorithms import PAGERANK, SSSP
    from repro.graph.generators import rmat_graph

    g = rmat_graph(500, 4000, seed=7)
    pr = dataclasses.replace(PAGERANK, tolerance=1e-6)
    for prog, src, autotune in (
        (SSSP, 0, False), (SSSP, 0, True), (pr, None, False),
    ):
        cfg1 = HyTMConfig(
            n_partitions=8, async_sweep=False, mesh_axis="graph",
            sync_every=1, autotune=autotune,
            cds_mode="delta" if prog.combine else "hub",
        )
        cfgK = dataclasses.replace(cfg1, sync_every=4)
        a = run_hytm(g, prog, source=src, config=cfg1)
        b = run_hytm(g, prog, source=src, config=cfgK)
        if prog.combine == 0:
            np.testing.assert_array_equal(a.values, b.values)
            if not autotune:  # feedback timing is nondeterministic
                assert a.iterations == b.iterations
                assert a.total_transfer_bytes == b.total_transfer_bytes
                np.testing.assert_array_equal(
                    a.history["ici_bytes"], b.history["ici_bytes"])
        else:
            np.testing.assert_allclose(
                a.values + a.delta, b.values + b.delta, rtol=0, atol=1e-5)
            assert a.iterations == b.iterations
        # the chunked sharded run still matches the single-device oracle
        oracle = run_hytm(g, prog, source=src,
                          config=dataclasses.replace(cfgK, mesh_axis=None))
        if prog.combine == 0:
            np.testing.assert_array_equal(b.values, oracle.values)
        else:
            np.testing.assert_allclose(
                b.values, oracle.values, rtol=0, atol=1e-5)
        print("OK", prog.name, "autotune" if autotune else "plain",
              b.iterations)
"""


def test_chunked_sharded_matches_k1_and_oracle():
    """Sharded path on 4 forced-host devices: K=4 shard_mapped chunks
    reproduce chunks of one (bit-exact MIN with identical ICI accounting;
    tolerance-bounded SUM) and the single-device oracle, autotune on and
    off."""
    out = run_forced_devices(_SHARDED_CHUNK_SCRIPT, devices=4)
    assert out.count("OK") == 3
