"""Warm-start incremental recomputation after edge-update batches.

Instead of re-running ``run_hytm`` from ``program.init_state`` on the
post-update graph, seed the frontier from update-affected vertices only
and let the unchanged Algorithm-1 machinery (cost model, engine
selection, priority sweep) converge the *residual* work.  The evolving
frontier this produces — small, shifting, re-activated per batch — is
exactly the regime HyTM's per-iteration engine re-selection targets.

Seeding rules by program family:

* **MIN (traversal)** — monotone relaxation can absorb improvements but
  never un-derive a value, so:
    - insertions (and reweights to a smaller weight) activate the edge's
      source: the new edge relaxes on the next sweep;
    - deletions (and reweights to a larger weight) conservatively
      invalidate every vertex whose current value *routed through* a
      removed edge — ``values[v] == edge_message(values[u], w_old)`` —
      then propagate invalidation along the same routed-through relation
      over the live edges to a fixpoint.  Invalidated vertices reset to
      their init values; their live in-neighbors (and, for programs with
      finite init values like CC, the reset vertices themselves) seed the
      frontier.  Over-invalidation is safe: alternative routes re-derive
      the value during the sweep.
* **SUM (accumulative)** — the Δ-invariant says every consumed δ at u
  pushed ``damping * δ * w/W(u)`` along each out-edge, so after u's
  out-distribution changes from p_old to p_new the already-distributed
  mass ``values[u]`` is corrected by injecting *signed* deltas
  ``damping * values[u] * (p_new(x) - p_old(x))`` at each neighbor x
  (pending residual δ follows the new distribution automatically).  The
  core frontiers propagate ``|Δ| > tolerance`` so negative corrections
  travel like positive ones.

The seeding is the host span ``stream.seed`` (stats ``reset``, the
vertices reset to their init values, and ``seeded``, the frontier it
seeds), and, given a recorder, the counters ``stream.reset`` and
``stream.seeded``.

Equivalence contract (tests/test_stream.py): the warm-started run matches
a from-scratch ``run_hytm`` on the post-update graph — bit-exact for MIN
programs (the converged fixpoint is unique and each value is the same
f32 path sum), within tolerance-bounded residual for SUM programs.
"""

from __future__ import annotations

from typing import Iterable, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.hytm import HyTMConfig, HyTMResult, HyTMState, run_hytm
from repro.graph.algorithms import MIN, VertexProgram
from repro.obs.trace import set_stats, span
from repro.stream.delta_csr import _TRACK, DeltaCSR, UpdateReport


def _routed_through(
    program: VertexProgram,
    values: np.ndarray,   # (n,) f32 — pre-update converged values
    src: np.ndarray,
    dst: np.ndarray,
    w: np.ndarray,
) -> np.ndarray:
    """Mask of edges whose destination value equals the edge's message —
    i.e. the destination's value may have been derived via this edge.
    Evaluated in f32 to match the device sweep bit-for-bit."""
    if len(src) == 0:
        return np.zeros(0, bool)
    vs = values[src].astype(np.float32)
    msg = np.asarray(
        program.edge_message(vs, w.astype(np.float32)), dtype=np.float32
    )
    return np.isfinite(vs) & (values[dst].astype(np.float32) == msg)


def seed_min(
    program: VertexProgram,
    values: np.ndarray,
    reports: Sequence[UpdateReport],
    dcsr: DeltaCSR,
    source: int | None,
) -> tuple[HyTMState, int, int]:
    """Frontier/state seed for traversal programs (see module docstring),
    with the number of vertices reset and the seeded frontier's size."""
    n = dcsr.n_nodes
    values = np.asarray(values, np.float32).copy()
    init_vals = np.asarray(program.init_state(n, source)[0])

    ins_src = np.concatenate([r.ins_src for r in reports]) if reports else np.zeros(0, np.int64)
    del_src = np.concatenate([r.del_src for r in reports]) if reports else np.zeros(0, np.int64)
    del_dst = np.concatenate([r.del_dst for r in reports]) if reports else np.zeros(0, np.int64)
    del_w = np.concatenate([r.del_w for r in reports]) if reports else np.zeros(0, np.float32)

    suspect = np.zeros(n, bool)
    routed = _routed_through(program, values, del_src, del_dst, del_w)
    suspect[del_dst[routed]] = True
    if source is not None:
        suspect[source] = False

    ls, ld, lw = dcsr.live_edges()
    routed_live = _routed_through(program, values, ls, ld, lw)
    while True:
        grow = routed_live & suspect[ls] & ~suspect[ld]
        if not grow.any():
            break
        suspect[ld[grow]] = True
        if source is not None:
            suspect[source] = False

    new_vals = values.copy()
    new_vals[suspect] = init_vals[suspect]

    frontier = np.zeros(n, bool)
    if len(ins_src):
        fin = np.isfinite(new_vals[ins_src])
        frontier[ins_src[fin]] = True
    feeds = suspect[ld] & np.isfinite(new_vals[ls])
    frontier[ls[feeds]] = True
    # programs with finite init values (CC) must push the reset labels out
    frontier[suspect & np.isfinite(new_vals)] = True

    return HyTMState(
        values=jnp.asarray(new_vals),
        delta=jnp.zeros(n, jnp.float32),
        frontier=jnp.asarray(frontier),
    ), int(suspect.sum()), int(frontier.sum())


def seed_sum(
    program: VertexProgram,
    values: np.ndarray,
    delta: np.ndarray,
    reports: Sequence[UpdateReport],
    dcsr: DeltaCSR,
) -> tuple[HyTMState, int, int]:
    """Correction-delta seed for accumulative programs, with the number of
    vertices reset (none) and the seeded frontier's size."""
    n = dcsr.n_nodes
    values = np.asarray(values, np.float32)
    new_delta = np.asarray(delta, np.float64).copy()
    damping = program.damping
    weighted = program.weighted

    for rep in reports:
        for u, (pre_d, pre_w) in rep.pre_adj.items():
            post_d, post_w = rep.post_adj[u]
            v_u = float(values[u])
            if v_u == 0.0:
                continue
            if weighted:
                w_old = float(pre_w.sum())
                w_new = float(post_w.sum())
                p_old = pre_w / w_old if w_old > 0 else pre_w
                p_new = post_w / w_new if w_new > 0 else post_w
            else:
                p_old = np.full(len(pre_d), 1.0 / max(len(pre_d), 1))
                p_new = np.full(len(post_d), 1.0 / max(len(post_d), 1))
            if len(pre_d):
                np.subtract.at(new_delta, pre_d, damping * v_u * p_old)
            if len(post_d):
                np.add.at(new_delta, post_d, damping * v_u * p_new)

    new_delta = new_delta.astype(np.float32)
    frontier = np.abs(new_delta) > program.tolerance
    return HyTMState(
        values=jnp.asarray(values),
        delta=jnp.asarray(new_delta),
        frontier=jnp.asarray(frontier),
    ), 0, int(frontier.sum())


def incremental_state(
    program: VertexProgram,
    values: np.ndarray,
    delta: np.ndarray,
    reports: Iterable[UpdateReport],
    dcsr: DeltaCSR,
    source: int | None,
    obs=None,
) -> HyTMState:
    """The warm state to converge from, seeded in the host span
    ``stream.seed``; ``obs`` (a ``repro.obs.TraceRecorder``) records the
    span and the counters ``stream.reset`` and ``stream.seeded``."""
    reports = list(reports)
    with span("stream.seed", obs, track=_TRACK) as opened:
        if program.combine == MIN:
            state, reset, seeded = seed_min(program, values, reports, dcsr, source)
        else:
            state, reset, seeded = seed_sum(program, values, delta, reports, dcsr)
        set_stats(opened, reset=reset, seeded=seeded)
        if obs is not None:
            obs.counter("stream.reset", reset, track=_TRACK)
            obs.counter("stream.seeded", seeded, track=_TRACK)
    return state


def run_incremental(
    dcsr: DeltaCSR,
    program: VertexProgram,
    reports: Iterable[UpdateReport],
    values: np.ndarray,
    delta: np.ndarray,
    source: int | None = 0,
    config: HyTMConfig | None = None,
    calibrator=None,
    mesh=None,
    obs=None,
    faults=None,
    retry=None,
) -> HyTMResult:
    """Converge the post-update graph from the warm (values, Δ) state of a
    previous converged run, seeding only update-affected vertices.

    ``reports`` are the ``DeltaCSR.apply`` reports for every batch applied
    since ``values``/``delta`` were computed, in order.

    With ``config.mesh_axis`` set the residual convergence runs *on the
    mesh*: the same host-side seeding builds the warm (values, Δ,
    frontier) triple, which re-enters the shard_mapped chunked driver
    replicated over the devices, sweeping ``dcsr``'s device-sharded
    (P_pad, B) grid (``DeltaCSR.sharded_runtime_for``).  Sharded
    equivalence guarantee: because seeding is identical and the sharded
    sweep reproduces the single-device ``async_sweep=False`` dataflow,
    the sharded incremental run is bit-identical to the single-device
    incremental run for MIN programs — values, iterations, transfer
    accounting, engine picks — and tolerance-bounded for SUM programs
    (tests/test_stream_sharded.py).  ``mesh`` optionally pins the device
    mesh (defaults to every visible device).

    The run inherits ``config.sync_every``: with K > 1 the residual
    convergence runs through the chunked device-resident driver
    (``core.hytm.hytm_chunk``, or ``graph_shard.make_sharded_chunk`` on
    the mesh).  Incremental runs are exactly where the chunk's early exit
    matters — warm starts converge in a handful of iterations, and the
    while-loop condition stops the chunk the moment the residual frontier
    drains, so a short run never pays for K iterations.  The seeded state
    is materialized fresh per run (``incremental_state`` builds new
    device arrays), so the chunked driver's state donation never
    invalidates the caller's cached warm (values, Δ) buffers."""
    config = config if config is not None else dcsr.config
    state = incremental_state(program, values, delta, reports, dcsr, source,
                              obs=obs)
    if config.mesh_axis is not None:
        runtime = dcsr.sharded_runtime_for(
            program, mesh=mesh, axis=config.mesh_axis)
        return run_hytm(
            None, program, source=source, config=config,
            runtime=runtime, mesh=runtime.mesh, initial_state=state,
            calibrator=calibrator, obs=obs, faults=faults, retry=retry,
        )
    return run_hytm(
        None, program, source=source, config=config,
        runtime=dcsr.runtime_for(program), initial_state=state,
        calibrator=calibrator, obs=obs, faults=faults, retry=retry,
    )
