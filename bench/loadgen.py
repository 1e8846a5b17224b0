"""The one generator every traffic mix goes through, and the comparison
that decides ``correct``.

A traffic mix is a JSON file under ``bench/traffic/`` (see its keys in
``PERF.md``): the program it runs, how its keys are drawn, what one run
covers, and the numbers compared with their limits.  Nothing here is
specific to one mix.
"""

from __future__ import annotations

import numpy as np

from graphs import EdgeList
from reference import Reference, drop_last_level, to_bfloat16


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A generator for one use of ``--seed``; any whole number works."""
    return np.random.default_rng([seed % 2**64, sum(map(ord, stream))])


def draw_keys(traffic: dict, edges: EdgeList, seed: int) -> list:
    """The warm-up key, then the window's keys in the order they run:
    ``key_pool`` of them, or as many as the graph has.

    ``graph500`` keys are distinct vertices with an edge to another
    vertex, drawn from ``--seed`` as the Graph500 spec samples its search
    keys; ``none`` runs the whole graph (``source=None``) every time.
    """
    kind, count = traffic["keys"], traffic["key_pool"] + 1
    if kind == "none":
        return [None] * count
    if kind != "graph500":
        raise ValueError(f"unknown key kind {kind!r}")
    loops = edges.src == edges.dst
    candidates = np.unique(np.concatenate([edges.src[~loops], edges.dst[~loops]]))
    picked = rng_for(seed, "keys").choice(
        candidates, min(count, len(candidates)), replace=False)
    return [int(k) for k in picked]


def covered_edges(traffic: dict, edges: EdgeList, ref: Reference, key) -> int:
    """Input edges one run covers: the key's component (Graph500's TEPS
    count, duplicates and self-loops included), or the whole graph."""
    if traffic["coverage"] == "all":
        return edges.m
    return int(ref.component_edges[ref.components[key]])


def compare(traffic: dict, ref: Reference, runs, seed: int) -> tuple[dict, set]:
    """The numbers compared, each as ``(value, limit)``, and the indices
    of runs found wrong.  ``runs`` holds ``(key, values, delta)`` of every
    run the window completed."""
    limits = dict(traffic["limits"])
    if "pending_max" in limits:
        # the program holds Δ and its tolerance in float32, so a pending
        # |Δ| equal to float32(tolerance) is within it
        limits["pending_max"] = float(np.float32(limits["pending_max"]))
    kind = traffic["check"]
    wrong: set[int] = set()
    if kind == "exact":
        unit = traffic["program"] == "bfs"
        unreached = 0
        for i, (key, values, _) in enumerate(runs):
            want = int(ref.component_size[ref.components[key]])
            off = abs(int(np.isfinite(values).sum()) - want)
            unreached += off
            if off:
                wrong.add(i)
        sample = rng_for(seed, "check").permutation(len(runs))[:traffic["check_runs"]]
        mismatched = 0
        for i in sorted(sample):
            key, values, _ = runs[i]
            want = ref.distances(key, unit=unit)
            off = int(np.sum(values.astype(np.float64) != want))
            mismatched += off
            if off:
                wrong.add(i)
        numbers = {"unreached": unreached, "mismatched": mismatched}
    elif kind == "pagerank":
        damping = traffic["damping"]
        pending = gap = 0.0
        for i, (_, values, delta) in enumerate(runs):
            p, g = pagerank_readings(ref, damping, values, delta)
            if p > limits["pending_max"] or g > limits["invariant_gap"]:
                wrong.add(i)
            pending, gap = max(pending, p), max(gap, g)
        numbers = {"pending_max": pending, "invariant_gap": gap}
    else:
        raise ValueError(f"unknown check {kind!r}")
    return {k: (v, limits[k]) for k, v in numbers.items()}, wrong


def pagerank_readings(ref: Reference, damping: float, values, delta):
    """Δ-PageRank's answer is the rank x = values + Δ with every pending
    |Δ| under the tolerance, and x obeys x = (1 - d) + d·Aᵀ D⁻¹ values
    exactly: whatever was consumed into ``values`` has been pushed to the
    neighbours.  Together the two pin x to the fixpoint up to the pending
    mass.  Returns (largest pending |Δ|, largest relative gap of x from
    the reference operator applied to ``values``)."""
    values = np.asarray(values, np.float64)
    delta = np.asarray(delta, np.float64)
    want = ref.push(values, damping)
    gap = np.abs(values + delta - want) / want
    if not np.all(np.isfinite(gap)) or not np.all(np.isfinite(delta)):
        return float("inf"), float("inf")
    return float(np.max(np.abs(delta))), float(np.max(gap))


def control(traffic: dict, ref: Reference, key):
    """The control's answer for one key, as (values, delta): the
    reference a step below what the configuration states (see
    ``reference``'s docstring)."""
    if traffic["check"] == "pagerank":
        rank = ref.pagerank(traffic["damping"], rounding=to_bfloat16, max_iters=300)
        return rank, np.zeros_like(rank)
    if traffic["program"] == "bfs":
        return drop_last_level(ref.distances(key, unit=True)), None
    return ref.distances(key, rounding=to_bfloat16), None
