"""Δ-PageRank: ``pending_max``, the largest pending |Δ| of any run, and
``invariant_gap``, the largest relative gap of its rank from the
reference operator (see ``readings``).  The control is the reference
iterated in bfloat16."""

import numpy as np

from reference import to_bfloat16


def compare(traffic, ref, runs, seed):
    limits = dict(traffic["limits"])
    # the program holds Δ and its tolerance in float32, so a pending |Δ|
    # equal to float32(tolerance) is within it
    limits["pending_max"] = float(np.float32(limits["pending_max"]))
    wrong = set()
    pending = gap = 0.0
    for i, (_, values, delta) in enumerate(runs):
        p, g = readings(ref, traffic["damping"], values, delta)
        if p > limits["pending_max"] or g > limits["invariant_gap"]:
            wrong.add(i)
        pending, gap = max(pending, p), max(gap, g)
    return {"pending_max": (pending, limits["pending_max"]),
            "invariant_gap": (gap, limits["invariant_gap"])}, wrong


def readings(ref, damping, values, delta):
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


def control(traffic, ref, key):
    rank = ref.pagerank(traffic["damping"], rounding=to_bfloat16, max_iters=300)
    return rank, np.zeros_like(rank)
