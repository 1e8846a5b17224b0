"""Exact distances (SSSP) or levels (BFS).

``unreached``: over every run, the vertices by which the finite answers
miss the key's component size.  ``mismatched``: over ``check_runs`` runs
drawn from the seed, the vertices whose answer differs from the
reference's.  The control is the reference a step below the stated
guarantee: SSSP in bfloat16, BFS with its deepest level unreached."""

import numpy as np

from loadgen import rng_for
from reference import drop_last_level, to_bfloat16


def compare(traffic, ref, runs, seed):
    unit = traffic["program"] == "bfs"
    wrong = set()
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
        off = int(np.sum(values.astype(np.float64) != ref.distances(key, unit=unit)))
        mismatched += off
        if off:
            wrong.add(i)
    limits = traffic["limits"]
    return {"unreached": (unreached, limits["unreached"]),
            "mismatched": (mismatched, limits["mismatched"])}, wrong


def control(traffic, ref, key):
    if traffic["program"] == "bfs":
        return drop_last_level(ref.distances(key, unit=True)), None
    return ref.distances(key, rounding=to_bfloat16), None
