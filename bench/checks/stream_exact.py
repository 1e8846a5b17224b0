"""Exact SSSP after every batch of an edge-update stream
(``keys/update_batches``).

A run's answer is held to the graph its batch leaves: the input edges
less the deletions and plus the insertions of batches 0..i, replayed in
NumPy (``key.stream.live(i)``) without the program's state, with a
``Reference`` of its own.  ``unreached`` (every run): the vertices by
which the finite answers miss the size of the source's component there.
``mismatched`` (``check_runs`` runs drawn from the seed): the vertices
whose distance differs from that reference's.

Two controls, each a step below the guarantee: ``stale``, the reference
on the graph one batch behind (read-your-writes broken), the one
``control`` gives; and ``bfloat16``, the reference's distances kept in
bfloat16, as ``exact`` gives for a static graph."""

import numpy as np

from loadgen import rng_for
from reference import Reference, to_bfloat16


def distances(key, behind: int = 0, rounding=None) -> np.ndarray:
    """The source's distances in the graph ``behind`` batches before the
    key's."""
    return Reference(key.stream.live(key.index - behind)).distances(
        key.source, rounding=rounding)


def compare(traffic, ref, runs, seed):
    wrong = set()
    unreached = 0
    for i, (key, values, _) in enumerate(runs):
        want = key.stream.source_component(key.index)[0]
        off = abs(int(np.isfinite(values).sum()) - want)
        unreached += off
        if off:
            wrong.add(i)
    sample = rng_for(seed, "check").permutation(len(runs))[:traffic["check_runs"]]
    mismatched = 0
    for i in sorted(sample):
        key, values, _ = runs[i]
        off = int(np.sum(values.astype(np.float64) != distances(key)))
        mismatched += off
        if off:
            wrong.add(i)
    limits = traffic["limits"]
    return {"unreached": (unreached, limits["unreached"]),
            "mismatched": (mismatched, limits["mismatched"])}, wrong


CONTROLS = {
    "stale": lambda key: distances(key, behind=1),
    "bfloat16": lambda key: distances(key, rounding=to_bfloat16),
}


def control(traffic, ref, key, kind: str = "stale"):
    return CONTROLS[kind](key), None
