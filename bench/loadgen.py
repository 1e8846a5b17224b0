"""The one generator every traffic mix goes through, and the comparison
that decides ``correct``.

A traffic mix is a JSON file under ``bench/traffic/`` (see its keys in
``PERF.md``): the program it runs, and the names of its ``keys``, its
``coverage``, its ``check`` and its ``driver``, each a file of its own
(``named``), with the numbers compared and their limits.  Nothing here
is specific to one mix.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

from graphs import EdgeList
from named import BENCH, load
from reference import Reference


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A generator for one use of ``--seed``; any whole number works."""
    return np.random.default_rng([seed % 2**64, sum(map(ord, stream))])


def draw_keys(traffic: dict, edges: EdgeList, seed: int, bench: Path = BENCH) -> list:
    """The warm-up key, then the window's keys in the order they run."""
    return load("keys", traffic["keys"], bench).draw(traffic, edges, seed)


def covered_edges(traffic: dict, edges: EdgeList, ref: Reference, key,
                  bench: Path = BENCH) -> int:
    """Input edges one run covers."""
    return load("coverage", traffic["coverage"], bench).covered(traffic, edges, ref, key)


def compare(traffic: dict, ref: Reference, runs, seed: int,
            bench: Path = BENCH) -> tuple[dict, set]:
    """The numbers compared, each as ``(value, limit)``, and the indices
    of runs found wrong.  ``runs`` holds ``(key, values, delta)`` of every
    run the window completed."""
    return load("checks", traffic["check"], bench).compare(traffic, ref, runs, seed)


def control(traffic: dict, ref: Reference, key, bench: Path = BENCH):
    """The control's answer for one key, as (values, delta)."""
    return load("checks", traffic["check"], bench).control(traffic, ref, key)
