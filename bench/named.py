"""The code a name stands for: ``bench/<kind>/<name>.py``.

Every name that ``BENCHMARK.json``, a configuration or a traffic mix
gives is a file of its own, loaded here, so a new deployment joins the
benchmark with new files and entries only:

* a configuration's ``generator.kind``: ``generators/<kind>.py``,
  ``generate(spec, rng) -> (src, dst)``, the input edges;
* a traffic mix's ``keys``: ``keys/<name>.py``,
  ``draw(traffic, edges, seed) -> list``, the warm-up key, then the
  window's keys in the order they run (any objects);
* its ``coverage``: ``coverage/<name>.py``,
  ``covered(traffic, edges, ref, key) -> int``, the input edges one run
  covers;
* its ``check``: ``checks/<name>.py``, ``compare(traffic, ref, runs,
  seed) -> (numbers, wrong)`` and ``control(traffic, ref, key)``;
* its ``driver`` (``run_hytm`` where it names none): ``drivers/<name>.py``,
  ``prepare(config, traffic, edges, jax) -> (run_one, setup)``;
* a per-layer metric's ``name``: ``metrics/<name>.py``, ``read(ctx)``.
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


class Refused(SystemExit):
    """The run cannot measure what the cell asks for; no result."""

    def __init__(self, why: str):
        print(f"bench: {why}", file=sys.stderr, flush=True)
        super().__init__(2)


def load(kind: str, name: str, bench: Path = BENCH):
    """The module ``<bench>/<kind>/<name>.py``, or a refusal naming it."""
    path = Path(bench) / kind / f"{name}.py"
    if not path.is_file():
        raise Refused(f"no {kind} named {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
