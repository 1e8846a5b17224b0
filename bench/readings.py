#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program over a dozen
seeds or more and the control over three or more, at the cell's size.

    python3 bench/readings.py --workload kron17-sssp --seeds 1-12 \\
        --control-seeds 1-3 --seconds 10

One process sets the cell up once (every seed shares the
configuration's graph), then for each seed runs the closed loop for
``--seconds`` on the keys that seed draws and compares the answers as
a run does.  For each control seed the control (``loadgen.control``)
takes the program's place on that seed's keys.  Prints one JSON line per
reading, then the largest program reading and the smallest control
reading of each number.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time

import loadgen
import run
from reference import Reference


def seeds(text: str) -> list[int]:
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    traffic = cell.traffic
    jax, device, _, meter = run.start_jax(cell, run.ROOT)
    edges, run_one, _ = run.prepare(cell, jax)
    program_runs = {}
    for seed in args.seeds:
        keys = loadgen.draw_keys(traffic, edges, seed)
        run_one(keys[0])
        _, runs = run.window(run_one, keys[1:], args.seconds, None,
                             lambda index, key: contextlib.nullcontext())
        program_runs[seed] = [(r.key, r.values, r.delta) for r in runs]
    compiles = meter.compiles
    del run_one
    gc.collect()
    ref = Reference(edges)

    worst, least = {}, {}
    for seed, answers in program_runs.items():
        t = time.monotonic()
        checks, wrong = loadgen.compare(traffic, ref, answers, seed)
        line = {"side": "program", "seed": seed, "runs": len(answers),
                "wrong": len(wrong), "compare_s": time.monotonic() - t,
                **{k: v for k, (v, _) in checks.items()}}
        print(json.dumps(line), flush=True)
        for k, (v, _) in checks.items():
            worst[k] = max(worst.get(k, v), v)
    for seed in args.control_seeds:
        keys = [k for k, _, _ in program_runs[seed]][:traffic.get("check_runs", 1)]
        answers = [(k, *loadgen.control(traffic, ref, k)) for k in keys]
        checks, wrong = loadgen.compare(traffic, ref, answers, seed)
        print(json.dumps({"side": "control", "seed": seed, "runs": len(answers),
                          "wrong": len(wrong),
                          **{k: v for k, (v, _) in checks.items()}}), flush=True)
        for k, (v, _) in checks.items():
            least[k] = min(least.get(k, v), v)
    print(json.dumps({"workload": cell.name, "device": device,
                      "compiles": compiles,
                      "program_max": worst, "control_min": least}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
