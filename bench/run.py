#!/usr/bin/env python3
"""One run of one benchmark cell, on the chip the process finds.

    python3 bench/run.py --workload kron17-sssp --seed 7 --seconds 30 --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``configs/<name>.json``: the graph and the ``HyTMConfig`` overrides) and
a traffic mix (``traffic/<name>.json``); every name these give is a file
of its own (``named``).  The run:

1. fails, printing no result, unless JAX's backend is a TPU with as many
   chips as the cell asks for and a ``device_kind`` in ``peaks.json``;
2. set-up: generates the configuration's graph, sets the traffic's
   driver up on it (``run_hytm`` on a runtime built once, where the mix
   names no driver) and makes one untimed warm-up run, which compiles or
   loads from the compile cache at ``<checkout>/.jax_cache``;
3. window: runs keys drawn from ``--seed`` back to back through the
   driver for ``--seconds`` (a closed loop; every run is whole);
   with ``--trace 1`` it profiles ``trace_runs`` whole runs instead;
4. after the window: reads the device's peak bytes, frees the program's
   state, and compares the runs' answers with the NumPy reference.

With ``--trace 0`` the result carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics (``metrics/<name>.py``).  The
last line of standard output is the result as one JSON object; the last
lines of standard error are the numbers compared, each beside its limit.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent  # the checkout: BENCHMARK.json, bench/, src/
sys.path.insert(0, str(BENCH))

import graphs  # noqa: E402
import loadgen  # noqa: E402
import stats  # noqa: E402
import tracereduce  # noqa: E402
from named import Refused, load  # noqa: E402
from reference import Reference  # noqa: E402


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Everything the run needs, found by the names in BENCHMARK.json."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    bench = root / "bench"

    def mine(metric):
        return name in metric.get("workloads", [name])

    return Cell(
        name=name, chips=cell["chips"],
        config=json.loads((root / config["file"]).read_text()),
        traffic=json.loads((bench / "traffic" / f"{cell['traffic']}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if mine(m)],
        per_layer=[m for m in spec["per_layer"] if mine(m)],
    )


def load_peaks(kind: str, root: Path = ROOT) -> dict:
    devices = json.loads((root / "bench" / "peaks.json").read_text())["devices"]
    if kind not in devices:
        raise Refused(f"device_kind {kind!r} is not in peaks.json")
    return devices[kind]


def check_device(backend: str, devices, chips: int) -> dict:
    """The chip the cell runs on, or a refusal: never the CPU."""
    if backend != "tpu":
        raise Refused(f"no TPU: JAX's backend is {backend!r}")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX finds {len(devices)}")
    dev = devices[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


class CompileMeter:
    """Compile seconds, compiles and persistent-cache hits, as
    ``jax.monitoring`` reports them."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = self.requests = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


@dataclasses.dataclass
class Run:
    key: object
    start: float
    end: float
    values: np.ndarray
    delta: np.ndarray
    engines: np.ndarray   # (iterations, P) engine picks
    edges: int = 0        # input edges covered, set after the window

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Context:
    """What a per-layer metric reads (``metrics/<name>.py``)."""

    runs: list
    traffic: dict
    peaks: dict
    trace: tracereduce.Trace | None


def read_metric(name: str, ctx: Context, root: Path = ROOT):
    return load("metrics", name, root / "bench").read(ctx)


def span_key(index: int, key) -> int:
    """The ``key`` a run's annotation carries: -1 for a whole-graph run
    (``None``), the vertex for an integer key, else the run's index."""
    return -1 if key is None else key if isinstance(key, int) else index


def window(run_one, keys, seconds: float, max_runs: int | None, annotate):
    """Run keys back to back until ``seconds`` have passed (or
    ``max_runs`` are done); every run started is finished.
    ``annotate(index, key)`` is the context each run is made in."""
    runs, t0 = [], time.monotonic()
    for key in keys:
        if time.monotonic() - t0 >= seconds or (max_runs and len(runs) >= max_runs):
            break
        start = time.monotonic()
        with annotate(len(runs), key):
            res = run_one(key)
        runs.append(Run(key, start, time.monotonic(), res.values, res.delta,
                        np.asarray(res.history["engines"])))
    if len(runs) == len(keys):
        raise RuntimeError("the key pool ran out before the window closed")
    return t0, runs


def end_to_end(t0: float, runs, setup_s: float) -> dict:
    secs = [r.seconds for r in runs]
    return {
        "edges_per_s": stats.rate(t0, [(r.start, r.end, r.edges) for r in runs]),
        "run_s.p50": stats.percentile(secs, 50),
        "run_s.p75": stats.percentile(secs, 75),
        "setup_s": setup_s,
    }


def start_jax(cell: Cell, root: Path):
    """JAX on the cell's chip, with the compile cache in the checkout at a
    fixed path, whatever the environment says, so that two checkouts
    never share one.  Returns (jax, device, peaks, meter)."""
    cache_dir = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    device = check_device(jax.default_backend(), jax.devices(), cell.chips)
    return jax, device, load_peaks(device["kind"], root), CompileMeter(jax)


def prepare(cell: Cell, jax, root: Path = ROOT):
    """Generate the configuration's graph and set the traffic's driver up
    on it (``drivers/<name>.py``).  Returns the input edges, the driver's
    ``run_one(key)`` and what set-up reports: seconds and sizes."""
    sys.path.insert(0, str(ROOT / "src"))
    bench = root / "bench"
    t = time.monotonic()
    edges = graphs.generate(cell.config["generator"], bench)
    setup = {"generate_s": time.monotonic() - t}
    driver = load("drivers", cell.traffic.get("driver", "run_hytm"), bench)
    run_one, more = driver.prepare(cell.config, cell.traffic, edges, jax)
    return edges, run_one, {**setup, **more}


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=Path, default=None,
                    help="also write the reduced trace (.json.gz) here")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, root)
    traffic = cell.traffic
    bench = root / "bench"
    jax, device, peaks, meter = start_jax(cell, root)
    edges, run_one, setup = prepare(cell, jax, root)
    keys = loadgen.draw_keys(traffic, edges, args.seed, bench)

    t = time.monotonic()
    run_one(keys[0])
    setup["warmup_s"] = time.monotonic() - t
    compiles_setup = meter.compiles
    print("[setup] " + " ".join(f"{k}={v}" for k, v in setup.items())
          + f" compile_s={meter.seconds} compiles={compiles_setup}"
          f" cache_hits={meter.hits}/{meter.requests}", file=sys.stderr, flush=True)

    annotate = lambda index, key: contextlib.nullcontext()  # noqa: E731
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        annotate = lambda index, key: jax.profiler.TraceAnnotation(  # noqa: E731
            tracereduce.RUN_SPAN, key=span_key(index, key))

    setup_s = time.monotonic() - T_START
    t0, runs = window(run_one, keys[1:], args.seconds,
                      traffic["trace_runs"] if args.trace else None, annotate)
    if args.trace:
        jax.profiler.stop_trace()
        trace = tracereduce.load_xplane(sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1])
        shutil.rmtree(trace_dir, ignore_errors=True)
        if args.keep_trace:
            trace.to_json(args.keep_trace)
    compiles_window = meter.compiles - compiles_setup
    device["memory_peak_bytes"] = int(
        (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0))
    print(f"[window] runs={len(runs)} compiles={compiles_window} "
          f"slowest_s={max(r.seconds for r in runs)} "
          f"peak_bytes={device['memory_peak_bytes']}", file=sys.stderr, flush=True)

    # the program's state goes before the reference runs
    del run_one
    gc.collect()
    ref = Reference(edges)
    for r in runs:
        r.edges = loadgen.covered_edges(traffic, edges, ref, r.key, bench)

    if args.trace:
        ctx = Context(runs=runs, traffic=traffic, peaks=peaks, trace=trace)
        values = {m["name"]: read_metric(m["name"], ctx, root) for m in cell.per_layer}
        chosen = cell.per_layer
        busy_s, window_s, breakdown = tracereduce.summary(trace)
        device.update(busy_s=busy_s, window_s=window_s)
    else:
        values = end_to_end(t0, runs, setup_s)
        chosen = cell.end_to_end
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in chosen if values[m["name"]] is not None}

    checks, wrong = loadgen.compare(
        traffic, ref, [(r.key, r.values, r.delta) for r in runs], args.seed, bench)
    result = {
        "correct": not wrong and all(v <= lim for v, lim in checks.values()),
        "attempted": len(runs), "failed": len(wrong),
        "metrics": metrics, "device": device,
    }
    if args.trace:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"[check] {k} {v} limit {lim}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
