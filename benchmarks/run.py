"""Benchmark harness entry point: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit);
``--json <path>`` additionally writes the rows machine-readably so perf
trajectories (``BENCH_*.json``) can be recorded across revisions.
``--compare <baseline.json>`` checks the freshly emitted rows against a
prior ``--json`` dump and exits non-zero when any matching row regressed
past ``--compare-ratio`` (default 2.0x — a coarse tripwire for CI, not a
microbenchmark gate)."""

import argparse
import json
import sys


def compare_rows(rows, baseline_doc: dict, ratio: float) -> list[str]:
    """Rows regressed past ``ratio``x their baseline ``us_per_call``.
    Rows are matched by name; names present on only one side are skipped
    (benchmarks come and go across revisions)."""
    base = {r["name"]: float(r["us_per_call"])
            for r in baseline_doc.get("rows", [])}
    failures = []
    for name, us, _ in rows:
        b = base.get(name)
        # sub-ms rows are dominated by dispatch noise on shared CI
        if b is None or b <= 0 or max(us, b) < 1_000:
            continue
        if us > ratio * b:
            failures.append(
                f"{name}: {us:.1f}us > {ratio:.1f}x baseline {b:.1f}us")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="run a single benchmark module")
    ap.add_argument("--fast", action="store_true", help="smaller graphs / fewer repeats")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the emitted rows as JSON to PATH")
    ap.add_argument("--compare", default=None, metavar="BASELINE",
                    help="compare the emitted rows against a prior --json "
                         "dump; exit non-zero on any regression past "
                         "--compare-ratio")
    ap.add_argument("--compare-ratio", type=float, default=2.0,
                    help="regression threshold for --compare "
                         "(default: 2.0x the baseline us_per_call)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        autotune_bench,
        chaos_bench,
        common,
        fig3_analysis,
        fig7_execution_path,
        fig8_gains,
        fig9_scaling,
        iterloop,
        kernels,
        obs_bench,
        roofline,
        serve_bench,
        stream_bench,
        table5_runtime,
        table6_transfer,
    )

    # --fast applies to every entry: the table/fig3/7/8 family shrinks its
    # graphs via kw; the rest take an explicit fast flag.
    kw = dict(n_nodes=5000, n_edges=80_000, n_partitions=32) if args.fast else {}
    mods = {
        "table5": lambda: table5_runtime.run(**kw),
        "table6": lambda: table6_transfer.run(**kw),
        "fig3": lambda: fig3_analysis.run(**kw),
        "fig7": lambda: fig7_execution_path.run(**kw),
        "fig8": lambda: fig8_gains.run(**kw),
        "fig9": lambda: fig9_scaling.run(fast=args.fast),
        # selfcheck always on: the owner-sharding ~n/D per-device
        # state-byte gate rides every fig9-devices run
        "fig9-devices": lambda: fig9_scaling.run_devices(
            fast=args.fast, selfcheck=True),
        "kernels": lambda: kernels.run(fast=args.fast),
        "kernels-roofline": lambda: roofline.run_engines(fast=args.fast),
        "roofline": lambda: roofline.run(fast=args.fast),
        "stream": lambda: stream_bench.run(smoke=args.fast),
        "stream-devices": lambda: stream_bench.run_sharded(smoke=args.fast),
        "serve": lambda: serve_bench.run(smoke=args.fast),
        "autotune": lambda: autotune_bench.run(fast=args.fast),
        "iterloop": lambda: iterloop.run(fast=args.fast),
        "obs": lambda: obs_bench.run(fast=args.fast),
        "chaos": lambda: chaos_bench.run(fast=args.fast),
    }
    print("name,us_per_call,derived")
    for name, fn in mods.items():
        if args.only and name != args.only:
            continue
        fn()

    if args.json:
        doc = {
            "fast": args.fast,
            "only": args.only,
            "rows": [
                {"name": n, "us_per_call": us, "derived": d}
                for n, us, d in common.ROWS
            ],
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
        print(f"# wrote {len(common.ROWS)} rows -> {args.json}")

    if args.compare:
        with open(args.compare) as f:
            baseline = json.load(f)
        failures = compare_rows(common.ROWS, baseline, args.compare_ratio)
        if failures:
            print("# REGRESSIONS vs", args.compare)
            for line in failures:
                print("#   " + line)
            sys.exit(1)
        print(f"# compare OK: no row regressed past "
              f"{args.compare_ratio:.1f}x {args.compare}")


if __name__ == "__main__":
    main()
