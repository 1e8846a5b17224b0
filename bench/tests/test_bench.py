"""CPU tests of the benchmark harness: its arithmetic, generators,
references, trace reduction and refusals, and whole runs at a tiny size
with the device check stubbed out.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import graphs
import loadgen
import named
import run
import stats
import tracereduce
from reference import Reference, drop_last_level, to_bfloat16

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
RECORDED = BENCH / "tests" / "data" / "v5e-kron17-sssp.trace.json.gz"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")

KRON = {"kind": "kronecker", "scale": 10, "edgefactor": 16, "a": 0.57,
        "b": 0.19, "c": 0.19, "weights": [1, 255], "seed": 3}
URAND = {"kind": "uniform", "scale": 10, "edgefactor": 16,
         "weights": [1, 255], "seed": 3}
SEARCH = {"keys": "graph500", "key_pool": 16}


# ------------------------------------------------------------ arithmetic

def test_rate_counts_whole_runs_to_the_last_completion():
    runs = [(0.0, 1.0, 100), (1.0, 2.0, 100), (2.0, 4.0, 200)]
    assert stats.rate(0.0, runs) == pytest.approx(400 / 4.0)
    # the window opened half a second before the first run started
    assert stats.rate(-0.5, runs) == pytest.approx(400 / 4.5)


def test_rate_and_percentiles_keep_a_stall():
    runs = [(float(i), i + 1.0, 10) for i in range(9)]
    runs.append((9.0, 29.0, 10))  # one run stalls for 20 s
    assert stats.rate(0.0, runs) == pytest.approx(100 / 29.0)
    secs = [e - s for s, e, _ in runs]
    assert stats.percentile(secs, 50) == 1.0
    assert stats.percentile(secs, 100) == 20.0
    assert stats.percentile(secs, 75) == pytest.approx(np.percentile(secs, 75))


@pytest.mark.parametrize("q", [0, 25, 50, 75, 90, 100])
def test_percentile_matches_numpy(q):
    xs = np.random.default_rng(q).random(37)
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_rate_refuses_an_empty_window():
    with pytest.raises(ValueError):
        stats.rate(0.0, [])


# ------------------------------------------------------------ generators

@pytest.mark.parametrize("spec", [KRON, URAND], ids=["kron", "urand"])
def test_generator_is_deterministic_and_has_graph500_edge_count(spec):
    a, b = graphs.generate(spec), graphs.generate(spec)
    assert a.n == 1 << spec["scale"]
    assert a.m == spec["edgefactor"] << spec["scale"]
    for x, y in ((a.src, b.src), (a.dst, b.dst), (a.weight, b.weight)):
        np.testing.assert_array_equal(x, y)
    other = graphs.generate({**spec, "seed": spec["seed"] + 1})
    assert not np.array_equal(a.src, other.src)
    assert a.weight.min() >= 1 and a.weight.max() <= 255
    assert np.all(a.weight == np.round(a.weight))
    src, dst, w = a.directed()
    assert len(src) == 2 * a.m
    np.testing.assert_array_equal(src[a.m:], a.dst)
    np.testing.assert_array_equal(w[a.m:], a.weight)


def test_kronecker_permutes_hubs_away_from_low_ids():
    e = graphs.generate({**KRON, "scale": 12})
    deg = np.bincount(np.r_[e.src, e.dst], minlength=e.n)
    # unpermuted, the Kronecker hubs sit at vertex 0 and its neighbours
    assert np.argmax(deg) != 0
    assert deg.max() > 8 * np.mean(deg)


def test_keys_depend_on_the_seed_and_avoid_isolated_vertices():
    e = graphs.generate(KRON)
    traffic = {"keys": "graph500", "key_pool": 32}
    a = loadgen.draw_keys(traffic, e, 2**40 + 7)
    assert a == loadgen.draw_keys(traffic, e, 2**40 + 7)
    assert a != loadgen.draw_keys(traffic, e, 8)
    assert len(set(a)) == len(a) == 33
    loops = e.src == e.dst
    linked = set(e.src[~loops]) | set(e.dst[~loops])
    assert set(a) <= linked
    assert loadgen.draw_keys({"keys": "none", "key_pool": 3}, e, 1) == [None] * 4


# ------------------------------------------------------------ references

@pytest.fixture(scope="module")
def kron_graph():
    from repro.graph.csr import csr_from_edges

    e = graphs.generate(KRON)
    return e, csr_from_edges(e.n, *e.directed()), Reference(e)


@pytest.mark.parametrize("prog", ["sssp", "bfs"])
def test_distances_agree_with_run_hytm(kron_graph, prog):
    from repro.core.hytm import HyTMConfig, run_hytm
    from repro.graph.algorithms import ALGORITHMS

    e, g, ref = kron_graph
    for key in loadgen.draw_keys(SEARCH, e, 5)[:3]:
        res = run_hytm(g, ALGORITHMS[prog], source=key,
                       config=HyTMConfig(n_partitions=8))
        np.testing.assert_array_equal(res.values, ref.distances(key, unit=prog == "bfs"))
        assert np.isfinite(res.values).sum() == ref.component_size[ref.components[key]]


def test_pagerank_readings_of_run_hytm_are_within_limits(kron_graph):
    from repro.core.hytm import HyTMConfig, run_hytm
    from repro.graph.algorithms import PAGERANK

    _, g, ref = kron_graph
    res = run_hytm(g, PAGERANK, source=None, config=HyTMConfig(n_partitions=8))
    readings = named.load("checks", "pagerank").readings
    pending, gap = readings(ref, 0.85, res.values, res.delta)
    assert pending <= PAGERANK.tolerance
    assert gap < 1e-5
    # and the answer is near the exact fixpoint, as far as the pending
    # mass allows
    rank = res.values + res.delta
    np.testing.assert_allclose(rank, ref.pagerank(0.85), rtol=2e-2)


def test_components_and_covered_edges(kron_graph):
    e, _, ref = kron_graph
    comp = ref.components
    src, dst, _ = e.directed()
    assert np.all(comp[src] == comp[dst])
    assert np.all(comp <= np.arange(e.n))
    assert ref.component_edges.sum() == e.m
    key = loadgen.draw_keys(SEARCH, e, 1)[1]
    covered = loadgen.covered_edges({"coverage": "component"}, e, ref, key)
    assert covered == np.sum(comp[e.src] == comp[key])
    assert loadgen.covered_edges({"coverage": "all"}, e, ref, None) == e.m


# --------------------------------------------- controls read as not correct

def test_controls_fail_the_comparison():
    """Each cell's control, at a size a test run holds: the reference a
    step below the stated guarantee, in the program's place."""
    e = graphs.generate({**KRON, "scale": 12})
    ref = Reference(e)
    keys = loadgen.draw_keys(SEARCH, e, 9)[:3]
    exact = {"check": "exact", "program": "sssp", "check_runs": 3,
             "limits": {"unreached": 0, "mismatched": 0}}
    bf16 = [(k, ref.distances(k, rounding=to_bfloat16), None) for k in keys]
    checks, wrong = loadgen.compare(exact, ref, bf16, 1)
    assert checks["mismatched"][0] > 0 and wrong
    bfs = {**exact, "program": "bfs"}
    short = [(k, drop_last_level(ref.distances(k, unit=True)), None) for k in keys]
    checks, wrong = loadgen.compare(bfs, ref, short, 1)
    assert checks["mismatched"][0] > 0 and checks["unreached"][0] > 0
    pr = {"check": "pagerank", "damping": 0.85,
          "limits": {"pending_max": 1e-3, "invariant_gap": 1e-5}}
    rank = ref.pagerank(0.85, rounding=to_bfloat16, max_iters=300)
    checks, wrong = loadgen.compare(pr, ref, [(None, rank, np.zeros_like(rank))], 1)
    assert checks["invariant_gap"][0] > 10 * checks["invariant_gap"][1]
    exact_rank = ref.pagerank(0.85)
    checks, wrong = loadgen.compare(pr, ref, [(None, exact_rank, np.zeros_like(rank))], 1)
    assert not wrong


# ------------------------------- pins: the cells' graphs, keys and checks

def digest(a: np.ndarray) -> str:
    a = np.ascontiguousarray(a)
    return hashlib.sha256(a.dtype.str.encode() + a.tobytes()).hexdigest()[:16]


def cell_inputs(traffic: str, scale: int):
    """The traffic mix and the graph of the cell that runs it, at ``scale``."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in spec["workloads"] if w["traffic"] == traffic)
    config = json.loads((BENCH / "configs" / f"{cell['config']}.json").read_text())
    mix = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    return mix, graphs.generate({**config["generator"], "scale": scale})


@pytest.mark.parametrize("config,scale,want", [
    ("gap-kron-s17", 10, ("603c05639dd74d05", "ca86ba7d72ea8d5a", "eb99f61bc68c0c08")),
    ("gap-kron-s17", 17, ("d2ec5a68616268f3", "3887688f31e37418", "eaa546d3f81d9c31")),
    ("gap-urand-s17", 10, ("9f0babdde9acf1a3", "4c42e13acafa7d92", "0c975419e3a0cbff")),
    ("gap-urand-s17", 17, ("27720f24b9b35609", "278f0c611eee87e2", "7287208b13590c92")),
])
def test_pinned_graphs(config, scale, want):
    """src, dst and weight of each configuration's graph, bit for bit."""
    spec = json.loads((BENCH / "configs" / f"{config}.json").read_text())["generator"]
    e = graphs.generate({**spec, "scale": scale})
    assert (digest(e.src), digest(e.dst), digest(e.weight)) == want


@pytest.mark.parametrize("traffic,seed,want", [
    ("graph500-sssp", 7, "70fd5a5547bd05a3"),
    ("graph500-sssp", 2**33 + 5, "53208c2a9797c1b8"),
    ("graph500-bfs", 7, "d1bb67339ebcc1d3"),
    ("graph500-bfs", 2**33 + 5, "351189cecc75e93b"),
    ("delta-pagerank", 7, "03aff0b3004a28fb"),
    ("delta-pagerank", 2**33 + 5, "03aff0b3004a28fb"),
])
def test_pinned_keys(traffic, seed, want):
    """The warm-up key and the window's keys of each cell at its own size."""
    mix, e = cell_inputs(traffic, 17)
    keys = loadgen.draw_keys(mix, e, seed)
    assert len(keys) == mix["key_pool"] + 1
    assert hashlib.sha256(json.dumps(keys).encode()).hexdigest()[:16] == want


@pytest.mark.parametrize("traffic,want,wrong", [
    ("graph500-sssp", {"unreached": (0, 0), "mismatched": (825, 0)}, list(range(8))),
    ("graph500-bfs", {"unreached": (6223, 0), "mismatched": (6223, 0)}, list(range(16))),
    ("delta-pagerank", {"pending_max": (0.0, 0.0010000000474974513),
                        "invariant_gap": (0.003708240875837696, 1e-05)}, [0]),
])
def test_pinned_control_checks(traffic, want, wrong):
    """The ``[check]`` numbers of each traffic's control at scale 10."""
    mix, e = cell_inputs(traffic, 10)
    ref = Reference(e)
    keys = loadgen.draw_keys(mix, e, 11)[1:1 + mix.get("check_runs", 1)]
    answers = [(k, *loadgen.control(mix, ref, k)) for k in keys]
    checks, found = loadgen.compare(mix, ref, answers, 11)
    assert checks == want
    assert sorted(found) == wrong


# ---------------------------------------------------------- trace reduction

def ev(name, start, dur):
    return tracereduce.Event(name, float(start), float(dur))


def test_union_gaps_and_busy():
    iv = [(0, 2), (1, 3), (5, 6), (5.5, 5.7), (8, 9)]
    assert tracereduce.union(iv) == [(0, 3), (5, 6), (8, 9)]
    assert tracereduce.covered(iv, 0, 10) == 5
    assert tracereduce.covered(iv, 2, 8.5) == 2.5
    assert tracereduce.gaps(iv, 0, 10) == [(3, 5), (6, 8), (9, 10)]
    assert tracereduce.gaps(iv, 1, 2) == []


def test_op_labels_from_hlo_text():
    label = tracereduce.op_label
    assert label("%fusion.127 = pred[93696]{0:T(1024)(128)(4,1)S(1)} fusion("
                 "pred[131072]{0:T(1024)} %get-tuple-element.2032), kind=kCustom"
                 ) == "fusion.127 pred[93696] fusion"
    assert label("%segment_spmm_pallas.1 = f32[131072,1]{1,0:T(8,128)S(1)} custom-call("
                 "s32[1024]{0:T(1024)S(1)} %a), custom_call_target=\"tpu_custom_call\""
                 ) == "segment_spmm_pallas.1 f32[131072,1] custom-call"
    assert label("%while.146 = (f32[131072]{0:T(1024)}, s32[8]{0:T(128)}) while("
                 "(f32[131072]{0:T(1024)}) %tuple.326), condition=%c") == "while.146 tuple while"


def test_attribution_self_time_and_gap_labels():
    ops = [ev("while.1 tuple while", 0, 70),
           ev("sort.12 s32[93696] sort", 0, 10),
           ev("fusion.3 f32[93696] fusion", 10, 5),
           ev("segment_spmm_pallas.1 f32[131072,1] custom-call", 20, 30),
           ev("reduce.8 f32[131072] reduce", 50, 5),
           ev("fusion.4 f32[93696] fusion", 60, 10)]
    host = [ev(tracereduce.RUN_SPAN, 0, 80), ev("PjitFunction(hytm_chunk)", 0, 2),
            ev("np.asarray(jax.Array)", 70, 9), ev(tracereduce.RUN_SPAN, 100, 10)]
    trace = tracereduce.Trace("/device:TPU:0", ops, host)
    mine = tracereduce.named(ops, ["segment_spmm_pallas"])
    assert [e.instruction for e in mine] == ["segment_spmm_pallas.1"]
    assert tracereduce.busy(mine, 0, 110) == 30
    assert tracereduce.busy(ops, 0, 110) == 70  # the while encloses the rest
    own = {e.instruction: t for e, t in tracereduce.self_times(ops)}
    assert own == {"while.1": 10, "sort.12": 10, "fusion.3": 5,
                   "segment_spmm_pallas.1": 30, "reduce.8": 5, "fusion.4": 10}
    assert tracereduce.top_ops(ops, 1) == [
        ("segment_spmm_pallas.1 f32[131072,1] custom-call", 30.0)]
    gaps = tracereduce.idle_gaps(trace, 0, 110)
    assert gaps == [("between runs", 40)]  # 70 .. 110, centred between runs
    inner = tracereduce.Trace("/device:TPU:0", ops[1:], host)
    gaps = tracereduce.idle_gaps(inner, 0, 80)
    assert ("bench.run", 5) in gaps  # 15 .. 20, no host event
    assert ("bench.run > np.asarray(jax.Array)", 10) in gaps  # 70 .. 80


def test_json_round_trip(tmp_path):
    t = tracereduce.Trace("/device:TPU:0", [ev("a.1 f32[8] add", 0, 1)],
                          [ev(tracereduce.RUN_SPAN, 0, 2)])
    t.to_json(tmp_path / "t.json.gz")
    back = tracereduce.Trace.from_json(tmp_path / "t.json.gz")
    assert back == t


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_per_layer_metrics_on_a_recorded_v5e_trace():
    trace = tracereduce.Trace.from_json(RECORDED)
    assert trace.ops and trace.runs()
    assert RECORDED.stat().st_size < 1 << 20
    engines = np.zeros((4, 64), np.int32)
    runs = [run.Run(1, 0.0, 1.0, None, None, engines, edges=2_000_000)
            for _ in trace.runs()]
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]
    traffic = json.loads((BENCH / "traffic" / "graph500-sssp.json").read_text())
    ctx = run.Context(runs=runs, traffic=traffic, peaks=peaks, trace=trace)
    got = {m: run.read_metric(m, ctx) for m in
           ("device.idle_share", "driver.idle_ms_per_run", "kernels.busy_share",
            "filter.us_per_call", "sweep.edge_roofline", "selection.filter_share")}
    # readings of the excerpt, fixed so that the reduction stays the same
    assert got == pytest.approx({
        "device.idle_share": 3.2229407027260626,
        "driver.idle_ms_per_run": 9.656454,
        "kernels.busy_share": 6.517467586484599,
        "filter.us_per_call": 153.6085630252101,
        "sweep.edge_roofline": 0.0067285874975225165,
        "selection.filter_share": 100.0}, rel=1e-9)
    busy_s, window_s, breakdown = tracereduce.summary(trace)
    assert (busy_s, window_s) == pytest.approx((0.580687092, 0.60002556))
    assert breakdown["device_ops"][0] == ["fusion.127 pred[93696] fusion", 0.091227377]
    assert breakdown["idle_gaps"][0] == ["bench.run", pytest.approx(0.006971705)]


# ------------------------------------------------------- refusals and names

def test_refuses_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "kron17-sssp",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=120)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert "{" not in out.stdout


class FakeDevice:
    platform, device_kind = "tpu", "TPU v99 imaginary"


def test_refuses_unknown_device_kind_and_too_few_chips():
    with pytest.raises(SystemExit) as e:
        run.load_peaks(FakeDevice.device_kind)
    assert e.value.code != 0
    with pytest.raises(SystemExit) as e:
        run.check_device("tpu", [FakeDevice()], chips=4)
    assert e.value.code != 0
    assert run.check_device("tpu", [FakeDevice()], chips=1)["count"] == 1
    assert run.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "kron17-sssp", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=tmp_path, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert out.returncode != 0
    assert "{" not in out.stdout


def test_benchmark_json_names_units_and_files():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = []
    for c in spec["configs"]:
        names.append(c["name"])
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).exists()
        assert all(NAME.match(k) for k in c["reduced"])
    for w in spec["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
        mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        config = next(c for c in spec["configs"] if c["name"] == w["config"])
        kind = json.loads((ROOT / config["file"]).read_text())["generator"]["kind"]
        for where, name in (("generators", kind), ("keys", mix["keys"]),
                            ("coverage", mix["coverage"]), ("checks", mix["check"]),
                            ("drivers", mix.get("driver", "run_hytm"))):
            assert (BENCH / where / f"{name}.py").is_file(), (where, name)
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in spec["end_to_end"] + spec["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in spec["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()
        assert m["moves"] in {e["name"] for e in spec["end_to_end"]}
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    for p in BENCH.rglob("*"):
        rel = p.relative_to(ROOT).as_posix()
        if "__pycache__" not in rel:
            assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel


# ------------------------------------------------ whole runs at a tiny size

@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """A checkout whose configurations are scale 10, with the chip check
    stubbed: everything else is the harness as it runs on the chip."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for f in (tmp_path / "bench" / "configs").glob("*.json"):
        c = json.loads(f.read_text())
        c["generator"]["scale"] = 10
        c["hytm"]["n_partitions"] = 8
        f.write_text(json.dumps(c))
    monkeypatch.setattr(run, "check_device", lambda backend, devices, chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    return tmp_path


def result_of(capsys, root, workload, seed=2**33 + 5, seconds=0.3):
    assert run.main(["--workload", workload, "--seed", str(seed),
                     "--seconds", str(seconds)], root=root) == 0
    out, err = capsys.readouterr()
    res = json.loads(out.strip().splitlines()[-1])
    assert list(res)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("[check]")
    return res


@pytest.mark.parametrize("workload", ["kron17-sssp", "urand17-bfs", "kron17-pagerank"])
def test_whole_run_is_correct(tiny_root, capsys, workload):
    res = result_of(capsys, tiny_root, workload)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 1
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    want = {m["name"] for m in spec["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())


def test_a_new_traffic_file_and_metric_need_no_code(tiny_root, capsys):
    """A later PR adds a cell with files and entries only."""
    mix = json.loads((tiny_root / "bench/traffic/graph500-bfs.json").read_text())
    mix["check_runs"] = 2
    (tiny_root / "bench/traffic/dummy-bfs.json").write_text(json.dumps(mix))
    (tiny_root / "bench/metrics/dummy.runs.py").write_text(
        "def read(ctx):\n    return float(len(ctx.runs))\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "kron17-dummy", "config": "gap-kron-s17",
                              "traffic": "dummy-bfs", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "dummy.runs", "unit": "runs", "better": "higher",
                              "source": "program_counter", "layer": "driver",
                              "moves": "edges_per_s", "workloads": ["kron17-dummy"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = run.load_cell("kron17-dummy", tiny_root)
    assert cell.traffic["check_runs"] == 2
    assert [m["name"] for m in cell.per_layer][-1] == "dummy.runs"
    ctx = run.Context(runs=[1, 2], traffic=cell.traffic, peaks={}, trace=None)
    assert run.read_metric("dummy.runs", ctx, tiny_root) == 2.0
    res = result_of(capsys, tiny_root, "kron17-dummy")
    assert res["correct"] is True


# A deployment no cell has: WCC on a graph of disjoint blocks, through a
# driver, keys, coverage and a check of its own, each a new file.
DEPLOYMENT = {
    "generators/blocks.py": """
def generate(spec, rng):
    n, m = 1 << spec["scale"], spec["edgefactor"] << spec["scale"]
    src = rng.integers(0, n, m)
    return src, src ^ rng.integers(0, spec["block"], m)  # within src's block
""",
    "keys/rounds.py": """
def draw(traffic, edges, seed):
    return [("round", i) for i in range(traffic["key_pool"] + 1)]
""",
    "coverage/whole.py": """
def covered(traffic, edges, ref, key):
    return edges.m
""",
    "drivers/wcc.py": """
def prepare(config, traffic, edges, jax):
    from repro.core.hytm import HyTMConfig, build_runtime, run_hytm
    from repro.graph.algorithms import ALGORITHMS
    from repro.graph.csr import csr_from_edges

    g = csr_from_edges(edges.n, *edges.directed())
    cfg = HyTMConfig(**config["hytm"])
    rt = build_runtime(g, cfg)

    def run_one(key):
        return run_hytm(g, ALGORITHMS["wcc"], source=None, config=cfg, runtime=rt)

    return run_one, {"partitions": rt.parts.n_partitions}
""",
    "checks/components.py": """
import numpy as np


def partition_gap(labels, components):
    # columns (label, component) beyond as many as there are labels, and
    # beyond as many as there are components: 0 when the labels define
    # the components, whatever label each carries
    pairs = np.unique(np.stack([labels, components]), axis=1).shape[1]
    return (pairs - len(np.unique(labels))) + (pairs - len(np.unique(components)))


def compare(traffic, ref, runs, seed):
    limit = traffic["limits"]["partition_gap"]
    gaps = [partition_gap(values, ref.components) for _, values, _ in runs]
    return ({"partition_gap": (max(gaps), limit)},
            {i for i, gap in enumerate(gaps) if gap > limit})


def control(traffic, ref, key):
    labels = ref.components.astype(np.float32)
    labels[labels == labels.max()] = labels.min()  # two components merged
    return labels, None
""",
    "traffic/wcc-rounds.json": json.dumps({
        "program": "wcc", "driver": "wcc", "keys": "rounds", "key_pool": 4096,
        "coverage": "whole", "bytes_per_edge": 4, "trace_runs": 2,
        "check": "components", "limits": {"partition_gap": 0}}),
    "configs/blocks-s10.json": json.dumps({
        "generator": {"kind": "blocks", "scale": 10, "edgefactor": 16, "block": 16,
                      "weights": [1, 1], "seed": 5},
        "hytm": {"n_partitions": 8}}),
}


def add_deployment(root: Path) -> None:
    for rel, text in DEPLOYMENT.items():
        (root / "bench" / rel).write_text(text)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "blocks-s10", "source": "test",
                            "file": "bench/configs/blocks-s10.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "blocks-wcc", "config": "blocks-s10",
                              "traffic": "wcc-rounds", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def relabel_one(res):
    """Vertex 0 takes the label of the last vertex, in another block."""
    res.values = res.values.copy()
    res.values[0] = res.values[-1]


@pytest.mark.parametrize("fault", [None, relabel_one], ids=["sound", "one-label-altered"])
def test_a_new_deployment_needs_no_code(tiny_root, capsys, monkeypatch, fault):
    """A later PR adds a generator, keys, coverage, a driver and a check
    as files, and the cell runs and is judged like any other."""
    import repro.core.hytm as hytm

    add_deployment(tiny_root)
    bench = tiny_root / "bench"
    cell = run.load_cell("blocks-wcc", tiny_root)
    edges = graphs.generate(cell.config["generator"], bench)
    ref = Reference(edges)
    assert len(np.unique(ref.components)) > 1
    key = loadgen.draw_keys(cell.traffic, edges, 1, bench)[1]
    checks, wrong = loadgen.compare(
        cell.traffic, ref, [(key, *loadgen.control(cell.traffic, ref, key, bench))], 1, bench)
    assert checks["partition_gap"][0] > 0 and wrong == {0}
    assert run.span_key(3, key) == 3
    if fault:
        monkeypatch.setattr(hytm, "run_hytm", altered(hytm.run_hytm, fault))
    res = result_of(capsys, tiny_root, "blocks-wcc")
    assert res["attempted"] > 1
    assert res["metrics"]["edges_per_s"]["value"] > 0
    assert res["correct"] is (fault is None)
    assert res["failed"] == (0 if fault is None else res["attempted"])


@pytest.mark.parametrize("kind", ["generators", "keys", "coverage", "checks", "drivers"])
def test_an_unknown_name_is_refused_naming_its_file(tiny_root, capsys, kind):
    field = {"keys": "keys", "coverage": "coverage", "checks": "check", "drivers": "driver"}
    mix = tiny_root / "bench/traffic/graph500-bfs.json"
    if kind == "generators":
        mix = tiny_root / "bench/configs/gap-urand-s17.json"
        config = json.loads(mix.read_text())
        config["generator"]["kind"] = "nowhere"
        mix.write_text(json.dumps(config))
    else:
        traffic = json.loads(mix.read_text())
        traffic[field[kind]] = "nowhere"
        mix.write_text(json.dumps(traffic))
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "urand17-bfs", "--seed", "1", "--seconds", "0.1"],
                 root=tiny_root)
    assert e.value.code == 2
    out, err = capsys.readouterr()
    assert "{" not in out
    assert str(tiny_root / "bench" / kind / "nowhere.py") in err


def altered(fn, how):
    def wrapper(*args, **kwargs):
        res = fn(*args, **kwargs)
        how(res)
        return res
    return wrapper


def shift_one_value(res):
    res.values = res.values.copy()
    i = int(np.flatnonzero(np.isfinite(res.values))[-1])
    res.values[i] += 1.0


def lose_a_vertex(res):
    res.values = res.values.copy()
    i = int(np.flatnonzero(np.isfinite(res.values))[-1])
    res.values[i] = np.inf


@pytest.mark.parametrize("workload", ["kron17-sssp", "urand17-bfs", "kron17-pagerank"])
@pytest.mark.parametrize("fault", [shift_one_value, lose_a_vertex])
def test_an_altered_answer_reads_not_correct(tiny_root, capsys, monkeypatch,
                                             workload, fault):
    import repro.core.hytm as hytm

    monkeypatch.setattr(hytm, "run_hytm", altered(hytm.run_hytm, fault))
    res = result_of(capsys, tiny_root, workload)
    assert res["correct"] is False and res["failed"] > 0


@pytest.mark.parametrize("workload", ["kron17-sssp", "kron17-pagerank"])
def test_a_run_cut_short_reads_not_correct(tiny_root, capsys, monkeypatch, workload):
    """The state left as it stands after one iteration, as a driver that
    stops early would return it."""
    import dataclasses

    import repro.core.hytm as hytm

    real = hytm.run_hytm

    def one_iteration(*args, config, **kwargs):
        return real(*args, config=dataclasses.replace(config, max_iters=1), **kwargs)

    monkeypatch.setattr(hytm, "run_hytm", one_iteration)
    res = result_of(capsys, tiny_root, workload)
    assert res["correct"] is False
