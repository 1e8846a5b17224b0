"""repro.autotune: calibration, registry round-trip, regret contract,
and the online feedback loop (ISSUE 3 acceptance criteria)."""

import dataclasses

import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from repro.autotune import (
    OnlineCalibrator,
    calibrate,
    default_grid,
    fit_link,
    load_profile,
    model_probe,
    observation_matrix,
    save_profile,
    selection_on_grid,
    stats_for,
    total_regret,
)
from repro.core.constants import PCIE3, TPU_V5E_HBM, LinkModel
from repro.core.cost_model import (
    NONE,
    engine_costs,
    modeled_best_engines,
    select_engines,
)

GRID = default_grid()


# ----------------------------------------------------------------- validation

def test_linkmodel_validation_d1_divides_m():
    with pytest.raises(ValueError, match="divide"):
        LinkModel(name="bad", d1=3.0, m=128.0)


def test_linkmodel_validation_unit_interval():
    for field in ("alpha", "beta", "gamma"):
        with pytest.raises(ValueError, match=field):
            LinkModel(name="bad", **{field: 0.0})
        with pytest.raises(ValueError, match=field):
            LinkModel(name="bad", **{field: 1.5})


def test_linkmodel_validation_positive():
    with pytest.raises(ValueError, match="bandwidth"):
        LinkModel(name="bad", bandwidth=0.0)
    with pytest.raises(ValueError, match="launch_overhead_s"):
        LinkModel(name="bad", launch_overhead_s=-1e-6)
    # shipped profiles are all valid (construction is the check)
    assert PCIE3.rtt > 0 and TPU_V5E_HBM.rtt > 0


# ------------------------------------------------------------------ registry

def test_profile_json_roundtrip_identical_selection(tmp_path):
    obs = model_probe(GRID, TPU_V5E_HBM)
    rep = calibrate(GRID, obs, PCIE3)
    save_profile(rep.profile, device_kind="test", base=tmp_path,
                 meta={"static_regret": rep.static_regret})
    loaded, meta = load_profile(device_kind="test", base=tmp_path, with_meta=True)
    assert loaded == rep.profile
    assert meta["static_regret"] == rep.static_regret
    np.testing.assert_array_equal(
        selection_on_grid(GRID, loaded), selection_on_grid(GRID, rep.profile))


def test_load_missing_profile_raises(tmp_path):
    with pytest.raises(FileNotFoundError, match="repro.launch.calibrate"):
        load_profile(device_kind="absent", base=tmp_path)


def test_corrupt_profile_rejected_by_validation(tmp_path):
    import json

    def write(profile):
        (tmp_path / "ed.json").write_text(json.dumps(
            {"schema": 1, "device_kind": "ed", "profile": profile, "meta": {}}))

    write(dataclasses.asdict(PCIE3) | {"gamma": 7.0})
    with pytest.raises(ValueError, match="gamma"):
        load_profile(device_kind="ed", base=tmp_path)
    # truncated profiles fail loudly instead of inheriting defaults
    truncated = dataclasses.asdict(PCIE3)
    del truncated["bandwidth"]
    write(truncated)
    with pytest.raises(ValueError, match="missing.*bandwidth"):
        load_profile(device_kind="ed", base=tmp_path)


# ------------------------------------------------------- calibration contract

def test_misspecified_profile_calibrates_strictly_better():
    """Acceptance: PCIe profile on the TPU link — calibrated regret vs
    the measured-best oracle strictly below static regret."""
    obs = model_probe(GRID, TPU_V5E_HBM)
    rep = calibrate(GRID, obs, PCIE3)
    assert rep.calibrated_regret < rep.static_regret
    # the fit recovers the true smooth-model parameters
    assert rep.profile.bandwidth == pytest.approx(TPU_V5E_HBM.bandwidth, rel=0.05)
    assert rep.profile.compaction_bandwidth == pytest.approx(
        TPU_V5E_HBM.compaction_bandwidth, rel=0.05)


def test_correct_profile_calibration_is_noop():
    """Acceptance: correctly-specified profile — selection decisions
    unchanged on the probe grid.  Uses the TPU profile, whose selection
    models the full compaction cost; PCIE3's selection deliberately
    omits the CPU pass (paper §V-A), so its thresholds are always
    tunable against physical measurements."""
    obs = model_probe(GRID, TPU_V5E_HBM)
    rep = calibrate(GRID, obs, TPU_V5E_HBM)
    np.testing.assert_array_equal(
        selection_on_grid(GRID, rep.profile),
        selection_on_grid(GRID, TPU_V5E_HBM))
    assert rep.calibrated_regret <= rep.static_regret


def test_regret_never_worse_regression():
    """Calibrated thresholds achieve <= the static thresholds' regret on
    the probe set — across profile pairs and under measurement noise."""
    for initial, truth, noise in [
        (PCIE3, TPU_V5E_HBM, 0.0),
        (PCIE3, TPU_V5E_HBM, 0.05),
        (TPU_V5E_HBM, PCIE3, 0.0),
        (PCIE3, PCIE3, 0.1),
    ]:
        obs = model_probe(GRID, truth, noise=noise, seed=11)
        rep = calibrate(GRID, obs, initial)
        assert rep.calibrated_regret <= rep.static_regret + 1e-12, (
            initial.name, truth.name, noise)


def test_fit_link_keeps_topology_constants():
    obs = model_probe(GRID, TPU_V5E_HBM)
    fitted = fit_link(GRID, obs, PCIE3)
    for f in ("m", "mr", "d1", "d2", "selection_uses_full_compaction_cost"):
        assert getattr(fitted, f) == getattr(PCIE3, f)
    # model probes carry no per-task dispatch signal, so the overhead is
    # inherited, not zeroed (wall probes opt in via fit_overhead=True)
    assert fitted.launch_overhead_s == PCIE3.launch_overhead_s


def test_registry_rejects_path_escaping_device_kind(tmp_path):
    from repro.autotune import profile_path

    for bad in ("../../etc/x", "a/b", "..", ""):
        with pytest.raises(ValueError, match="device kind"):
            profile_path(device_kind=bad, base=tmp_path)


def test_total_regret_zero_for_oracle_selection():
    obs = model_probe(GRID, TPU_V5E_HBM)
    measured = observation_matrix(GRID, obs)
    oracle_engines = np.argmin(measured, axis=1)
    assert total_regret(oracle_engines, measured) == 0.0


# ------------------------------------------------------------- property tests

@settings(deadline=None, max_examples=30)
@given(
    bw_exp=st.floats(8.0, 12.5),
    gamma=st.floats(0.01, 1.0),
    alpha=st.floats(0.05, 1.0),
    beta=st.floats(0.05, 1.0),
    granule=st.integers(1, 9),
    mr=st.integers(1, 512),
    full_cost=st.booleans(),
)
def test_any_valid_profile_skips_inactive_partitions(
    bw_exp, gamma, alpha, beta, granule, mr, full_cost
):
    """Selection under ANY valid profile maps zero-active partitions to
    NONE — the invariant every engine family relies on."""
    link = LinkModel(
        name="prop", d1=4.0, m=4.0 * (2 ** granule), mr=float(mr),
        bandwidth=10.0 ** bw_exp, gamma=gamma, alpha=alpha, beta=beta,
        compaction_bandwidth=10.0 ** (bw_exp - 1),
        selection_uses_full_compaction_cost=full_cost,
    )
    from repro.core.cost_model import PartitionStats
    import jax.numpy as jnp

    stats = PartitionStats(
        active_edges=jnp.asarray([0.0, 100.0, 0.0], jnp.float32),
        active_vertices=jnp.asarray([0.0, 10.0, 0.0], jnp.float32),
        zc_requests=jnp.asarray([0.0, 12.0, 0.0], jnp.float32),
        total_edges=jnp.asarray([1000.0, 1000.0, 0.0], jnp.float32),
    )
    eng = np.asarray(select_engines(stats, engine_costs(stats, link), link))
    assert eng[0] == NONE and eng[2] == NONE and eng[1] != NONE
    best = np.asarray(modeled_best_engines(stats, engine_costs(stats, link)))
    assert best[0] == NONE and best[2] == NONE and best[1] != NONE


@settings(deadline=None, max_examples=20)
@given(scale=st.floats(1e-6, 1e3), ratio=st.floats(1.0, 2000.0))
# a microsecond-scale wall clock once let the ridge prior swamp the data
@example(scale=1e-6, ratio=1.0)
def test_online_calibrator_learns_relative_ratio(scale, ratio):
    """Feeding measured = scale * (c_f*T_f + c_z*T_z) with c_z/c_f =
    ratio, the solved correction reproduces the *relative* ratio
    regardless of the absolute scale (wall units need not match model
    units)."""
    cal = OnlineCalibrator(decay=0.2, ridge=1e-4)
    rng = np.random.default_rng(0)
    for _ in range(60):
        t = np.array([rng.uniform(0.5, 2.0), 0.0, rng.uniform(0.5, 2.0)])
        measured = scale * (t[0] + ratio * t[2])
        cal.update(t, measured)
    c = cal.correction()
    assert c[1] == 1.0  # COMPACT never observed: stays at identity
    assert c[2] / c[0] == pytest.approx(min(ratio, 400.0), rel=0.25) or (
        # both ends clipped when the ratio exceeds the safety range
        c[2] / c[0] == pytest.approx(cal.clip[1] / cal.clip[0], rel=1e-6))


def test_online_calibrator_ignores_degenerate_updates():
    cal = OnlineCalibrator()
    cal.update(np.zeros(3), 1.0)          # no modeled mass
    cal.update(np.ones(3), -1.0)          # negative wall
    cal.update(np.ones(3), float("nan"))  # NaN wall
    assert cal.n_updates == 0
    np.testing.assert_array_equal(cal.correction(), np.ones(3))


# ------------------------------------------------------------- online feedback

def test_run_hytm_autotune_traversal_bit_identical():
    from repro.core.hytm import HyTMConfig, run_hytm
    from repro.graph.algorithms import SSSP
    from repro.graph.generators import rmat_graph

    g = rmat_graph(1000, 12_000, seed=21)
    cfg = HyTMConfig(n_partitions=8)
    base = run_hytm(g, SSSP, source=0, config=cfg)
    tuned = run_hytm(g, SSSP, source=0,
                     config=dataclasses.replace(cfg, autotune=True))
    np.testing.assert_array_equal(base.values, tuned.values)
    assert tuned.engine_corrections is not None
    assert tuned.engine_corrections.shape == (3,)
    assert np.all(tuned.engine_corrections > 0)
    assert tuned.history["mispredictions"].shape == (tuned.iterations,)
    assert tuned.total_mispredictions >= 0
    # the default path reports diagnostics too, with no corrections
    assert base.engine_corrections is None
    assert "mispredictions" in base.history


def test_run_hytm_autotune_accumulative_tolerance_bounded():
    from repro.core.hytm import HyTMConfig, run_hytm
    from repro.graph.algorithms import PAGERANK
    from repro.graph.generators import rmat_graph

    g = rmat_graph(800, 8_000, seed=4)
    pr = dataclasses.replace(PAGERANK, tolerance=1e-7)
    cfg = HyTMConfig(n_partitions=8)
    base = run_hytm(g, pr, source=None, config=cfg)
    tuned = run_hytm(g, pr, source=None,
                     config=dataclasses.replace(cfg, autotune=True))
    # engine choices may legitimately differ (that is the point); results
    # agree to the program tolerance (FP summation order + second-pass
    # trajectory differences are tolerance-bounded, not bit-exact)
    assert np.max(np.abs(
        (base.values + base.delta) - (tuned.values + tuned.delta))) < 1e-3


def test_graph_service_autotune_matches_plain():
    from repro.core.hytm import HyTMConfig
    from repro.graph.generators import rmat_graph
    from repro.graph.algorithms import SSSP
    from repro.stream import GraphService, random_batch

    g = rmat_graph(500, 4_000, seed=9)
    plain = GraphService(g, HyTMConfig(n_partitions=8), max_lanes=4)
    tuned = GraphService(g, HyTMConfig(n_partitions=8, autotune=True),
                         max_lanes=4)
    sources = [0, 7, 33]
    r_plain = plain.query(SSSP, sources)
    r_tuned = tuned.query(SSSP, sources)
    for a, b in zip(r_plain, r_tuned):
        np.testing.assert_array_equal(a.values, b.values)
    assert "engine_corrections" in tuned.stats.extra
    assert len(tuned.stats.extra["engine_corrections"]) == 3
    assert "engine_corrections" not in plain.stats.extra

    # the incremental path after an update learns into the SAME
    # service-lifetime calibrator (no throwaway per-run ones)
    n_before = tuned._calibrator.n_updates
    rng = np.random.default_rng(9)
    batch = random_batch(tuned.dcsr, rng, n_insert=32, n_delete=32)
    plain.update(batch)
    tuned.update(batch)
    r_plain2 = plain.query(SSSP, sources)
    r_tuned2 = tuned.query(SSSP, sources)
    assert all(r.mode == "incremental" for r in r_tuned2)
    for a, b in zip(r_plain2, r_tuned2):
        np.testing.assert_array_equal(a.values, b.values)
    assert tuned._calibrator.n_updates > n_before
