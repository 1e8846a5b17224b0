"""The device-scope and driver-phase metrics on a recorded v5e excerpt.

The excerpt (``data/v5e-kron17-sssp-scoped.trace.json.gz``) is a
``--keep-trace`` output of a ``kron17-sssp`` traced run cut to the
first and the last 0.3 s of one run; the op -> scope map beside it
(``...-scoped.scopes.json.gz``) is ``repro.obs.scopes.op_scopes()`` as
that run computed it.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path

import numpy as np
import pytest

import run
import tracereduce
from repro.obs import scopes

DATA = Path(__file__).resolve().parent / "data"
RECORDED = DATA / "v5e-kron17-sssp-scoped.trace.json.gz"
SCOPE_MAP = DATA / "v5e-kron17-sssp-scoped.scopes.json.gz"
OLD = DATA / "v5e-kron17-sssp.trace.json.gz"  # recorded before the scopes
SCOPED = ("device.scoped_share", "selection.busy_share", "sweep.block_share",
          "engines.busy_share", "filter.order_share")
PHASED = ("driver.init_idle_ms_per_run", "driver.drain_idle_ms_per_run")


@pytest.fixture
def recorded_map(monkeypatch):
    with gzip.open(SCOPE_MAP, "rt") as f:
        pairs = [tuple(p) for p in json.load(f)]
    monkeypatch.setattr(scopes, "op_scopes", lambda: pairs)
    return pairs


def context(trace):
    runs = [run.Run(1, 0.0, 1.0, None, None, np.zeros((4, 64), np.int32))]
    return run.Context(runs=runs, traffic={}, peaks={}, trace=trace)


def test_recorded_map_names_the_hot_ops(recorded_map):
    scope_of = {tracereduce.op_label(line): s for line, s in recorded_map}
    assert scope_of["fusion.127 pred[93696] fusion"] == "sweep.block"
    assert scope_of["fusion.132 s32[93696] fusion"] == "filter.order"
    assert scope_of["fusion.133 f32[93696] fusion"] == "filter.order"
    assert set(scopes.SCOPES) <= set(scope_of.values())


def test_new_metrics_on_a_recorded_v5e_trace(recorded_map):
    trace = tracereduce.Trace.from_json(RECORDED)
    assert RECORDED.stat().st_size + SCOPE_MAP.stat().st_size < 1 << 20
    got = {m: run.read_metric(m, context(trace)) for m in SCOPED + PHASED}
    # readings of the excerpt, fixed so that the reduction stays the same
    assert got == pytest.approx({
        "device.scoped_share": 99.98334095098207,
        "selection.busy_share": 0.744635803123887,
        "sweep.block_share": 15.889519790002714,
        "engines.busy_share": 82.85014091672508,
        "filter.order_share": 60.133169738489556,
        "driver.init_idle_ms_per_run": 7.0084005,
        "driver.drain_idle_ms_per_run": 1.0869785}, rel=1e-9)
    assert got["filter.order_share"] < got["engines.busy_share"] < got["device.scoped_share"]


@pytest.mark.parametrize("metric", SCOPED + PHASED)
def test_new_metrics_read_nothing_without_a_trace(recorded_map, metric):
    assert run.read_metric(metric, context(None)) is None


@pytest.mark.parametrize("metric", SCOPED)
def test_scope_metrics_read_nothing_without_a_map(monkeypatch, metric):
    monkeypatch.setattr(scopes, "op_scopes", lambda: [])
    assert run.read_metric(metric, context(tracereduce.Trace.from_json(RECORDED))) is None


@pytest.mark.parametrize("metric", PHASED)
def test_phase_metrics_read_nothing_without_phase_spans(metric):
    """A trace of a program that emits no ``hytm.*`` spans."""
    assert run.read_metric(metric, context(tracereduce.Trace.from_json(OLD))) is None


@pytest.mark.parametrize("metric", SCOPED)
def test_scope_metrics_read_nothing_from_a_program_without_scopes(monkeypatch, metric):
    """A program that does not name the scopes, as before they existed."""
    import repro.obs

    monkeypatch.delattr(repro.obs, "scopes")
    monkeypatch.setitem(sys.modules, "repro.obs.scopes", None)
    assert run.read_metric(metric, context(tracereduce.Trace.from_json(RECORDED))) is None
