"""The per-gridpoint climatology cell and the Q6 cell at a tiny size on the
CPU: served answers match the plain reference on both backends, the
float32-accumulation control does not, the reference's integer keying of
the 1.5-degree grid is exact, and the per-morsel host-time reader reads
the backend's counters when they exist."""

from __future__ import annotations

import time
import types

import numpy as np
import pytest

import cb_control
import cb_harness

SEED = 2**31 + 11
CLIM = "era5_1p5deg_clim.gridpoint_clim_2streams"
Q6 = "tpch_sf1.q6_2streams"
TINY = {
    CLIM: {"config": {"lat": 13, "lon": 24, "time_steps": 64}, "traffic": {}, "seconds": 2.0},
    Q6: {"config": {"rows": 40000, "parts": 2}, "traffic": {"requests_per_stream": 800}, "seconds": 1.0},
}


def _run(workload: str, backend: str) -> dict:
    tiny = TINY[workload]
    return cb_harness.run_cell(
        workload,
        SEED,
        tiny["seconds"],
        False,
        time.perf_counter(),
        require_tpu=False,
        executor_overrides={"backend": backend},
        config_overrides=tiny["config"],
        traffic_overrides=tiny["traffic"],
        log=lambda *a, **k: None,
    )


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
@pytest.mark.parametrize("workload", [CLIM, Q6])
def test_served_answers_match_the_reference(workload, backend):
    res = _run(workload, backend)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"rows_per_s", "setup_s"}


def test_clim_window_reads_61_steps_within_the_configuration():
    cell = cb_harness.load_cell(CLIM)
    config = {**cell["config"], **TINY[CLIM]["config"]}
    import cb_traffic

    reqs = cb_traffic.build_requests(cell["traffic"], config, SEED, 20)
    assert {len(r["sources"]) for r in reqs} == {61}
    assert {r["params"]["START"] for r in reqs} <= set(range(config["time_steps"] - 61 + 1))
    assert reqs[0]["filter"] == ["eq", ["col", "level"], 500]


def test_float32_accumulation_control_is_not_correct():
    cell = cb_harness.load_cell(CLIM)
    cell["config"] = {**cell["config"], **TINY[CLIM]["config"]}
    cell["traffic"] = {**cell["traffic"], "check": {**cell["traffic"]["check"], "sample": 2}}
    checks, compared = cb_control.control_check(cell, SEED)
    assert compared > 0 and not cb_harness.verdict(checks, compared), checks
    assert checks["sum_rel_err"]["value"] > checks["sum_rel_err"]["limit"]
    assert checks["groups_wrong"]["value"] == 0 and checks["minmax_wrong"]["value"] == 0


def test_integer_keying_of_the_grid_is_injective():
    """The reference keys a group by ``int()`` of each key: on the 1.5-degree
    grid (every latitude and longitude a multiple of 1.5) truncation keeps
    the 121 latitudes and the 240 longitudes distinct."""
    config = cb_harness.load_cell(CLIM)["config"]
    tables = cb_harness.load_module("data", config["generator"]).make({**config, "time_steps": 1}, SEED)
    cols = tables["t000"]["columns"]
    for key, count in (("lat", 121), ("lon", 240)):
        values = np.unique(cols[key])
        assert values.size == count
        assert len({int(v) for v in values.tolist()}) == count
    pairs = set(zip(cols["lat"].tolist(), cols["lon"].tolist()))
    assert len({(int(a), int(b)) for a, b in pairs}) == len(pairs) == 121 * 240


def test_host_ms_per_morsel_reads_the_backend_counters(monkeypatch):
    from repro.core import backend as backend_mod

    reader = cb_harness.load_module("layer_metrics", "aggregate.host_ms_per_morsel.clim")
    fake = types.SimpleNamespace()
    monkeypatch.setattr(backend_mod, "get_backend", lambda name=None: fake)
    assert reader.read({}) is None  # a program without the counters
    fake.agg_morsels, fake.agg_host_s = 0, 0.0
    assert reader.read({}) is None
    fake.agg_morsels, fake.agg_host_s = 8, 0.02
    assert reader.read({}) == pytest.approx(2.5)
