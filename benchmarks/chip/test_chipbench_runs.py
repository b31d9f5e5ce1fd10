"""Whole runs of both cells at a tiny size on the CPU, with the harness's
look for a chip skipped: the served answers match the plain reference on
the numpy backend and on the pallas backend (kernels in interpret mode),
and ``correct`` comes out false when the served path is broken underneath
or when the float32-accumulation control stands in for the program."""

from __future__ import annotations

import itertools
import time

import numpy as np
import pytest

import cb_control
import cb_harness
import cb_reference
import cb_traffic

SEED = 2**31 + 3
CELLS = {
    "tpch_sf1.q1_2streams": {
        "config": {"rows": 40000, "parts": 2},
        "traffic": {"requests_per_stream": 400},
        "seconds": 1.0,
    },
    "era5_1p5deg.level_stats_open": {
        "config": {"lat": 13, "lon": 24, "time_steps": 6},
        "traffic": {"rate_per_s": 8.0},
        "seconds": 1.5,
    },
}


def _run(workload: str, backend: str, seed: int = SEED) -> dict:
    tiny = CELLS[workload]
    return cb_harness.run_cell(
        workload,
        seed,
        tiny["seconds"],
        False,
        time.perf_counter(),
        require_tpu=False,
        executor_overrides={"backend": backend},
        config_overrides=tiny["config"],
        traffic_overrides=tiny["traffic"],
        log=lambda *a, **k: None,
    )


def _passes(result: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in result["checks"].values())


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
@pytest.mark.parametrize("workload", sorted(CELLS))
def test_served_answers_match_the_reference(workload, backend):
    res = _run(workload, backend)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert {"setup_s"} < set(res["metrics"])


def _fault_fold(monkeypatch, fault: str):
    from repro.core.backend import FUSED_INELIGIBLE, FusedChainPlan

    orig = FusedChainPlan.fold
    calls = itertools.count()

    def fold(self, batch):
        st = orig(self, batch)
        if st is None or st is FUSED_INELIGIBLE:
            return st
        if fault == "half_left_out":
            return None if next(calls) % 2 else st
        for name, acc in st.acc.items():  # "answer_altered": every float sum, where the morsel's fold makes it
            if acc.dtype == np.float64:
                st.acc[name] = acc * (1.0 + 2.0**-30)
        return st

    monkeypatch.setattr(FusedChainPlan, "fold", fold)


@pytest.mark.parametrize("fault", ["half_left_out", "answer_altered"])
@pytest.mark.parametrize("workload", sorted(CELLS))
def test_a_broken_served_path_is_not_correct(monkeypatch, workload, fault):
    _fault_fold(monkeypatch, fault)
    res = _run(workload, "pallas")
    assert not res["correct"], res["checks"]
    assert not _passes(res)


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_float32_accumulation_control_is_not_correct(workload):
    cell = cb_harness.load_cell(workload)
    tiny = CELLS[workload]
    cell["config"] = {**cell["config"], **tiny["config"]}
    cell["traffic"] = {**cell["traffic"], **tiny["traffic"], "check": {**cell["traffic"]["check"], "sample": 4}}
    checks, compared = cb_control.control_check(cell, SEED)
    assert compared > 0 and not cb_harness.verdict(checks, compared), checks
    assert checks["sum_rel_err"]["value"] > checks["sum_rel_err"]["limit"]
    assert checks["groups_wrong"]["value"] == 0 and checks["minmax_wrong"]["value"] == 0


Q6 = cb_harness.load_json("testdata", "q6_2streams.json")


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_q6_traffic_runs_as_data_alone(backend):
    """TPC-H Q6, a global aggregate whose dates are built from a drawn year,
    runs through the harness from its traffic file alone (here over the Q1
    cell's configuration and metrics)."""
    tiny = CELLS["tpch_sf1.q1_2streams"]
    res = cb_harness.run_cell(
        "tpch_sf1.q1_2streams",
        SEED,
        tiny["seconds"],
        False,
        time.perf_counter(),
        require_tpu=False,
        executor_overrides={"backend": backend},
        config_overrides=tiny["config"],
        traffic_overrides=Q6,
        log=lambda *a, **k: None,
    )
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {"rows_per_s", "setup_s"} == set(res["metrics"])


def test_q6_reference_matches_hand_sums():
    """The reference's Q6 answers against sums written out by hand: the
    discount window as whole cents, and the revenue as the float32 product
    (the projection's own rounding) summed exactly."""
    import math

    config = {**cb_harness.load_cell("tpch_sf1.q1_2streams")["config"], **CELLS["tpch_sf1.q1_2streams"]["config"]}
    tables = cb_harness.load_module("data", config["generator"]).make(config, SEED)
    c = tables["lineitem"]["columns"]
    cents = np.rint(c["l_discount"].astype(np.float64) * 100).astype(np.int64)
    requests = cb_traffic.build_requests(Q6, config, SEED, 40)
    assert {r["params"]["YEAR"] for r in requests} == set(range(1993, 1998))
    for r in requests[:10]:
        p = r["params"]
        lo = cb_traffic.date_days(f"{p['YEAR']}-01-01")
        hi = cb_traffic.date_days(f"{p['YEAR'] + 1}-01-01")
        keep = (c["l_shipdate"] >= lo) & (c["l_shipdate"] < hi)
        keep &= np.abs(cents - p["DISCOUNT"]) <= 1
        keep &= c["l_quantity"] < p["QUANTITY"]
        assert 0.005 < keep.mean() < 0.04
        want = math.fsum((c["l_extendedprice"][keep] * c["l_discount"][keep]).astype(np.float64).tolist())
        got = cb_reference.answer(tables, r, Q6["query"])
        assert list(got) == [()]
        value, scale = got[()]["revenue"]
        assert abs(value - want) <= 1e-12 * scale
        assert cb_reference.rows_passing(tables, r) == int(keep.sum())
