"""The benchmark's yardstick on its own: work counts against hand counts,
the traffic generator's draws, and the trace reduction on a synthetic trace."""

from __future__ import annotations

import numpy as np
import pytest

import cb_harness
import cb_trace
import cb_traffic
import cb_work

Q1 = cb_harness.load_json("traffic", "q1_2streams.json")["query"]
ERA5 = cb_harness.load_json("traffic", "level_stats_open.json")["query"]
Q1_ITEMS = {"l_quantity": 4, "l_extendedprice": 4, "l_discount": 4, "l_tax": 4, "l_returnflag": 1, "l_linestatus": 1, "l_shipdate": 4}
ERA5_ITEMS = {
    c: 4
    for c in [
        "time", "level", "lat", "lon", "temperature", "u_component_of_wind", "v_component_of_wind",
        "specific_humidity", "geopotential", "vertical_velocity",
    ]
}


@pytest.mark.parametrize(
    "query,items,row_bytes,result_bytes,row_ops",
    [
        # flags 1 B each + quantity, extendedprice, discount, tax 4 B each; keys 2 B +
        # 7 float sums/means + count at 8 B; sub+mul, add+mul; 4 sums, 3 means (2), count
        (Q1, Q1_ITEMS, 18, 66, 4 + 4 + 6 + 1),
        # level + six variables at 4 B; level 4 + count 8 + 6 x (sum, sumsq 8; min, max 4);
        # six squares; count + 6 x (sum, sumsq, min, max)
        (ERA5, ERA5_ITEMS, 28, 4 + 8 + 6 * 24, 6 + 1 + 24),
    ],
)
def test_work_matches_hand_counts(query, items, row_bytes, result_bytes, row_ops):
    assert cb_work.row_bytes(query, items) == row_bytes
    assert cb_work.result_row_bytes(query, items) == result_bytes
    assert cb_work.row_ops(query) == row_ops
    assert cb_work.request_work(query, items, 1000, 4) == (1000 * row_bytes + 4 * result_bytes, 1000 * row_ops)


def test_least_time_names_its_bound():
    peaks = {"hbm_bytes_per_s": 1e9, "ops_per_s": 1e12}
    assert cb_work.least_time(2e9, 1e12, peaks) == (2.0, "bytes")
    assert cb_work.least_time(1e6, 3e12, peaks) == (3.0, "ops")


def test_balanced_draws_follow_the_schedule_not_the_run_seed():
    specs = [{"name": "K", "draw": "balanced_int", "range": [1, 4]}]
    a = [p["K"] for p in cb_traffic.draw_params(specs, 40, 1, {}, 7)]
    b = [p["K"] for p in cb_traffic.draw_params(specs, 40, 2**33 + 5, {}, 7)]
    c = [p["K"] for p in cb_traffic.draw_params(specs, 40, 1, {}, 8)]
    assert a == b and a != c
    assert sorted(a) == sorted(c) == sorted([1, 2, 3, 4] * 10)


def test_uniform_draw_respects_earlier_params_and_follows_the_run_seed():
    specs = [
        {"name": "K", "draw": "balanced_int", "range": [1, 4]},
        {"name": "S", "draw": "uniform_int", "range": [0, ["sub", ["config", "steps"], ["param", "K"]]]},
    ]
    a = cb_traffic.draw_params(specs, 400, 3, {"steps": 8}, 7)
    b = cb_traffic.draw_params(specs, 400, 2**35 + 3, {"steps": 8}, 7)
    for p in a + b:
        assert 0 <= p["S"] <= 8 - p["K"]
    assert [p["K"] for p in a] == [p["K"] for p in b] and [p["S"] for p in a] != [p["S"] for p in b]


def test_fold_dates_and_params():
    tree = ["le", ["col", "d"], ["sub", ["date", "1998-12-01"], ["param", "DELTA"]]]
    assert cb_traffic.fold(tree, {"DELTA": 90}, {}) == ["le", ["col", "d"], 10561 - 90]
    assert cb_traffic.date_days("1998-12-01") == 10561
    # a date from a drawn year, across the 1996 leap day
    nxt = ["date_ymd", ["add", ["param", "YEAR"], 1], 1, 1]
    assert cb_traffic.fold(["date_ymd", ["param", "YEAR"], 1, 1], {"YEAR": 1996}, {}) == 9496
    assert cb_traffic.fold(nxt, {"YEAR": 1996}, {}) == 9496 + 366
    assert cb_traffic.fold(nxt, {"YEAR": 1995}, {}) == 9496


def test_arrivals_keep_their_count_gap_set_and_window():
    traffic = {"loop": "open", "rate_per_s": 5.0}
    n = cb_traffic.request_count(traffic, 100.0)
    assert n == 500 and cb_traffic.request_count(traffic, 0.01) == 1
    a = cb_traffic.arrival_offsets(n, 11, 100.0)
    b = cb_traffic.arrival_offsets(n, 2**40 + 12, 100.0)
    assert np.array_equal(a, cb_traffic.arrival_offsets(n, 11, 100.0))
    assert a[0] == b[0] == 0.0 and not np.allclose(a, b)
    gaps_a, gaps_b = np.diff(np.append(a, 100.0)), np.diff(np.append(b, 100.0))
    assert np.allclose(np.sort(gaps_a), np.sort(gaps_b)) and np.all(gaps_a > 0)
    assert a[-1] < 100.0 and abs(np.sum(gaps_a) - 100.0) < 1e-9


@pytest.mark.parametrize("n", [2, 3, 81, 82, 83, 84])
def test_arrivals_split_evenly_between_the_window_halves(n):
    a = cb_traffic.arrival_offsets(n, 20261016, 51.0)
    assert np.all(np.diff(a) > 0) and a[0] == 0.0 and a[-1] < 51.0
    assert int(np.sum(a < 25.5 - 1e-9)) == n // 2
    assert cb_traffic.arrival_offsets(1, 5, 51.0).tolist() == [0.0]


def test_streams_deal_requests_round_robin():
    reqs = list(range(7))
    assert cb_traffic.streams({"streams": 2}, reqs) == [[0, 2, 4, 6], [1, 3, 5]]


def _synthetic():
    """Device ops named as the TPU trace names them: by HLO instruction."""
    tr = cb_trace.Trace()
    tr.devices["/device:TPU:0"] = [
        ("%fused_chain_tiles.1 = (s32[8]{0}) custom-call(s32[8]{0} %a)", 100, 200),
        ("%copy.3 = s32[8]{0} copy(s32[8]{0} %fused_chain_tiles.1)", 150, 260),  # names the kernel as an operand
        ("%fused_chain_tiles.1 = (s32[8]{0}) custom-call(s32[8]{0} %a)", 400, 450),
        ("%get-tuple-element.7 = s32[8]{0} get-tuple-element((s32[8]{0}) %fused_chain_tiles.1), index=0", 900, 950),
        ("%copy.9 = s32[8]{0} copy(s32[8]{0} %b)", 1200, 1300),  # after the window
    ]
    tr.host = [(cb_trace.WINDOW_SPAN, 0, 1000), (cb_trace.REQUEST_SPAN, 50, 500)]
    return tr


def test_synthetic_trace_reduction():
    tr = _synthetic()
    lo, hi = cb_trace.window(tr)
    assert (lo, hi) == (0, 1000)
    # busy: [100, 260] + [400, 450] + [900, 950] = 160 + 50 + 50; the op past the window is cut
    assert cb_trace.busy_ns(tr, lo, hi) == 260
    assert cb_trace.idle_pct(tr, lo, hi) == pytest.approx(74.0)
    # the kernel's own instructions only, not the ops that read its outputs
    assert cb_trace.kernel_ns(tr, lo, hi, "fused_chain_tiles") == 100 + 50
    assert cb_trace.kernel_ns(tr, lo, hi, "segment_sum_tiles") == 0
    assert cb_trace.top_ops(tr, lo, hi) == [["fused_chain_tiles", 150 / 1e9], ["copy", 110 / 1e9], ["get-tuple-element", 50 / 1e9]]
    gaps = cb_trace.idle_gaps(tr, lo, hi)
    # gaps [450, 900] and [950, 1000] have no request in flight at their middle; [260, 400] and [0, 100] have
    assert gaps[0] == [f"no {cb_trace.REQUEST_SPAN} at 0.000 s", 450 / 1e9]
    assert gaps[1] == [f"{cb_trace.REQUEST_SPAN} at 0.000 s", 140 / 1e9]
    assert [g[1] for g in gaps] == [450 / 1e9, 140 / 1e9, 100 / 1e9, 50 / 1e9]


@pytest.mark.parametrize(
    "name,op",
    [
        ("%fused_chain_tiles.1 = (s32[65536,12]{1,0}) custom-call(s32[4]{0} %scalars)", "fused_chain_tiles"),
        ("%copy-start.2 = (s32[1]{0}, u32[]) copy-start(s32[1]{0} %limb_tbl.1)", "copy-start"),
        ("%xor_bitcast_fusion = s32[1,65024]{1,0} fusion(f32[65024,1]{0,1} %mmf.1), kind=kLoop", "xor_bitcast_fusion"),
        ("jit_fused_chain_tiles(4526410447928524780)", "jit_fused_chain_tiles(4526410447928524780)"),
    ],
)
def test_op_names(name, op):
    assert cb_trace.op_name(name) == op


def _recorded():
    """A trace recorded on one TPU v5e (see the fixture's ``recorded``),
    as ``cb_trace.load_xplane`` reduced it: the device's op events and the
    benchmark's host spans."""
    import gzip
    import json
    import os

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata", "era5_level_stats_trace.json.gz")
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    tr = cb_trace.Trace()
    tr.devices = {k: [tuple(e) for e in v] for k, v in raw["devices"].items()}
    tr.host = [tuple(e) for e in raw["host"]]
    return tr


def test_recorded_trace_reduction():
    tr = _recorded()
    lo, hi = cb_trace.window(tr)
    (evs,) = tr.devices.values()
    # the run launched 42 fused morsels: one kernel event each, 12.77 ms in all
    kernel = [(s, e) for n, s, e in evs if cb_trace.op_name(n) == "fused_chain_tiles"]
    assert len(kernel) == 42
    assert cb_trace.kernel_ns(tr, lo, hi, "fused_chain_tiles") == sum(e - s for s, e in kernel) == 12773269
    # busy time against a union taken on a 100 ns grid
    step = 100
    grid = np.zeros((hi - lo) // step + 1, bool)
    for _n, s, e in evs:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            grid[(a - lo) // step : (b - lo + step - 1) // step] = True
    busy = cb_trace.busy_ns(tr, lo, hi)
    assert abs(busy - grid.sum() * step) <= 2 * step * len(evs)
    assert 0.0 < busy < hi - lo
    assert cb_trace.idle_pct(tr, lo, hi) == pytest.approx(100.0 * (1.0 - busy / (hi - lo)))
    top = cb_trace.top_ops(tr, lo, hi)
    assert top[0] == ["fused_chain_tiles", 12773269 / 1e9] and len(top) <= 10
    assert sum(t for _n, t in top) <= busy / 1e9 * 1.5  # ops overlap (async copies), but not by half
    gaps = cb_trace.idle_gaps(tr, lo, hi)
    assert len(gaps) == 10 and all(g[1] > 0 for g in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    assert {g[0].split(" at ")[0] for g in gaps} <= {cb_trace.REQUEST_SPAN, f"no {cb_trace.REQUEST_SPAN}"}
    assert sum(g[1] for g in gaps) <= (hi - lo - busy) / 1e9 + 1e-9
