"""BENCHMARK.json and the files it names: every configuration, traffic
mix, generator and metric reader loads by name, and the entries keep the
benchmark's rules (names, units, lengths, bounds, which cell reports what)."""

from __future__ import annotations

import json
import os
import re

import pytest

import cb_harness
import cb_traffic

ROOT = cb_harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_paths(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(ROOT, p))
    assert 1 <= len(bench["command"]) <= 32 and all(_line(w) for w in bench["command"])
    script = bench["command"][1]
    assert any(script.startswith(p + "/") for p in bench["paths"]) and os.path.isfile(os.path.join(ROOT, script))
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_configs(bench):
    used = {w["config"] for w in bench["workloads"]}
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["name"] in used
        assert c["file"] not in files and any(c["file"].startswith(p + "/") for p in bench["paths"])
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        assert sorted(c["reduced"]) == sorted(conf["reduced"]), "reduced keys are stated in the file"
        for k in c["reduced"]:
            assert k in conf and not k.endswith(("_dim", "_rank"))
        assert cb_harness.load_module("data", conf["generator"]).make is not None


def test_workloads(bench):
    configs = {c["name"] for c in bench["configs"]}
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and _line(w["why"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        traffic = cb_harness.load_json("traffic", f"{w['traffic']}.json")
        assert traffic["loop"] in ("closed", "open")
        assert set(traffic["check"]["limits"]) >= {"failed", "groups_wrong", "sum_rel_err"}
    assert 2 * sum(w["chips"] == 4 for w in bench["workloads"]) <= max(2, len(bench["workloads"]))


def test_metrics_and_readers(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    names = set()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
        assert callable(cb_harness.load_module("e2e_metrics", m["name"]).read)
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert _line(m["layer"]) and m["moves"] in e2e and "mfu" not in m["name"]
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
        assert callable(cb_harness.load_module("layer_metrics", m["name"]).read)
    for w in cells:
        cell = cb_harness.load_cell(w, bench)
        reported = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2 and cell["per_layer"]


@pytest.mark.parametrize("traffic_name", ["q1_2streams", "level_stats_open"])
def test_traffic_requests_fold(bench, traffic_name):
    (w,) = [w for w in bench["workloads"] if w["traffic"] == traffic_name]
    cell = cb_harness.load_cell(w["name"], bench)
    reqs = cb_traffic.build_requests(cell["traffic"], cell["config"], 2**31 + 7, 64)
    def ops(tree):
        if isinstance(tree, list):
            yield tree[0]
            if tree[0] != "col":
                for a in tree[1:]:
                    yield from ops(a)

    for r in reqs:
        assert r["sources"] and len(set(r["sources"])) == len(r["sources"])
        assert not set(ops(r["filter"])) & {"param", "date", "config"}
        if "time_steps" in cell["config"]:  # one table per step, t000 ... : every step read exists
            assert all(0 <= int(t[1:]) < cell["config"]["time_steps"] for t in r["sources"])


def test_peaks_table():
    peaks = cb_harness.load_json("peaks.json")
    v5e = peaks["devices"]["TPU v5 lite"]
    assert peaks["source"] and v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12 and v5e["hbm_bytes"] == 16e9
