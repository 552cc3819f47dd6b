"""``BENCHMARK.json`` against the benchmark's contract, and the traffic
generators."""
import json
import os
import re

import numpy as np
import pytest

from chipbench import spec
from chipbench.tests.conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark(ROOT)


def test_benchmark_json_keeps_the_contract(bench):
    assert set(bench) == TOP_KEYS
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert bench["command"] == ["python3", "chipbench/run.py"]
    assert bench["paths"] == ["chipbench"]
    assert 1 <= bench["run_seconds"] <= 51
    names = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    for n in names + cells + metrics:
        assert NAME.match(n), n
    assert len(set(names)) == len(names) and len(set(cells)) == len(cells)
    assert len(set(metrics)) == len(metrics)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/")
        cfg = spec.config(bench, c["name"], ROOT)
        assert cfg["reduced"] == c["reduced"] == []
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        mix = spec.traffic(w["traffic"])
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "traffic", "kinds", f"{mix['kind']}.py"))
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 2)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for cell in m["workloads"]:
            target = next(x for x in bench["end_to_end"]
                          if x["name"] == m["moves"])
            assert cell in target.get("workloads", cells)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "metrics", f"{m['name']}.py"))
    for cell in cells:
        mine = [m for m in bench["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])


def test_peak_table_refuses_unknown_devices():
    assert spec.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("TPU v99")


def test_open_loop_offers_the_same_gaps_in_another_order():
    kind = spec.traffic_kind("open")
    mix = {"kind": "open", "rate": 500.0, "max_bucket": 32}
    a = kind.arrivals(mix, 10.0, 2 ** 40 + 1)
    b = kind.arrivals(mix, 10.0, 7)
    assert a != b
    assert abs(len(a) - 5000) <= 5 and abs(len(b) - 5000) <= 5
    ga, gb = np.diff([0.0] + a), np.diff([0.0] + b)
    n = min(len(ga), len(gb))
    assert np.allclose(np.sort(ga)[:n - 5], np.sort(gb)[:n - 5])
    assert kind.warm_sizes(mix, 1) == list(range(1, 33))


def test_open_loop_arrivals_fall_in_order_inside_the_window():
    kind = spec.traffic_kind("open")
    mix = {"kind": "open", "rate": 500.0, "max_bucket": 32}
    due = np.array(kind.arrivals(mix, 10.0, 3))
    assert (np.diff(due) >= 0).all() and 0 < due[0] and due[-1] < 10.0
    assert abs(len(due) / 10.0 - 500.0) < 5.0
    assert kind.warm_sizes(mix, 4) == list(range(1, 129))


def test_closed_loop_warms_its_one_batch():
    kind = spec.traffic_kind("closed")
    assert kind.warm_sizes({"depth": 128, "max_bucket": 32}, 4) == [128]


def test_mix_files_are_data():
    d = os.path.join(ROOT, "chipbench", "traffic")
    for f in os.listdir(d):
        if f.endswith(".json"):
            with open(os.path.join(d, f)) as fh:
                mix = json.load(fh)
            assert "kind" in mix and "max_bucket" in mix
