"""BENCHMARK.json against the contract's limits, and the files it names."""

import os
import re

import pytest

from benchmark import spec

BENCH = spec.benchmark()
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
LINE = re.compile(r"^[^\t\n]{1,200}$")


def test_keys_names_and_units():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([m["name"] for m in METRICS]
             + [c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    for n in names:
        assert spec.NAME_RE.match(n), n
    for group in (METRICS, BENCH["configs"], BENCH["workloads"]):
        assert len({x["name"] for x in group}) == len(group)
    for m in METRICS:
        assert spec.UNIT_RE.match(m["unit"]) and len(m["unit"]) <= 16, m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in spec.SOURCES
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert LINE.match(m["layer"])
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    # a full check with all 24 cells must fit the driver's 43,200 s
    runs = 2 + 14 * 24
    assert (runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
            <= 43200)
    assert len(open(os.path.join(spec.ROOT, "BENCHMARK.json")).read()) < 65536


def test_cells_configs_and_their_files():
    configs = {c["name"]: c for c in BENCH["configs"]}
    assert {w["config"] for w in BENCH["workloads"]} == set(configs)
    assert len({(w["config"], w["traffic"])
                for w in BENCH["workloads"]}) == len(BENCH["workloads"])
    for c in BENCH["configs"]:
        assert c["file"].startswith("benchmark/")
        assert os.path.exists(os.path.join(spec.ROOT, c["file"]))
        assert LINE.match(c["why"]) and LINE.match(c["source"])
        assert len(c["reduced"]) <= 16
        assert not [k for k in c["reduced"]
                    if re.search(r"(_dim|_rank|hidden_size|intermediate_size"
                                 r"|head_dim)$", k)]
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and LINE.match(w["why"]), w
        workload = spec.workload_of(w)
        assert workload["config"] == w["config"]
        assert hasattr(spec.runner(workload["runner"]), "run")
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    for word in BENCH["command"]:
        assert LINE.match(word) and not word.startswith("/")
        assert ".." not in word


def test_every_cell_reports_setup_another_metric_and_a_layer_metric():
    for w in BENCH["workloads"]:
        e2e = [m["name"] for m in
               spec.metrics_for(BENCH, w["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert spec.metrics_for(BENCH, w["name"], "per_layer"), w["name"]


def test_every_moves_target_is_reported_wherever_the_layer_metric_is():
    cells = [w["name"] for w in BENCH["workloads"]]
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        target = e2e[m["moves"]]
        for c in m.get("workloads", cells):
            assert c in cells
            assert c in target.get("workloads", cells), (m["name"], c)


def test_every_layer_metric_has_its_file_and_reader():
    for m in BENCH["per_layer"]:
        mf = spec.metric_file(m["name"])
        assert hasattr(spec.reader(mf["reader"]), "read")
        assert isinstance(mf["params"], dict)


def test_files_under_paths_are_named_from_the_allowed_characters():
    for path in BENCH["paths"]:
        for d, dirs, files in os.walk(os.path.join(spec.ROOT, path)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), spec.ROOT)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_readers_return_nothing_where_there_is_nothing_to_read():
    from benchmark.readers import (
        counter_ratio, request_percentile, window_value,
    )

    empty = {"requests": [], "counters": {}, "trace": None}
    assert window_value.read(empty, {"name": "tpot_p95_ms"}) is None
    assert window_value.read({"e2e": {"tpot_p95_ms": 93.5}},
                             {"name": "tpot_p95_ms"}) == 93.5
    assert request_percentile.read(
        empty, {"field": "queue_wait", "percentile": 95}) is None
    assert counter_ratio.read(
        empty, {"numerator": "a", "denominator": "b"}) is None
    obs = {"requests": [{"queue_wait": 0.001 * i} for i in range(1, 101)],
           "counters": {"step_host_s": 1.0, "step_wall_s": 4.0}}
    assert request_percentile.read(
        obs, {"field": "queue_wait", "percentile": 95,
              "scale": 1000.0}) == pytest.approx(95.0)
    assert counter_ratio.read(
        obs, {"numerator": "step_host_s", "denominator": "step_wall_s",
              "scale": 100.0}) == pytest.approx(25.0)
