"""Where the benchmark's data lives and how the harness finds it by name.

``BENCHMARK.json`` at the repo root names the cells, configurations and
metrics; everything that belongs to one of them sits in a file of its
own under ``benchmark/`` that later PRs add and never edit:

- ``configs/<config>.json``    sizes, source, departures, layout
- ``workloads/<cell>.json``    runner, loop, rate or clients, lengths
- ``metrics/<metric>.json``    reader and its parameters
- ``runners/<runner>.py``      ``run(ctx) -> Observation``
- ``readers/<reader>.py``      ``read(obs, params) -> float | None``
"""

from __future__ import annotations

import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts), encoding="utf-8") as f:
        return json.load(f)


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"BENCHMARK.json has no workload {name!r}; it has "
                     f"{[w['name'] for w in bench['workloads']]}")


def config_of(bench: dict, cell_entry: dict) -> dict:
    for c in bench["configs"]:
        if c["name"] == cell_entry["config"]:
            with open(os.path.join(ROOT, c["file"]), encoding="utf-8") as f:
                return json.load(f)
    raise SystemExit(f"no configuration {cell_entry['config']!r}")


def workload_of(cell_entry: dict) -> dict:
    return load_json("workloads", cell_entry["name"] + ".json")


def metrics_for(bench: dict, cell_name: str, group: str) -> list[dict]:
    """Entries of ``end_to_end`` or ``per_layer`` this cell reports: all
    that carry no ``workloads`` key, and those that list the cell."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def metric_file(name: str) -> dict:
    return load_json("metrics", name + ".json")


def runner(name: str):
    return importlib.import_module(f"benchmark.runners.{name}")


def reader(name: str):
    return importlib.import_module(f"benchmark.readers.{name}")
