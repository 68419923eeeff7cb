"""The readers over ``Request.cp`` that PR 27's metrics use, and those
metrics' files."""

import pytest

from benchmark import spec
from benchmark.readers import request_sum_ratio

PARAMS = spec.metric_file("dispatch_issue_share")["params"]
NEW = ("prefill_stall_p95_ms", "request_host_gap_p95_ms",
       "dispatch_issue_share", "frontend_pre_submit_p95_ms",
       "first_flush_p95_ms")


def test_sum_ratio_is_a_ratio_of_sums():
    obs = {"requests": [
        {"dispatch_issue": 0.02, "prefill_dispatch": 0.1,
         "decode_dispatch": 0.5, "host_gap": 9.0},
        {"dispatch_issue": 0.01, "decode_dispatch": 0.2,
         "prefill_stall": 0.15, "decode_interleave": 0.05},
    ]}
    assert request_sum_ratio.read(obs, PARAMS) == pytest.approx(
        100.0 * 0.03 / 1.0)
    # not a mean of ratios: a long request weighs by its windows
    obs["requests"].append({"dispatch_issue": 1.0, "decode_dispatch": 1.0})
    assert request_sum_ratio.read(obs, PARAMS) == pytest.approx(
        100.0 * 1.03 / 2.0)


@pytest.mark.parametrize("requests", [
    [],
    # the parent commit: windows booked, no issue part anywhere
    [{"prefill_dispatch": 0.1, "decode_dispatch": 0.5, "host_gap": 0.2}],
    # an issue part and nothing under the denominator
    [{"dispatch_issue": 0.01, "queue_wait": 1.0}],
], ids=["no-request", "older-program", "no-window"])
def test_sum_ratio_gives_nothing_where_nothing_is_to_read(requests):
    assert request_sum_ratio.read({"requests": requests}, PARAMS) is None


def test_an_issue_part_of_zero_is_a_reading():
    obs = {"requests": [{"dispatch_issue": 0.0, "decode_dispatch": 0.5}]}
    assert request_sum_ratio.read(obs, PARAMS) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_new_metrics_are_wired(name):
    bench = spec.benchmark()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == ["qwen3-8b.chat-steady",
                                  "qwen3-8b.prefill-heavy"]
    assert (entry["source"], entry["better"], entry["moves"]) == (
        "program_span", "lower", "ttft_p95_ms")
    mf = spec.metric_file(name)
    assert callable(spec.reader(mf["reader"]).read)
    # an engine that finished nothing in the window: no reading, no raise
    assert spec.reader(mf["reader"]).read(
        {"requests": [], "counters": {}}, mf["params"]) is None
