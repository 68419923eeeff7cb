"""``flops_swa.py``'s counts against brute-force counts of live pairs at
small sizes, and the readers of the hybrid cell's metrics on hand-made
observations; nothing to read gives ``None``."""

import json

import pytest

from benchmark import flops_swa, spec
from benchmark.readers import (
    counter_complement,
    counter_ratio,
    program_device_ms,
    scope_roofline,
)
from benchmark.runners import serve_hybrid_cell as cell

CELL = "mimo-v2.5.agent-context"


@pytest.mark.parametrize("start", [0, 3, 7, 8, 20])
@pytest.mark.parametrize("length", [1, 5, 16, 40])
@pytest.mark.parametrize("window", [1, 8, 128])
def test_pairs_against_a_brute_force_count(start, length, window):
    live = [(i, j) for i in range(start, start + length)
            for j in range(i + 1)]
    assert flops_swa.causal_pairs(start, length) == len(live)
    band = [(i, j) for i, j in live if i - j < window]
    assert flops_swa.band_pairs(start, length, window) == len(band)
    assert flops_swa.band_keys(start, length, window) == len(
        {j for _, j in band})


def test_attention_cost_by_hand():
    # 2 query heads over 1 K/V head, keys 3 wide, values 2: a pair costs
    # a 3-wide dot and a 2-wide multiply-add, 2 FLOPs each, a head: 20;
    # 21 pairs x 3 layers; 8 key rows x 1 head x 5 values x 2 bytes x 3
    assert flops_swa.attention_cost(21, 8, 3, 2, 1, 3, 2) == (
        20.0 * 21 * 3, 8 * 5 * 2 * 3.0)
    # the published sizes: 2 x 64 x 320 a pair; a global layer's row
    # 4 x 320 x 2 = 2,560 B, a window layer's 5,120
    assert flops_swa.attention_cost(1, 1, 1, 64, 4, 192, 128) == (
        40960.0, 2560.0)
    assert flops_swa.attention_cost(1, 1, 1, 64, 8, 192, 128)[1] == 5120.0


def _metric(name):
    return spec.metric_file(name)


def test_the_three_rooflines_read_the_runners_observation():
    obs = {"device_kind": "TPU v5 lite",
           "scope_seconds": {cell.WINDOW_PREFILL: 4e-3,
                             cell.GLOBAL_PREFILL: 2e-3,
                             cell.GLOBAL_DECODE: 1e-3},
           "slice_work": {"window_prefill_flops": 197e12 * 1e-3,
                          "window_prefill_bytes": 819e9 * 0.2e-3,
                          "global_prefill_flops": 197e12 * 1e-3,
                          "global_prefill_bytes": 819e9 * 0.5e-3,
                          "global_decode_flops": 197e12 * 0.1e-3,
                          "global_decode_bytes": 819e9 * 0.3e-3}}
    for name, want in (("window_prefill_attention_roofline", 25.0),
                       ("global_prefill_attention_roofline", 50.0),
                       ("global_decode_attention_roofline", 30.0)):
        mf = _metric(name)
        assert mf["reader"] == "scope_roofline"
        assert abs(scope_roofline.read(obs, mf["params"]) - want) < 1e-9
        # the parent's program has no such kernel: nothing, not an error
        assert scope_roofline.read(dict(obs, scope_seconds={}),
                                   mf["params"]) is None
        assert scope_roofline.read(dict(obs, slice_work={}),
                                   mf["params"]) is None


def test_the_two_shares_read_the_runners_counters():
    waste, share = (_metric("global_view_waste_share"),
                    _metric("window_cache_share"))
    obs = {"counters": {"global_tokens_attended": 6000,
                        "global_view_tokens": 16 * 1024,
                        "kv_window_state_bytes": 50,
                        "kv_cache_bytes_peak": 2000}}
    assert abs(counter_complement.read(obs, waste["params"])
               - 100.0 * (1 - 6000 / 16384)) < 1e-9
    assert counter_ratio.read(obs, share["params"]) == 2.5
    # an untraced run takes no pool samples; the parent counts neither
    assert counter_ratio.read({"counters": {"kv_window_state_bytes": 50}},
                              share["params"]) is None
    assert counter_complement.read({"counters": {}},
                                   waste["params"]) is None


@pytest.mark.parametrize("name, want", [
    ("decode_device_ms.tokens", 21.0), ("prefill_device_ms.tokens", 80.0)])
def test_the_program_times_read_their_own_programs(name, want):
    """The cell is judged by tokens a second, so its two program times
    are listed under names whose ``moves`` says so; each reads the
    executions of its own programs and nothing where there are none."""
    mf = _metric(name)
    entry = next(m for m in spec.benchmark()["per_layer"]
                 if m["name"] == name)
    assert (entry["moves"], entry["workloads"]) == (
        "serve_tokens_per_s", [CELL])
    obs = {"trace": {"programs": {
        "jit__paged_decode_fn": [0.020, 0.021, 0.022],
        "jit__paged_mixed_fn": [0.080, 0.090],
        "jit__paged_chunk_fn": [0.060], "jit_other": [1.0]}}}
    assert abs(program_device_ms.read(obs, mf["params"]) - want) < 1e-9
    assert program_device_ms.read({"trace": {"programs": {}}},
                                  mf["params"]) is None
    assert program_device_ms.read({"trace": None}, mf["params"]) is None


def test_slice_work_counts_only_the_steps_inside_the_slice():
    cfg = cell.model_config(cell.REHEARSAL | {
        "vocab_size": 64, "hidden_size": 32, "intermediate_size": 64,
        "router_experts": 32, "n_routed_experts": 8, "expert_offset": 0,
        "partial_rotary_factor": 0.334})
    steps = [{"start_s": 1.0, "prefill_band_pairs": 10,
              "prefill_band_keys_read": 4, "prefill_global_pairs": 30,
              "prefill_keys_read": 9, "global_tokens_attended": 7},
             {"start_s": 5.0, "prefill_band_pairs": 1000,
              "global_tokens_attended": 1000}]
    work = cell.slice_work(steps, {"begin_wall": 0.5, "end_wall": 2.0}, cfg)
    assert work["steps"] == 1
    # 2 window layers of 4 K/V heads, 2 global of 2; 8 heads, 24 + 16
    assert work["window_prefill_flops"] == 2.0 * 8 * 40 * 10 * 2
    assert work["window_prefill_bytes"] == 4 * 4 * 40 * 2 * 2
    assert work["global_prefill_flops"] == 2.0 * 8 * 40 * 30 * 2
    assert work["global_prefill_bytes"] == 9 * 2 * 40 * 2 * 2
    assert work["global_decode_flops"] == 2.0 * 8 * 40 * 7 * 2
    assert work["global_decode_bytes"] == 7 * 2 * 40 * 2 * 2
    assert cell.slice_work(steps, {}, cfg) == {}


def test_scope_patterns_find_the_paths_by_their_text():
    cfg = cell.model_config(cell.REHEARSAL | {
        "vocab_size": 64, "hidden_size": 32, "intermediate_size": 64,
        "router_experts": 32, "n_routed_experts": 8, "expert_offset": 0})
    pats = cell.scope_patterns(cfg, 16)
    text = "%fusion.7 = f32[16,8,8192]{2,1,0} fusion(...)"
    assert pats[cell.GLOBAL_DECODE].search(text)
    assert not pats[cell.GLOBAL_DECODE].search("f32[16,4,2,8]{3,2,1,0}")
    assert not pats[cell.GLOBAL_DECODE].search("bf16[16,1,8,24]{3,2,1,0}")
    assert pats[cell.WINDOW_PREFILL].search("window_prefill_flash.36")
    assert pats[cell.GLOBAL_PREFILL].search("global_prefill_flash.14")
    assert not pats[cell.GLOBAL_PREFILL].search("window_prefill_flash.36")


def test_the_cell_is_listed_where_it_has_something_to_read():
    bench = spec.benchmark()
    assert len(bench["workloads"]) == 6 and len(bench["configs"]) == 5
    assert all(len(x["why"]) <= 200
               for x in bench["workloads"] + bench["configs"])
    e2e = {m["name"] for m in spec.metrics_for(bench, CELL, "end_to_end")}
    assert {"serve_tokens_per_s", "setup_s"} <= e2e
    for m in spec.metrics_for(bench, CELL, "per_layer"):
        assert m["moves"] in e2e, m["name"]
        mf = spec.metric_file(m["name"])
        assert spec.reader(mf["reader"]) is not None
    workload = spec.workload_of(spec.cell(bench, CELL))
    assert workload["prompt_tokens"] == {
        "dist": "lognormal", "median": 12288, "sigma": 0.7, "min": 4096,
        "max": 24576}
    assert (workload["clients"], workload["pool"], workload["cycle"]) == (
        16, 128, 64)
    config = spec.config_of(bench, spec.cell(bench, CELL))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "mimo-v2.5-ep16-bf16-serve")
    assert set(entry["reduced"]) == set(config["changed_from_source"])
    assert set(config["published"]) == set(entry["reduced"])
    json.dumps(config)
