"""The four metrics ``trinity-large.mixed-lengths`` brought (PR 44) on
hand-made observations, the slice's work by hand at this model's heads
(2 x 48 x 256 FLOPs a live pair, 4,096 B a key row a layer), the device
plane's paths by their text, and the cell's entries; nothing to read gives
``None``."""

import pytest

from benchmark import flops_swa, spec
from benchmark.readers import (
    counter_complement,
    counter_ratio,
    scope_roofline,
)
from benchmark.runners import serve_window_ring_cell as cell

CELL = "trinity-large.mixed-lengths"
CONFIG = "trinity-large-ep8-bf16-serve"
NEW = ("window_ring_prefill_attention_roofline",
       "window_decode_attention_roofline", "window_ring_waste_share",
       "chunk_fill_share")


@pytest.fixture(scope="module")
def cfg():
    bench = spec.benchmark()
    return cell.model_config(spec.config_of(bench, spec.cell(bench, CELL)))


def test_a_pair_and_a_row_at_the_published_heads():
    assert flops_swa.attention_cost(1, 1, 1, 48, 8, 128, 128) == (
        2.0 * 48 * 256, 4096.0)
    # a chunk of 2,048 at 12,288 under a band of 4,096: every query sees
    # the whole band; its own keys and the 4,095 before its first are read
    assert flops_swa.band_pairs(12288, 2048, 4096) == 2048 * 4096
    assert flops_swa.band_keys(12288, 2048, 4096) == 2048 + 4095
    # three quarters of those pairs lie in the ring (before the chunk)
    own = flops_swa.causal_pairs(0, 2048)
    assert 0.74 < 1 - own / (2048 * 4096) < 0.76


def test_the_two_rooflines_read_the_runners_observation():
    obs = {"device_kind": "TPU v5 lite",
           "scope_seconds": {cell.WINDOW_RING_PREFILL: 4e-3,
                             cell.WINDOW_DECODE: 2e-3},
           "slice_work": {"window_ring_prefill_flops": 197e12 * 1e-3,
                          "window_ring_prefill_bytes": 819e9 * 0.2e-3,
                          "window_decode_flops": 197e12 * 0.1e-3,
                          "window_decode_bytes": 819e9 * 0.5e-3}}
    for name, want in (("window_ring_prefill_attention_roofline", 25.0),
                       ("window_decode_attention_roofline", 25.0)):
        mf = spec.metric_file(name)
        assert mf["reader"] == "scope_roofline"
        assert abs(scope_roofline.read(obs, mf["params"]) - want) < 1e-9
        # a program without the path, a slice without such work: nothing
        assert scope_roofline.read(dict(obs, scope_seconds={}),
                                   mf["params"]) is None
        assert scope_roofline.read(dict(obs, slice_work={}),
                                   mf["params"]) is None


def test_the_two_shares_read_the_runners_counters():
    waste, fill = (spec.metric_file("window_ring_waste_share"),
                   spec.metric_file("chunk_fill_share"))
    obs = {"counters": {"window_rows_attended": 16384,
                        "window_ring_rows_read": 16 * 4096,
                        "prefill_chunk_tokens": 300 + 2048 + 1000,
                        "prefill_chunk_capacity": 3 * 2048}}
    assert counter_complement.read(obs, waste["params"]) == 75.0
    assert abs(counter_ratio.read(obs, fill["params"])
               - 100.0 * 3348 / 6144) < 1e-9
    # the parent's program counts neither
    assert counter_complement.read({"counters": {
        "window_rows_attended": 5}}, waste["params"]) is None
    assert counter_ratio.read({"counters": {}}, fill["params"]) is None


def test_slice_work_by_hand_counts_only_the_steps_inside_the_slice(cfg):
    steps = [{"start_s": 1.0, "prefill_band_pairs": 10,
              "prefill_band_keys_read": 4, "prefill_global_pairs": 30,
              "prefill_keys_read": 9, "global_tokens_attended": 7,
              "window_rows_attended": 5, "window_ring_rows_read": 65536},
             {"start_s": 5.0, "prefill_band_pairs": 1000,
              "window_rows_attended": 1000}]
    work = cell.slice_work(steps, {"begin_wall": 0.5, "end_wall": 2.0}, cfg)
    assert work["steps"] == 1
    pair, row = 2.0 * 48 * 256, 4096     # 4 window layers, 1 global
    assert work["window_ring_prefill_flops"] == pair * 10 * 4
    assert work["window_ring_prefill_bytes"] == row * 4 * 4
    assert work["global_prefill_flops"] == pair * 30
    assert work["global_prefill_bytes"] == row * 9
    assert work["global_decode_flops"] == pair * 7
    assert work["global_decode_bytes"] == row * 7
    # the rows ATTENDED, never the rows read
    assert work["window_decode_flops"] == pair * 5 * 4
    assert work["window_decode_bytes"] == row * 5 * 4
    assert cell.slice_work(steps, {}, cfg) == {}


def test_scope_patterns_find_the_paths_by_their_text(cfg):
    class Engine:
        max_slots, chunked_prefill = 16, 2048

        class paged:
            ring_rows = 4096

    pats = cell.scope_patterns(cfg, Engine)
    ring, decode = pats[cell.WINDOW_RING_PREFILL], pats[cell.WINDOW_DECODE]
    assert ring.search("%window_ring_prefill_flash.12 = custom-call(")
    assert ring.search("bf16[1,6144,8,128]{3,2,1,0} gather(")
    assert ring.search("bf16[1,8,6144,128]{3,2,1,0} transpose(")
    assert not ring.search("global_prefill_flash.3")
    assert not ring.search("bf16[1,2048,8,128]{3,2,1,0}")
    assert pats[cell.GLOBAL_PREFILL].search("global_prefill_flash.3")
    assert not pats[cell.GLOBAL_PREFILL].search("window_ring_prefill_flash")
    assert decode.search("bf16[16,4096,8,128]{3,2,1,0} dynamic-update-")
    assert decode.search("f32[16,8,6,4096]{3,2,1,0} fusion(")
    assert not decode.search("bf16[16,32768,1024]{2,1,0}")
    assert pats[cell.GLOBAL_DECODE].search("f32[16,48,32768]{2,1,0}")
    assert not pats[cell.GLOBAL_DECODE].search("f32[16,8,6,4096]")


def test_the_cell_is_listed_where_it_has_something_to_read():
    bench = spec.benchmark()
    entry = spec.cell(bench, CELL)
    assert (entry["config"], entry["chips"]) == (CONFIG, 1)
    assert len(entry["why"]) <= 200
    e2e = {m["name"] for m in spec.metrics_for(bench, CELL, "end_to_end")}
    # judged by tokens a second; the closed loop's TTFT tail is per layer
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    listed = {m["name"]: m for m in
              spec.metrics_for(bench, CELL, "per_layer")}
    assert set(NEW) <= set(listed)
    assert "window_prefill_attention_roofline" not in listed
    for name, m in listed.items():
        assert m["moves"] in e2e, name
        mf = spec.metric_file(name)
        assert spec.reader(mf["reader"]) is not None
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
    config = spec.config_of(bench, entry)
    conf = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert set(conf["reduced"]) == set(config["changed_from_source"]) == set(
        config["published"])
    workload = spec.workload_of(entry)
    assert workload["prompt_tokens"] == {
        "dist": "lognormal", "median": 4096, "sigma": 1.2, "min": 256,
        "max": 24576}
    assert workload["output_tokens"] == {"dist": "loguniform", "min": 64,
                                         "max": 512}
    assert (workload["clients"], workload["pool"], workload["cycle"],
            workload["max_total_tokens"]) == (16, 128, 64, 25088)
