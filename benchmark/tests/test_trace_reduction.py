"""The reduction from trace events to numbers, on hand-made event lists."""

import pytest

from benchmark import flops, trace
from benchmark.readers import program_device_ms
from benchmark.trace import BEGIN, END, Event

DEV, HOST = "/device:TPU:0", "/host:CPU"


def events():
    ns = 1e6      # 1 ms
    return [
        Event(HOST, "t", BEGIN, 0 * ns, 1),
        # under way when the capture began: recorded cut short, not counted
        Event(DEV, "XLA Modules", "jit__paged_decode_fn(11)", 1 * ns, 2 * ns),
        Event(DEV, "XLA Ops", "fusion.1", 1 * ns, 2 * ns),
        Event(HOST, "t", END, 100 * ns, 1),
        # two programs; ops overlap inside the first (busy is a UNION)
        Event(DEV, "XLA Modules", "jit__paged_decode_fn(11)", 10 * ns, 20 * ns),
        Event(DEV, "XLA Ops", "fusion.1", 10 * ns, 12 * ns),
        Event(DEV, "XLA Ops", "copy.2", 18 * ns, 12 * ns),
        Event(DEV, "XLA Modules", "jit__paged_decode_fn(11)", 40 * ns, 30 * ns),
        Event(DEV, "XLA Ops", "fusion.1", 40 * ns, 30 * ns),
        Event(DEV, "XLA Modules", "jit__paged_mixed_fn(12)", 80 * ns, 10 * ns),
        Event(DEV, "XLA Ops", "custom-call.7", 80 * ns, 10 * ns),
        # straddles the end marker: clipped for busy, not a whole execution
        Event(DEV, "XLA Modules", "jit__paged_decode_fn(11)", 95 * ns, 10 * ns),
        Event(DEV, "XLA Ops", "fusion.1", 95 * ns, 10 * ns),
    ]


def test_busy_is_the_union_clipped_to_the_markers():
    r = trace.reduce(events())
    assert r["window_s"] == pytest.approx(0.100)
    # [1,3] + [10,30] + [40,70] + [80,90] + [95,100] = 67 ms
    assert r["busy_s"] == pytest.approx(0.067)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.33)
    gaps = [(b - a) * 1e-6 for a, b in r["gaps_ns"]]
    assert gaps == pytest.approx([1, 7, 10, 10, 5])


def test_programs_keep_whole_executions_only_and_readers_take_medians():
    r = trace.reduce(events())
    assert sorted(r["programs"]) == ["jit__paged_decode_fn",
                                     "jit__paged_mixed_fn"]
    assert r["programs"]["jit__paged_decode_fn"] == pytest.approx(
        [0.020, 0.030])
    obs = {"trace": r}
    assert program_device_ms.read(
        obs, {"pattern": "^jit__paged_decode_fn$"}) == pytest.approx(20.0)  # nearest rank
    assert program_device_ms.read(obs, {"pattern": "^jit_qstep$"}) is None
    assert program_device_ms.read({"trace": None}, {"pattern": "x"}) is None


def test_top_operations_and_gap_charging():
    r = trace.reduce(events())
    ops = trace.top(r["op_seconds"])
    assert ops[0][0] == "fusion.1"
    assert ops[0][1] == pytest.approx(0.002 + 0.012 + 0.030 + 0.005)
    # wall clock 1000.0 s at the begin marker; one step record covers the
    # gap at 30-40 ms, none covers the others
    steps = [{"start_s": 1000.028, "wall_s": 0.015,
              "activities": {"sample_commit": 0.004, "index_build": 0.001}}]
    charged = dict(trace.charge_gaps(r, {"begin_wall": 1000.0}, steps))
    assert charged["step: sample_commit"] == pytest.approx(0.010)
    assert charged["between steps"] == pytest.approx(0.023)


def test_no_device_event_is_an_error():
    with pytest.raises(RuntimeError):
        trace.reduce([Event(HOST, "t", BEGIN, 0, 1)])


def test_flash_attention_cost_and_roofline_share():
    # one causal call: batch 8, 1,024 x 1,024, 40 query / 8 KV heads of 128
    f, b = flops.flash_attention_cost(8, 1024, 1024, 40, 8, 128, causal=True)
    pairs = 1024 * 1025 // 2
    assert f == 4.0 * 128 * pairs * 40 * 8
    assert b == 8 * 128 * (2 * 1024 * 40 + 2 * 1024 * 8) * 2
    # decode-like: one query against a 1,024 cache attends every key
    f1, _ = flops.flash_attention_cost(1, 1, 1024, 32, 8, 128, causal=True)
    assert f1 == 4.0 * 128 * 1024 * 32
    share, bound = flops.roofline_share(f, b, 2 * f / 197e12, 197e12, 819e9)
    assert bound == "compute" and share == pytest.approx(50.0)
    share, bound = flops.roofline_share(1e9, 819e6, 2e-3, 197e12, 819e9)
    assert bound == "memory" and share == pytest.approx(50.0)


def test_qlora_flops_per_token_matches_the_hand_count():
    m = flops.matmul_params(5120, 17408, 40, 8, 128, 40, 151936)
    per_layer = 5120 * (5120 + 2 * 1024) + 5120 * 5120 + 3 * 5120 * 17408
    assert m == 40 * per_layer + 151936 * 5120
    f = flops.qlora_flops_per_token(m, 40, 1024, 5120)
    assert f == 4.0 * m + 6.0 * 40 * 1024 * 5120
