"""Hand-worked cases for ``flops_mla.py``'s two counts and the two readers
the latent cell brought; nothing to read gives ``None``."""

from benchmark import flops_mla
from benchmark.readers import counter_complement, scope_roofline


def test_decode_cost_by_hand():
    # 2 heads, latent 4 + 2 = 6: a (query, key) pair of one head costs a
    # 6-wide dot and a 4-wide multiply-add, 2 FLOPs each: 20; 10 attended
    # rows x 2 heads x 3 layers = 1,200 FLOPs; every row read once a
    # layer: 10 x 6 values x 2 bytes x 3
    assert flops_mla.decode_cost(10, 3, 2, 4, 2) == (1200.0, 360.0)
    # the published sizes: 2 x 128 x (576 + 512) a row, 1,152 bytes
    flops, nbytes = flops_mla.decode_cost(1, 1, 128, 512, 64)
    assert (flops, nbytes) == (278528.0, 1152.0)


def test_prefill_cost_by_hand():
    # 3 queries at positions 5, 6, 7 see 6 + 7 + 8 = 21 keys
    assert flops_mla.chunk_pairs(5, 3) == 21
    assert flops_mla.chunk_pairs(0, 4) == 10
    # 2 heads, nope 3 + rope 1 + v 2 = 6 wide a pair: 2 x 2 x 6 = 24
    # FLOPs; 21 pairs x 2 layers; 8 latent rows (rank 4 + rope 1) read
    # once a layer
    assert flops_mla.prefill_cost(21, 8, 2, 2, 3, 1, 2, 4) == (
        24.0 * 21 * 2, 8 * 5 * 2 * 2.0)
    flops, _ = flops_mla.prefill_cost(1, 0, 1, 128, 128, 64, 128, 512)
    assert flops == 2 * 128 * 320


def test_scope_roofline_reader():
    params = {"scope": "mla_decode_attention", "flops": "mla_decode_flops",
              "bytes": "mla_decode_bytes"}
    obs = {"device_kind": "TPU v5 lite",
           "scope_seconds": {"mla_decode_attention": 2e-3},
           "slice_work": {"mla_decode_flops": 197e12 * 0.5e-3,
                          "mla_decode_bytes": 819e9 * 1e-3}}
    # memory-bound: 1 ms of bytes against 0.5 ms of FLOPs, in 2 ms: 50%
    assert abs(scope_roofline.read(obs, params) - 50.0) < 1e-9
    assert scope_roofline.read({"device_kind": "TPU v5 lite"}, params) is None
    assert scope_roofline.read(dict(obs, scope_seconds={}), params) is None
    assert scope_roofline.read(dict(obs, slice_work={}), params) is None
    # the parent's program has no such scope: the trace gives no seconds
    assert scope_roofline.read(
        dict(obs, scope_seconds={"mla_prefill_attention": 1.0}),
        params) is None


def test_counter_complement_reader():
    params = {"numerator": "latent_tokens_attended",
              "denominator": "latent_view_tokens", "scale": 100.0}
    obs = {"counters": {"latent_tokens_attended": 6000,
                        "latent_view_tokens": 16 * 1024}}
    want = 100.0 * (1 - 6000 / 16384)
    assert abs(counter_complement.read(obs, params) - want) < 1e-9
    assert counter_complement.read({"counters": {}}, params) is None
    assert counter_complement.read(
        {"counters": {"latent_tokens_attended": 5,
                      "latent_view_tokens": 0}}, params) is None


def test_scope_seconds_and_program_ops_on_hand_made_events():
    """The device plane names an operation by its instruction's text: the
    prefill path is its kernel's custom calls, the decode path whatever
    holds a (slots, heads, n) tensor; a loop's own event and whatever lies
    outside the slice are not counted."""
    from benchmark import trace
    from benchmark.runners import serve_latent_cell as cell

    dev, ops, mods = "/device:TPU:0", trace.OP_LINE, trace.MODULE_LINE
    ev = trace.Event
    kernel = ("%mla_prefill_flash.3 = (bf16[1,128,2048,128]) "
              "custom-call(s32[1] %a), custom_call_target=\"tpu_custom_call\"")
    scores = "%fusion.7 = f32[16,128,8192] fusion(bf16[16,128,576] %q)"
    loop = "%while.2 = (s32[], f32[16,128,8192]) while(%tuple.1)"
    other = "%fusion.9 = bf16[16,7168] fusion(bf16[16,7168] %x)"
    events = [
        ev("/host:CPU", "python3", trace.BEGIN, 1000.0, 0.0),
        ev("/host:CPU", "python3", trace.END, 9000.0, 0.0),
        ev(dev, ops, kernel, 500.0, 1000.0),        # half before the slice
        ev(dev, ops, kernel, 2000.0, 1000.0),
        ev(dev, ops, scores, 4000.0, 300.0),
        ev(dev, ops, loop, 4000.0, 2000.0),
        ev(dev, ops, other, 4400.0, 100.0),
        ev(dev, ops, scores, 9500.0, 300.0),        # after the slice
        ev("/device:TPU:1", ops, scores, 4000.0, 300.0),
        ev(dev, mods, "jit__paged_decode_fn(11)", 3900.0, 700.0),
        ev(dev, mods, "jit__paged_decode_fn(11)", 9400.0, 500.0),
    ]
    seconds, top = cell.scope_seconds(events, False, 16, 128)
    assert abs(seconds["mla_prefill_attention"] - 1500e-9) < 1e-15
    assert abs(seconds["mla_decode_attention"] - 300e-9) < 1e-15
    assert top["mla_prefill_attention"][0][0] == (
        "mla_prefill_flash.3 custom-call")
    got = cell.program_ops(events, "jit__paged_decode_fn")
    assert abs(got["run_ms"] - 700e-6) < 1e-12
    assert [name for name, _ in got["ops_ms"]] == [
        "fusion.7 fusion", "fusion.9 fusion"]
    assert abs(got["ops_total_ms"] - 400e-6) < 1e-12
    # the parent's program, or a run without such work: nothing, no error
    assert cell.scope_seconds(events[:2], False, 16, 128) == ({}, {})
    assert cell.program_ops(events[:2], "jit__paged_decode_fn") == {}
