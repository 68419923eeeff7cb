"""MiMo-V2 (models/mimo_v2.py, ops/swa_attention.py) against its plain
reference (benchmark/reference/mimo_v2.py) at toy widths that keep what is
distinctive: keys wider than values (24 / 16), two K/V head counts (2
global, 4 window under 8 query heads), rotary on a part of the key (8 of
24), a window (8) far shorter than the rows, 16 experts of which a share
is held."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mimo_v2 as ref
from llm_in_practise_tpu.models import mimo_v2 as mm
from llm_in_practise_tpu.ops import swa_attention as swa

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def tiny():
    cfg = mm.mimo_v2_config(compute_dtype="float32", experts_held=4,
                            expert_offset=4)
    # sharper than N(0, 0.02): attention and routing must not be flat
    params = mm.random_params(cfg, 3, jnp.float32, std=0.2)
    ids = np.random.default_rng(0).integers(4, cfg.vocab_size, 37)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.Reference(ref.geometry(cfg)).logits(
            params, ids.tolist(), last=len(ids))
    return cfg, params, ids, want


def _apply(cfg, params, ids, **kw):
    with jax.default_matmul_precision("highest"):
        return mm.MiMoV2(cfg).apply({"params": params},
                                    jnp.asarray(ids)[None], **kw)


def test_full_forward_logits_match_reference(tiny):
    cfg, params, ids, want = tiny
    got = np.asarray(_apply(cfg, params, ids)[0])
    assert np.abs(got - want).max() < 1e-4 * np.std(want)


@pytest.mark.parametrize("wrong, change", [
    ("band of 7", {"window": 7}), ("band of 9", {"window": 9}),
    ("rotary on every dimension", {"partial_rotary_factor": 1.0}),
    ("value scale left out", {"attention_value_scale": 1.0}),
    ("one rotary base for both kinds", {"swa_rope_theta": 10_000_000.0}),
])
def test_a_wrong_equation_does_not_match(tiny, wrong, change):
    cfg, params, ids, want = tiny
    got = np.asarray(_apply(cfg.replace(**change), params, ids)[0])
    assert np.abs(got - want).max() > 1e-2 * np.std(want), wrong


def test_a_dropped_sink_does_not_match(tiny):
    cfg, params, ids, want = tiny
    dropped = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.full_like(a, -1e9)
        if "attention_sink_bias" in jax.tree_util.keystr(path) else a,
        params)
    got = np.asarray(_apply(cfg, dropped, ids)[0])
    assert np.abs(got - want).max() > 1e-2 * np.std(want)


@pytest.mark.parametrize("prefill", [5, 8, 21])
def test_prefill_then_decode_through_the_cache(tiny, prefill):
    """The ring wraps (window 8) and every later position's logits are
    the whole forward's."""
    cfg, params, ids, want = tiny
    cache = mm.MiMoV2(cfg).init_cache(1, 64, dtype=jnp.float32)
    assert [c["k"].shape[1:] for c in cache] == [
        (64, 2 * 24), (8, 4, 24), (8, 4, 24), (64, 2 * 24)]
    lg, cache = _apply(cfg, params, ids[:prefill], cache=cache)
    got = [np.asarray(lg[0])]
    for t in range(prefill, len(ids)):
        lg, cache = _apply(cfg, params, ids[t:t + 1], cache=cache)
        got.append(np.asarray(lg[0]))
    assert np.abs(np.concatenate(got) - want).max() < 1e-4 * np.std(want)


@pytest.mark.parametrize("chunk", [8, 16])
def test_padded_chunks_write_only_their_real_rows(tiny, chunk):
    """Chunks with ``valid`` < their width (the serving programs' padded
    last chunk): the ring takes the real positions only."""
    cfg, params, ids, want = tiny
    cache = mm.MiMoV2(cfg).init_cache(1, 64, dtype=jnp.float32)
    pos, got = 0, []
    while pos < 29:
        valid = min(chunk - (1 if pos else 0), 29 - pos)
        row = np.zeros(chunk, np.int64)
        row[:valid] = ids[pos:pos + valid]
        pinned = [dict(c, index=jnp.asarray([pos]),
                       valid=jnp.asarray([valid])) for c in cache]
        lg, cache = _apply(cfg, params, row, cache=pinned)
        got.append(np.asarray(lg[0, :valid]))
        pos += valid
    cache = [dict(c, index=jnp.asarray([pos]), valid=None) for c in cache]
    for t in range(pos, len(ids)):
        lg, cache = _apply(cfg, params, ids[t:t + 1], cache=cache)
        got.append(np.asarray(lg[0]))
    assert np.abs(np.concatenate(got) - want).max() < 1e-4 * np.std(want)


def test_a_dead_row_writes_nothing_into_its_ring(tiny):
    cfg, params, ids, _ = tiny
    cache = mm.MiMoV2(cfg).init_cache(2, 64, dtype=jnp.float32)
    cache = [dict(c, k=c["k"] + 3.0, index=jnp.asarray([9, 9]),
                  valid=jnp.asarray([1, 0])) for c in cache]
    with jax.default_matmul_precision("highest"):
        _, new = mm.MiMoV2(cfg).apply(
            {"params": params}, jnp.asarray([[5], [5]]), cache=cache)
    for layer in (1, 2):
        assert (np.asarray(new[layer]["k"][1]) == 3.0).all()
        assert (np.asarray(new[layer]["k"][0, 9 % 8]) != 3.0).any()


# ------------------------------------------------------------ the kernel


def _dense(q, k, v, q_start, k_start, scale, window, sink):
    """(B, L, H, D) operands: masked dense softmax, the sink one more
    logit a head whose probability is dropped."""
    b, lq, h, _ = q.shape
    lk, hk = k.shape[1], k.shape[2]
    kk = jnp.repeat(k, h // hk, axis=2)
    vv = jnp.repeat(v, h // hk, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * scale
    qp = q_start + jnp.arange(lq)[:, None]
    kp = k_start + jnp.arange(lk)[None, :]
    seen = (kp <= qp) & (kp >= 0)
    if window is not None:
        seen &= qp - kp < window
    s = jnp.where(seen[None, None], s, -jnp.inf)
    if sink is not None:
        s = jnp.concatenate([s, jnp.broadcast_to(
            sink[None, :, None, None], (b, h, lq, 1))], axis=-1)
    p = jax.nn.softmax(s, axis=-1)[..., :lk]
    return jnp.einsum("bhqk,bkhd->bqhd", jnp.nan_to_num(p), vv)


@pytest.mark.parametrize("window", [None, 8, 24])
@pytest.mark.parametrize("hk", [1, 2])        # 16 and 8 query heads a group
@pytest.mark.parametrize("sink", [False, True])
def test_kernel_matches_dense_attention(window, hk, sink, monkeypatch):
    """The Pallas kernel in interpret mode, small tiles so that a band
    spans several key blocks: band x sink x groups {16, 8} x Dq != Dv."""
    monkeypatch.setattr(swa, "GLOBAL_BLOCKS", (16, 16))
    monkeypatch.setattr(swa, "WINDOW_BLOCKS", (16, 8))
    h, dq, dv, lq, start = 16, 24, 16, 48, 32
    key = jax.random.split(jax.random.PRNGKey(window or 0), 6)
    q = jax.random.normal(key[0], (1, lq, h, dq))
    b_h = jax.random.normal(key[5], (h,)) if sink else None
    scale = dq ** -0.5
    with jax.default_matmul_precision("highest"):
        if window is None:
            k = jax.random.normal(key[1], (1, start + lq, hk, dq))
            v = jax.random.normal(key[2], (1, start + lq, hk, dv))
            got = swa.prefill_attention(q, k, v, start, scale=scale,
                                        sink=b_h)
            want = _dense(q, k, v, start, 0, scale, None, b_h)
        else:
            # the ring holds the `rows` positions before `start`, in ring
            # order; the dense twin sees them in position order
            rows = min(window, 16)
            past_k = jax.random.normal(key[3], (1, rows, hk, dq))
            past_v = jax.random.normal(key[4], (1, rows, hk, dv))
            order = (start - rows + np.arange(rows)) % rows
            ring_k = jnp.zeros_like(past_k).at[:, order].set(past_k)
            ring_v = jnp.zeros_like(past_v).at[:, order].set(past_v)
            k = jax.random.normal(key[1], (1, lq, hk, dq))
            v = jax.random.normal(key[2], (1, lq, hk, dv))
            got = swa.prefill_attention(
                q, k, v, start, scale=scale, window=min(window, rows),
                sink=b_h, cached=(ring_k, ring_v))
            want = _dense(q, jnp.concatenate([past_k, k], axis=1),
                          jnp.concatenate([past_v, v], axis=1), start,
                          start - rows, scale, min(window, rows), b_h)
    assert np.abs(np.asarray(got - want)).max() < 2e-5


def test_a_sink_of_minus_infinity_is_the_plain_softmax():
    key = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(key[0], (2, 1, 8, 24))
    k = jax.random.normal(key[1], (2, 8, 4, 24))
    v = jax.random.normal(key[2], (2, 8, 4, 16))
    index = jnp.asarray([20, 3])
    plain = swa.ring_decode_attention(q, k, v, index, scale=0.2, window=8)
    sunk = swa.ring_decode_attention(q, k, v, index, scale=0.2, window=8,
                                     sink=jnp.full((8,), -jnp.inf))
    real = swa.ring_decode_attention(q, k, v, index, scale=0.2, window=8,
                                     sink=jnp.zeros((8,)))
    assert np.abs(np.asarray(plain - sunk)).max() < 1e-6
    assert np.abs(np.asarray(plain - real)).max() > 1e-3
    # a row at position 3 attends 4 ring rows, whatever the others hold
    k2 = k.at[1, 4:].set(1e3)
    again = swa.ring_decode_attention(q, k2, v, index, scale=0.2, window=8)
    assert np.abs(np.asarray(plain[1] - again[1])).max() < 1e-6


@pytest.mark.parametrize("window, block_q, block_k", [
    (None, 16, 16), (8, 16, 8), (8, 16, 16), (24, 16, 8), (128, 512, 256)])
def test_the_band_skips_the_blocks_under_it(window, block_q, block_k):
    """The key axis of the kernel's grid is as long as a band can touch,
    and the host's count of computed blocks covers every block with a
    live pair and little else."""
    lq = 4 * block_q
    start = 3 * block_q + 5
    k_start = start if window is not None else 0
    n_keys = lq if window is not None else (
        -(-(start + lq) // block_k) * block_k)
    visited = swa.key_blocks_visited(start, k_start, lq, n_keys,
                                     window=window, block_q=block_q,
                                     block_k=block_k)
    live = 0
    for i in range(lq // block_q):
        q0 = start + i * block_q
        for j in range(n_keys // block_k):
            k0 = k_start + j * block_k
            lo = q0 - (window - 1) if window is not None else -1
            if k0 <= q0 + block_q - 1 and k0 + block_k - 1 >= max(lo, 0):
                live += 1
    assert live <= visited <= live + lq // block_q
    if window is not None:
        steps = swa.band_blocks(block_q, block_k, window)
        assert visited <= steps * (lq // block_q)
        q = jax.ShapeDtypeStruct((1, 2, lq, 24), jnp.float32)
        kv = jax.ShapeDtypeStruct((1, 1, n_keys, 24), jnp.float32)
        jaxpr = jax.make_jaxpr(lambda q, k, v: swa.flash_partial(
            q, k, v, start, k_start, scale=1.0, window=window,
            block_q=block_q, block_k=block_k))(q, kv, kv)
        (call,) = [e for e in jaxpr.jaxpr.eqns
                   if e.primitive.name == "pallas_call"]
        assert call.params["name"] == swa.WINDOW_KERNEL
        # the two query heads of the one K/V head share a q tile
        assert call.params["grid_mapping"].grid == (
            1, 1, lq // block_q, min(n_keys // block_k, steps))


# -------------------------------------------------------------- routing


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Guide section 4: 16 experts over 4 chips. Each share routes over
    all 16 and computes its own 4; the four parts (no shared expert)
    equal the uncut reference's layer output."""
    cfg = mm.mimo_v2_config(compute_dtype="float32")
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, cfg.hidden_size))
    params = mm.RoutedExperts(cfg).init(jax.random.PRNGKey(6), x)["params"]
    params = jax.tree.map(lambda a: 4.0 * a, params)    # sharper routing
    flat = x[0]
    with jax.default_matmul_precision("highest"):
        total, seen = 0.0, 0
        for rank in range(4):
            share = dict(params, **{
                k: params[k][4 * rank:4 * (rank + 1)]
                for k in ("w_gate", "w_up", "w_down")})
            y, ids, counts = mm.RoutedExperts(cfg.replace(
                experts_held=4, expert_offset=4 * rank)).apply(
                {"params": share}, x)
            total = total + y[0]
            seen += int(counts.sum())
        assert seen == 24 * cfg.n_experts_per_tok   # every assignment once
        geom = dict(ref.geometry(cfg), held=(0, 16))
        s, biased = ref.router_scores(flat, params)
        dense, _ = ref.choose(np.asarray(s), np.asarray(biased), geom,
                              None, 0)
        want = ref.held_experts(flat, jnp.asarray(dense), params)
    assert np.abs(np.asarray(total - want)).max() < 1e-4
    assert float(jnp.abs(want).max()) > 1e-3
    assert np.allclose(dense.sum(axis=1), 1.0, atol=1e-5)   # norm_topk


# ------------------------------------------------------- from_hf_config


def _row():
    return next(json.loads(line) for line in open(CATALOG)
                if '"name": "MiMo-V2.5"' in line)


def test_from_hf_config_on_the_catalog_row():
    cfg = mm.MiMoV2Config.from_hf_config(_row()["config"])
    assert (cfg.n_layer, cfg.n_head, cfg.n_kv_head, cfg.swa_n_kv_head) == (
        48, 64, 4, 8)
    assert (cfg.head_dim, cfg.v_head_dim, cfg.window, cfg.rotary_dim) == (
        192, 128, 128, 64)
    assert sum(cfg.hybrid_layer_pattern) == 39 and cfg.held == (0, 256)
    assert (cfg.rope_theta, cfg.swa_rope_theta, cfg.rms_norm_eps) == (
        1e7, 1e4, 1e-5)
    assert cfg.routed_scaling_factor == 1.0 and not cfg.is_routed(0)
    count = lambda tree: sum(int(np.prod(a.shape))  # noqa: E731
                             for a in jax.tree.leaves(tree))
    x = jnp.zeros((1, 8, cfg.hidden_size))
    small = cfg.replace(max_seq_len=16)
    for window, want in ((False, 89_128_960), (True, 94_371_840 + 64)):
        attn = jax.eval_shape(lambda w=window: mm.HybridAttention(
            small, w).init(jax.random.PRNGKey(0), x))
        assert count(attn) == want      # a window layer's 64 sinks on top
    moe = jax.eval_shape(lambda: mm.RoutedExperts(cfg.replace(
        experts_held=16)).init(jax.random.PRNGKey(0), x))["params"]
    assert count({k: moe[k] for k in ("w_gate", "w_up", "w_down")}) == (
        402_653_184)
    assert "shared" not in moe and moe["router"].shape == (4096, 256)


def test_every_key_of_the_catalog_row_is_read_or_refused():
    """No key of the row is silently ignored: each is consumed by
    ``from_hf_config`` (a changed value changes the configuration or is
    refused) or named among what is read and not applied."""
    row = _row()["config"]
    not_applied = {"attention_chunk_size", "attention_projection_layout",
                   "model_type"}
    base = mm.MiMoV2Config.from_hf_config(row)
    other = {"hidden_act": "gelu", "scoring_func": "softmax",
             "topk_method": "greedy", "hybrid_block_size": 4,
             "rope_scaling": {"rope_type": "yarn", "type": "yarn"},
             "n_shared_experts": 1, "routed_scaling_factor": 2.0,
             "hybrid_layer_pattern": [1] * 48, "moe_layer_freq": [1] * 48}
    for key, value in row.items():
        if key in not_applied:
            continue
        if key in other:
            changed = other[key]
        elif isinstance(value, bool):
            changed = not value
        else:
            changed = value * 2
        try:
            got = mm.MiMoV2Config.from_hf_config(dict(row, **{key: changed}))
        except ValueError:
            continue
        assert got != base, key


@pytest.mark.parametrize("change, match", [
    ({"add_full_attention_sink_bias": True}, "add_full_attention_sink_bias"),
    ({"n_shared_experts": 1}, "n_shared_experts"),
    ({"n_group": 8, "topk_group": 4}, "n_group"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"swa_head_dim": 128}, "swa_head_dim"),
])
def test_from_hf_config_refuses_what_it_does_not_implement(change, match):
    with pytest.raises(ValueError, match=match):
        mm.MiMoV2Config.from_hf_config(dict(_row()["config"], **change))


def test_the_benchmark_configuration_builds_the_cut():
    """benchmark/configs/mimo-v2.5-ep16-bf16-serve.json through the
    runner's ``model_config``: the published widths, seven layers, 16 of
    256 experts held; weights 6.86 GB in bf16."""
    from benchmark.runners import serve_hybrid_cell as cell

    with open("benchmark/configs/mimo-v2.5-ep16-bf16-serve.json") as f:
        config = json.load(f)
    cfg = cell.model_config(config)
    assert cfg.hybrid_layer_pattern == (0, 1, 1, 1, 1, 0, 1)
    assert cfg.held == (0, 16) and cfg.n_routed_experts == 256
    row = _row()["config"]
    changed = {k for k in row if config[k] != row[k]}
    assert changed == {"num_hidden_layers", "hybrid_layer_pattern",
                       "moe_layer_freq", "n_routed_experts", "vocab_size",
                       "max_position_embeddings"}
    shapes = jax.eval_shape(lambda: mm.MiMoV2(cfg).init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"])
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert abs(2 * n - 6.86e9) < 0.02e9
    cache = jax.eval_shape(lambda: mm.MiMoV2(cfg).init_cache(16, 32768))
    assert cache[0]["k"].shape == (16, 32768, 768)      # one vector a row
    assert cache[1]["k"].shape == (16, 128, 8, 192)
    grows = sum(c[k].size * 2 for c in cache if c["k"].shape[1] == 32768
                for k in "kv")
    rings = sum(c[k].size * 2 for c in cache if c["k"].shape[1] == 128
                for k in "kv")
    assert grows == 16 * 32768 * 5120 and rings == 16 * 128 * 25600


def _control_reading(rms, shortfall, flips):
    """What ``serve_hybrid_cell.check`` prints: readings beside limits."""
    return {"ok": False,
            "worst": {"rms_over_std": rms, "max_over_std": 0.05,
                      "token_margin_over_std": 0.01},
            "routing": {"pairs": 192, "flipped": flips,
                        "outside_margin": int(shortfall > ref.ROUTE_MARGIN),
                        "worst_shortfall": shortfall,
                        "flip_share": flips / 192},
            "tolerances": {"rms": ref.LOGIT_RMS_TOL, "max": ref.LOGIT_MAX_TOL,
                           "token_margin": ref.TOKEN_MARGIN_TOL,
                           "route_margin": ref.ROUTE_MARGIN,
                           "route_flip_share": ref.ROUTE_FLIP_SHARE_TOL}}


@pytest.mark.parametrize("reading, failed", [
    # the chip's sound maxima (PERF.md section 6): inside every limit
    ((0.0137, 0.0047, 27), []),
    # the fp8 control's least shortfall, alone: the route margin tells
    ((0.0137, 0.0243, 27), ["route_margin"]),
    # the fp8 control's first seed as it read
    ((0.0756, 0.0243, 99), ["rms", "route_margin", "route_flip_share"]),
])
def test_the_control_names_the_limits_a_reading_breaks(reading, failed):
    """tools/swa_check_control.py::limits_failed on hand-made readings:
    every limit lies between the sound runs' largest reading and the fp8
    control's smallest, the route margin too."""
    from tools.swa_check_control import limits_failed

    assert limits_failed(_control_reading(*reading)) == failed
    assert limits_failed({"ok": False, "why": "a probe is incomplete"}) == []
    assert 0.0047 * 2 < ref.ROUTE_MARGIN < 0.0243 / 1.5
