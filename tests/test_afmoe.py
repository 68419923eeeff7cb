"""Arcee Trinity (models/afmoe.py, the ring's part of a chunk in
ops/swa_attention.py) against its plain reference
(benchmark/reference/afmoe.py) at toy widths that keep what is
distinctive: window layers (rotary, a ring; one dense, one routed) before
a global layer (no position), a gate on the attention output, QK-norm,
four norms a layer, the muP factor, 16 experts of which a share is held
beside a shared one."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import afmoe as ref
from llm_in_practise_tpu.models import afmoe as am
from llm_in_practise_tpu.ops import swa_attention as swa

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def tiny():
    # two window layers (one dense, one routed) and a routed global one
    cfg = am.afmoe_config(compute_dtype="float32", experts_held=4,
                          expert_offset=4, n_layer=3,
                          window_layers=(True, True, False))
    # sharper than N(0, 0.02): attention, gates and routing must not be flat
    params = am.random_params(cfg, 3, jnp.float32, std=0.2)
    ids = np.random.default_rng(0).integers(4, cfg.vocab_size, 37)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.Reference(ref.geometry(cfg)).logits(
            params, ids.tolist(), last=len(ids))
        # jitted: op by op, the interpreted kernels take four times as long
        got = np.asarray(jax.jit(lambda p, row: am.Afmoe(cfg).apply(
            {"params": p}, row))(params, jnp.asarray(ids)[None])[0])
    return cfg, params, ids, want, got


def test_full_forward_logits_match_reference(tiny):
    _, _, _, want, got = tiny
    assert np.abs(got - want).max() < 1e-4 * np.std(want)


@pytest.mark.parametrize("left_out, change", [
    ("the gate", {"gate": False}),
    ("QK-norm", {"qk_norm": False}),
    *[(name, {"norms": tuple(n for n in ref.NORMS if n != name)})
      for name in ref.NORMS],
    ("the muP factor", {"embed_scale": 1.0}),
    ("rotary on a window layer", {"rotary": (False, True, False)}),
    ("no rotary on the global layer", {"rotary": (True,) * 3}),
])
def test_a_piece_left_out_does_not_match(tiny, left_out, change):
    """The model against a reference that lacks one piece of the layer
    (or turns the global layer's q and k): the comparison must fail."""
    cfg, params, ids, want, got = tiny
    # op by op: ten variants share every compiled primitive
    with jax.disable_jit(), jax.default_matmul_precision("highest"):
        wrong, _ = ref.Reference(dict(ref.geometry(cfg), **change)).logits(
            params, ids.tolist(), last=len(ids))
    assert np.abs(wrong - want).max() > 1e-2 * np.std(want), left_out
    assert np.abs(got - wrong).max() > 1e-2 * np.std(want), left_out


@pytest.mark.parametrize("window, chunk", [(8, 16), (24, 16)])
def test_prefill_then_decode_through_rings_that_wrap(tiny, monkeypatch,
                                                     window, chunk):
    """Chunks (the last one padded) then single tokens through the
    cache, the ring's part of every chunk through the KERNEL (a toy ring
    fits the dense corner: the threshold is lowered here), with a window
    shorter than the chunk and one longer: every position's logits are
    the whole forward's."""
    monkeypatch.setattr(swa, "RING_CORNER_MAX", 0)
    cfg, params, ids, _, _ = tiny
    cfg = cfg.replace(window=window)
    model = am.Afmoe(cfg)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.Reference(ref.geometry(cfg)).logits(
            params, ids.tolist(), last=len(ids))
        step = jax.jit(lambda row, cache: model.apply(
            {"params": params}, row, cache=cache))
        cache = model.init_cache(1, 64, dtype=jnp.float32)
        assert [c["k"].shape[1:] for c in cache] == (
            [(window, 2, 16)] * 2 + [(64, 2 * 16)])
        pos, got = 0, []
        while pos < 29:
            valid = min(chunk, 29 - pos)
            row = np.zeros((1, chunk), np.int64)
            row[0, :valid] = ids[pos:pos + valid]
            pinned = [dict(c, index=jnp.asarray([pos]),
                           valid=jnp.asarray([valid])) for c in cache]
            lg, cache = step(jnp.asarray(row), pinned)
            got.append(np.asarray(lg[0, :valid]))
            pos += valid
        cache = [dict(c, index=jnp.asarray([pos]), valid=jnp.asarray([1]))
                 for c in cache]
        for t in range(pos, len(ids)):
            lg, cache = step(jnp.asarray(ids[t:t + 1])[None], cache)
            got.append(np.asarray(lg[0]))
    assert np.abs(np.concatenate(got) - want).max() < 1e-4 * np.std(want)


def test_a_padded_chunk_reports_its_last_real_tokens_experts(tiny):
    """The step statistics' routed sets are those of each row's last REAL
    position (``valid - 1``), where the program's last-position logits
    come from, not of the row's last position (padding)."""
    cfg, params, ids, _, _ = tiny
    model = am.Afmoe(cfg)

    @jax.jit
    def routed(row, valid):
        cache = [dict(c, **more, index=jnp.zeros((1,), jnp.int32),
                      valid=valid)
                 for c, more in zip(model.init_cache(1, 32, jnp.float32),
                                    model.step_stats(1))]
        (_, cache), sown = model.apply({"params": params}, row, cache=cache,
                                       mutable=["routing"])
        every = [sown["routing"][f"block_{i}"]["moe"]["experts"][0]
                 for i in range(cfg.n_layer) if cfg.is_routed(i)]
        return [c[am.ROUTE_KEY][0] for c in cache if am.ROUTE_KEY in c], every

    row = np.zeros((1, 16), np.int64)
    row[0, :11] = ids[:11]
    with jax.default_matmul_precision("highest"):
        got, every = routed(jnp.asarray(row), jnp.asarray([11]))
    assert len(got) == 2
    for reported, ids_of_layer in zip(got, every):
        assert (np.asarray(reported) == np.asarray(ids_of_layer[10])).all()
    assert any((np.asarray(r) != np.asarray(e[15])).any()
               for r, e in zip(got, every))


# ------------------------------------------------- the ring's part of a chunk


def _dense_band(q, keys, vals, q_start, scale, window):
    """(L, H, D) queries at ``q_start ..`` over the WHOLE history ``keys``
    / ``vals`` (n, Hk, D) from position 0: masked dense softmax."""
    h, hk = q.shape[1], keys.shape[1]
    kk, vv = (jnp.repeat(t, h // hk, axis=1) for t in (keys, vals))
    s = jnp.einsum("qhd,khd->hqk", q, kk) * scale
    qp = q_start + jnp.arange(q.shape[0])[:, None]
    kp = jnp.arange(keys.shape[0])[None, :]
    seen = (kp <= qp) & (qp - kp < window)
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, vv)


@pytest.mark.parametrize("window, chunk, rows, corner", [
    (24, 16, 24, False),    # the window outlasts the chunk: the kernel
    (24, 16, 24, True),     # ... and the dense corner, where it is small
    (8, 16, 8, False),      # a window shorter than the chunk
    (40, 16, 32, False),    # a cache shorter than the window: R = max_len
])
def test_a_chunk_over_its_ring_and_its_own_keys(monkeypatch, window, chunk,
                                                rows, corner):
    """Two rows at different depths (one whose ring has not wrapped and
    still holds a last tenant's rows, one that wrapped twice): the chunk's
    attention over [ring ‖ own keys] is the dense band's over the whole
    history, by the kernel and by the corner alike."""
    if not corner:
        monkeypatch.setattr(swa, "RING_CORNER_MAX", 0)
    h, hk, d = 6, 2, 16
    # a cache shorter than the window never holds more than its rows
    starts = [5, 2 * rows + 3] if rows == window else [5, rows - chunk]
    rng = np.random.default_rng(window + chunk)
    hist_k, hist_v = (rng.standard_normal((2, starts[1] + chunk, hk, d))
                      .astype(np.float32) for _ in range(2))
    q = rng.standard_normal((2, chunk, h, d)).astype(np.float32)
    # the rings as they stand before the chunk; rows that hold nothing of
    # this sequence hold a last tenant's values (finite, never attended)
    ring_k, ring_v = (np.full((2, rows, hk, d), 7.0, np.float32)
                      for _ in range(2))
    for b, start in enumerate(starts):
        for p in range(max(start - rows, 0), start):
            ring_k[b, p % rows], ring_v[b, p % rows] = (
                hist_k[b, p], hist_v[b, p])
    own_k = np.stack([hist_k[b, s:s + chunk] for b, s in enumerate(starts)])
    own_v = np.stack([hist_v[b, s:s + chunk] for b, s in enumerate(starts)])
    with jax.default_matmul_precision("highest"):
        got = swa.prefill_attention(
            jnp.asarray(q), jnp.asarray(own_k), jnp.asarray(own_v),
            jnp.asarray(starts), scale=d ** -0.5, window=window,
            cached=(jnp.asarray(ring_k), jnp.asarray(ring_v)))
        for b, start in enumerate(starts):
            want = _dense_band(jnp.asarray(q[b]),
                               jnp.asarray(hist_k[b, :start + chunk]),
                               jnp.asarray(hist_v[b, :start + chunk]),
                               start, d ** -0.5, window)
            assert np.abs(np.asarray(got[b]) - np.asarray(want)).max() < 1e-5


def test_the_corner_stays_where_it_is_small_and_tiles_follow_the_band():
    # MiMo-V2's 128 x 128 corner stays dense; 2,048 x 4,096 may not
    assert 128 * 128 <= swa.RING_CORNER_MAX < 2048 * 4096
    assert swa.window_blocks(128) == swa.WINDOW_BLOCKS == (256, 256)
    assert swa.window_blocks(4096) == swa.LONG_BAND_BLOCKS
    bq, bk = swa.LONG_BAND_BLOCKS
    # a chunk at 12,288 over [ring ‖ own]: the blocks outside the band are
    # not visited (a full causal sweep of 6,144 keys would be 8 x 6 - ...)
    visited = swa.key_blocks_visited(12288, 8192, 2048, 6144, window=4096,
                                     block_q=bq, block_k=bk)
    assert visited <= (2048 // bq) * swa.band_blocks(bq, bk, 4096)
    assert visited * bq * bk < 1.5 * 2048 * 4096
    ring = jnp.arange(8.0).reshape(1, 8, 1)
    new = 100 + jnp.arange(4.0).reshape(1, 4, 1)
    run, k0 = swa.ring_stretch(ring, new, jnp.asarray([11]))
    # positions 3 .. 10 from rows 3 .. 7, 0 .. 2; then the stretch
    assert int(k0[0]) == 3
    assert np.asarray(run[0, :, 0]).tolist() == [
        3, 4, 5, 6, 7, 0, 1, 2, 100, 101, 102, 103]


# ---------------------------------------------------------------- the share


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Guide section 4: 16 experts over 8 chips. Each share routes over
    all 16 and computes its own 2; the eight routed parts plus the shared
    expert counted ONCE equal the uncut reference's layer output."""
    cfg = am.afmoe_config(compute_dtype="float32")
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, cfg.hidden_size))
    params = am.RoutedExperts(cfg).init(jax.random.PRNGKey(6), x)["params"]
    params = jax.tree.map(lambda a: 8.0 * a, params)    # sharper routing
    flat = x[0]
    with jax.default_matmul_precision("highest"):
        shared = ref.swiglu(flat, params["shared"])
        total, seen = shared, 0
        for rank in range(8):
            share = dict(params, **{
                k: params[k][2 * rank:2 * (rank + 1)]
                for k in ("w_gate", "w_up", "w_down")})
            y, _, counts = am.RoutedExperts(cfg.replace(
                experts_held=2, expert_offset=2 * rank)).apply(
                {"params": share}, x)
            total = total + (y[0] - shared)
            seen += int(counts.sum())
        assert seen == 24 * cfg.n_experts_per_tok   # every assignment once
        s, biased = ref.router_scores(flat, params)
        dense, _ = ref.choose(np.asarray(s), np.asarray(biased),
                              ref.geometry(cfg), None, 0)
        # the weights: the chosen sigmoids over their sum, x route_scale
        assert np.allclose(dense.sum(axis=1), cfg.route_scale, atol=1e-5)
        want = ref.held_experts(flat, jnp.asarray(dense), params) + shared
    assert np.abs(np.asarray(total - want)).max() < 1e-4
    assert float(jnp.abs(want - shared).max()) > 1e-3   # the experts count


# ------------------------------------------------------------ the config


def _catalog_row() -> dict:
    with open(CATALOG, encoding="utf-8") as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Trinity-Large-Preview":
                return row["config"]
    raise AssertionError("the catalog has no Trinity-Large-Preview")


def test_from_hf_config_reads_the_catalog_row():
    cfg = am.AfmoeConfig.from_hf_config(_catalog_row())
    assert (cfg.n_layer, cfg.hidden_size, cfg.n_head, cfg.n_kv_head,
            cfg.head_dim, cfg.window) == (60, 3072, 48, 8, 128, 4096)
    assert sum(cfg.window_layers) == 45 and not cfg.is_window(3)
    assert all(cfg.is_window(i) for i in (0, 1, 2, 4, 8, 9, 10))
    assert (cfg.n_dense_layers, cfg.n_routed_experts, cfg.n_experts_per_tok,
            cfg.n_shared_experts) == (6, 256, 4, 1)
    assert not cfg.is_routed(5) and cfg.is_routed(6)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size) == (12288, 3072)
    assert cfg.route_scale == 2.448 and cfg.route_norm and cfg.mup_enabled
    assert abs(cfg.embed_scale - 3072 ** 0.5) < 1e-9
    assert cfg.held == (0, 256) and cfg.rope_theta == 10000.0
    held = am.AfmoeConfig.from_hf_config(
        dict(_catalog_row(), experts_held=32, expert_offset=64))
    assert held.held == (64, 32)


@pytest.mark.parametrize("key, value", [
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}),
    ("score_func", "softmax"), ("n_group", 8), ("topk_group", 2),
    ("num_expert_groups", 4), ("num_limited_groups", 2),
    ("hidden_act", "gelu"), ("tie_word_embeddings", True),
    ("layer_types", ["sliding_attention", "chunked_attention"] * 30),
])
def test_from_hf_config_refuses_by_name(key, value):
    with pytest.raises(ValueError, match=f"afmoe: {key}"):
        am.AfmoeConfig.from_hf_config(dict(_catalog_row(), **{key: value}))


def test_the_benchmark_configuration_is_the_rows_cut():
    """benchmark/configs/trinity-large-ep8-bf16-serve.json: every number
    of the catalog row under its key, but the keys it lists as changed."""
    with open("benchmark/configs/trinity-large-ep8-bf16-serve.json",
              encoding="utf-8") as f:
        mine = json.load(f)
    with open("BENCHMARK.json", encoding="utf-8") as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "trinity-large-ep8-bf16-serve")
    row = _catalog_row()
    changed = {k for k in row if mine[k] != row[k]}
    assert changed == set(entry["reduced"]) == set(mine["changed_from_source"])
    assert {k: mine["published"][k] for k in changed} == {
        k: row[k] for k in changed}
    from benchmark.runners.serve_window_ring_cell import model_config

    cfg = model_config(mine)
    assert cfg.held == (0, 32) and cfg.n_routed_experts == 256
    assert cfg.window_layers == (True, True, True, True, False)
    assert [cfg.is_routed(i) for i in range(5)] == [False] + [True] * 4
    shapes = jax.eval_shape(lambda: am.Afmoe(cfg).init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"])
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert abs(n - 4.3219e9) < 1e6      # 8.64 GB in bf16
