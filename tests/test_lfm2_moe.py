"""LFM2-MoE (``models/lfm2_moe.py``) against the plain reference
(``benchmark/reference/lfm2_moe.py``) at a toy of the model's SHAPE: six
layers ``conv conv attn conv conv conv``, two dense, 8 experts top-2,
hidden 64; float32 on the CPU. The mixer's two-row tail at every split
point, padding and dead rows, a fresh slot, the published router, and
``route``'s older call shapes bit for bit."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_moe as ref
from llm_in_practise_tpu.models import layers
from llm_in_practise_tpu.models import lfm2_moe as lm
from llm_in_practise_tpu.ops.grouped_experts import route

LENGTH = 23


@pytest.fixture(scope="module")
def toy():
    cfg = lm.lfm2_moe_config(compute_dtype="float32")
    params = lm.random_params(cfg, 3, jnp.float32, std=0.1)
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, LENGTH)
    stores = {}
    with jax.default_matmul_precision("highest"):
        want = ref.Reference(ref.geometry(cfg)).logits(
            params, ids.tolist(), last=LENGTH, stores=stores)
    return cfg, params, ids, want, stores


@functools.lru_cache(maxsize=None)
def _forward(cfg):
    # jitted: an eager call pays every primitive's first compile (7 s a
    # new shape against 2 s)
    return jax.jit(lambda params, ids, cache: lm.Lfm2Moe(cfg).apply(
        {"params": params}, ids, cache=cache))


def _apply(cfg, params, ids, cache=None):
    with jax.default_matmul_precision("highest"):
        return _forward(cfg)(params, jnp.asarray(ids)[None], cache)


def _at(cache, pos, valid=None):
    extra = {} if valid is None else {
        layers.VALID_KEY: jnp.asarray([valid], jnp.int32)}
    return [dict(c, index=jnp.full((1,), pos, jnp.int32), **extra)
            for c in cache]


def _tails(cache):
    return [np.asarray(c["conv"][0]) for c in cache if "conv" in c]


def test_the_toy_has_the_models_shape(toy):
    cfg = toy[0]
    assert cfg.runs == (("conv", False, 0, 2), ("full_attention", True, 2, 1),
                        ("conv", True, 3, 3))
    assert cfg.held == (0, 8) and cfg.head_dim == 16


def test_whole_sequence_logits_are_the_references(toy):
    cfg, params, ids, want, _ = toy
    got = np.asarray(_apply(cfg, params, ids)[0])
    assert np.abs(got - want).max() < 2e-5 * np.std(want) + 1e-5


@pytest.mark.parametrize("split", range(1, LENGTH))
def test_the_mixer_chunked_at_every_split_point_equals_the_whole(toy, split):
    """The mixer alone, two calls that meet at ``split`` (chunks of 1 and 2
    rows among them): the tail carries ``g`` of the last two positions
    across, and what it holds at the end is the reference's."""
    cfg, params, _, _, _ = toy
    p = {k: v[0] for k, v in params["run_0"].items()}
    h = jnp.asarray(np.random.default_rng(split).normal(size=(1, LENGTH, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _ = lm.conv_mixer(cfg, p, h, None, None)
        want, tail = ref.conv_mixer(h[0], p["w_in"], p["conv_w"], p["w_out"],
                                    jnp.zeros((LENGTH,), bool), taps=3)
        entry = {"index": jnp.zeros((1,), jnp.int32),
                 "conv": jnp.full((1, 2, 64), 2.0, jnp.float32)}
        first, entry = lm.conv_mixer(cfg, p, h[:, :split], entry, None)
        second, entry = lm.conv_mixer(cfg, p, h[:, split:], entry, None)
    got = np.concatenate([np.asarray(first[0]), np.asarray(second[0])])
    np.testing.assert_allclose(got, np.asarray(whole[0]), atol=1e-5)
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(entry["conv"][0]), np.asarray(tail),
                               atol=1e-5)
    assert int(entry["index"][0]) == LENGTH


@pytest.mark.parametrize("split", [1, LENGTH - 1, 2, LENGTH - 2])
def test_the_model_chunked_equals_the_whole(toy, split):
    """The whole model, two calls that meet at ``split``: logits, tails and
    the attention layer's rows against the reference's. (A split and its
    mirror share their two programs: every compile here is seconds of the
    tier-1 run.)"""
    cfg, params, ids, want, stores = toy
    cache = lm.Lfm2Moe(cfg).init_cache(1, 32, dtype=jnp.float32)
    first, cache = _apply(cfg, params, ids[:split], _at(cache, 0))
    second, cache = _apply(cfg, params, ids[split:], _at(cache, split))
    got = np.concatenate([np.asarray(first[0]), np.asarray(second[0])])
    assert np.abs(got - want).max() < 1e-5
    for mine, theirs in zip(_tails(cache), stores["tail"]):
        np.testing.assert_allclose(mine, theirs, atol=1e-5)
    rows = [np.asarray(c["k"][0, :LENGTH]) for c in cache if "k" in c]
    np.testing.assert_allclose(rows[0], stores["pages"][0][0], atol=1e-5)


@pytest.mark.parametrize("valid", [0, 1, 2, 8])
def test_padding_and_dead_rows_leave_the_tail_alone(toy, valid):
    """A call of 8 positions of which ``valid`` are real leaves the tail
    of its last REAL position; none real: what it held, bit for bit."""
    cfg, params, ids, _, _ = toy
    model = lm.Lfm2Moe(cfg)
    cache = model.init_cache(1, 32, dtype=jnp.float32)
    _, cache = _apply(cfg, params, ids[:8], _at(cache, 0))
    before = _tails(cache)
    _, padded = _apply(cfg, params, ids[8:16], _at(cache, 8, valid=valid))
    if valid == 0:
        for a, b in zip(before, _tails(padded)):
            np.testing.assert_array_equal(a, b)
        return
    _, exact = _apply(cfg, params, ids[8:8 + valid], _at(cache, 8))
    for a, b in zip(_tails(exact), _tails(padded)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_a_fresh_slot_starts_from_zeros(toy):
    """A live call at position 0 ignores what the slot's last tenant left;
    a dead one keeps it."""
    cfg, params, ids, want, _ = toy
    cache = lm.Lfm2Moe(cfg).init_cache(1, 32, dtype=jnp.float32)
    dirty = [dict(c, conv=jnp.full_like(c["conv"], 2.0)) if "conv" in c
             else c for c in cache]
    got, _ = _apply(cfg, params, ids, _at(dirty, 0, valid=LENGTH))
    assert np.abs(np.asarray(got[0]) - want).max() < 1e-5
    _, kept = _apply(cfg, params, ids, _at(dirty, 0, valid=0))
    assert all((t == 2.0).all() for t in _tails(kept))


def test_the_router_is_the_published_form(toy):
    """The bias selects only, the weights are the unbiased scores over
    their sum + 1e-6: a bias that reorders the experts changes WHICH are
    chosen and not what a chosen one weighs."""
    cfg = toy[0]
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(5, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(8,)), jnp.float32)
    ids, weights = lm.choose_experts(cfg, w, bias, x)
    s = np.asarray(jax.nn.sigmoid(jnp.dot(
        x, w, precision=jax.lax.Precision.HIGHEST)))
    want_ids = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :2]
    assert (np.sort(np.asarray(ids)) == np.sort(want_ids)).all()
    picked = np.take_along_axis(s, np.asarray(ids), axis=-1)
    np.testing.assert_allclose(
        np.asarray(weights),
        picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    got_ids, got_w, _, _ = ref.router(x, w, bias, None,
                                        geom=ref.geometry(cfg))
    assert (np.asarray(got_ids) == np.asarray(ids)).all()
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(weights),
                               rtol=1e-6)


ROUTE_SHAPES = {
    "softmax": dict(norm_topk=True),
    "sigmoid-groups": dict(norm_topk=True, scoring="sigmoid", n_group=4,
                           topk_group=2, scale=2.5, bias=True),
    "sigmoid-one-group": dict(norm_topk=True, scoring="sigmoid",
                              scale=2.448, bias=True),
}


@pytest.mark.parametrize("shape", ROUTE_SHAPES)
def test_routes_older_call_shapes_are_unchanged(shape):
    """``route``'s new ``norm_eps`` defaults to the 1e-20 the three older
    callers had: their ids and weights bit for bit."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(9, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    kw = dict(ROUTE_SHAPES[shape])
    if kw.pop("bias", False):
        kw["bias"] = jnp.asarray(rng.normal(size=(16,)) * 0.01, jnp.float32)
    ids, weights = route(x, w, 4, **kw)
    # the arithmetic as it stood before the argument existed
    logits = jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)
    if shape == "softmax":
        want_w, want_ids = jax.lax.top_k(jax.nn.softmax(logits, -1), 4)
        want_w = want_w / jnp.sum(want_w, -1, keepdims=True)
    else:
        same = route(x, w, 4, norm_eps=1e-20, **kw)
        np.testing.assert_array_equal(np.asarray(same[1]),
                                      np.asarray(weights))
        s = jax.nn.sigmoid(logits)
        want_ids = ids
        picked = jnp.take_along_axis(s, ids, -1)
        want_w = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20) \
            * kw["scale"]
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    np.testing.assert_array_equal(np.asarray(weights), np.asarray(want_w))
    if shape != "softmax":
        other = route(x, w, 4, norm_eps=1e-2, **kw)[1]
        assert not np.array_equal(np.asarray(other), np.asarray(weights))


@pytest.mark.parametrize("fault", ["conv_break", "pad_advance",
                                   "bias_in_weights"])
def test_a_planted_fault_moves_the_references_logits(toy, fault):
    cfg, params, ids, want, _ = toy
    value = 8 if fault == "conv_break" else True
    geom = dict(ref.geometry(cfg), **{fault: value})
    with jax.default_matmul_precision("highest"):
        got = ref.Reference(geom).logits(params, ids.tolist(), last=LENGTH,
                                         prompt=12)
    assert np.abs(got - want).max() > 1e-3 * np.std(want)


def test_refusals_by_name():
    hf = dict(vocab_size=64, hidden_size=64, intermediate_size=128,
              moe_intermediate_size=32, num_hidden_layers=2,
              num_attention_heads=4, num_key_value_heads=2,
              layer_types=["conv", "full_attention"], num_dense_layers=1,
              num_experts=4, num_experts_per_tok=2)
    assert lm.Lfm2MoeConfig.from_hf_config(hf).n_layer == 2
    for key, value in (("conv_bias", True), ("tie_word_embeddings", False),
                       ("rope_scaling", {"type": "yarn"}), ("head_dim", 32),
                       ("layer_types", ["conv", "sliding_attention"])):
        with pytest.raises(ValueError, match=key if key != "rope_scaling"
                           else "rope_type"):
            lm.Lfm2MoeConfig.from_hf_config(dict(hf, **{key: value}))
