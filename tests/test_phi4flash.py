"""Phi-4-mini-flash (``models/phi4flash.py``) against the plain reference
(``benchmark/reference/phi4flash.py``): the forward without a cache, a
prefill then decode through the cache, the state's discipline, the
configuration as published, and the comparison against a reference with
one form left out; tiny sizes on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import phi4flash as ref
from llm_in_practise_tpu.models import phi4flash as pf

# the catalog row's ``config``, verbatim
HF = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
      "intermediate_size": 10240, "layer_norm_eps": 1e-05,
      "max_position_embeddings": 262144, "mb_per_layer": 2,
      "model_type": "phi4flash", "num_attention_heads": 40,
      "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
      "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
      "lm_head_bias": False, "vocab_size": 200064}
TIGHT = 1e-4        # relative to the logits' spread, float32


@pytest.fixture(scope="module")
def toy():
    """8 layers [M, W, M, W, M, F, G, X]; weights of unit gain (N(0, 0.1)
    at 64 wide), so that every mixer carries weight in the logits."""
    cfg = pf.phi4flash_config(compute_dtype="float32")
    params = pf.random_params(cfg, 7, jnp.float32, std=0.1)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 4,
                             cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(pf.Phi4Flash(cfg).apply({"params": params}, ids))
    return cfg, params, np.asarray(ids), logits


@pytest.fixture(scope="module")
def step(toy):
    """``model.apply`` with a cache, jitted: one program a call shape."""
    cfg, params, _, _ = toy
    model = pf.Phi4Flash(cfg)
    return jax.jit(lambda i, c: model.apply({"params": params}, i, cache=c))


def _cache(cfg, index, valid, fill=None):
    c = pf.Phi4Flash(cfg).init_cache(2, 64, dtype=jnp.float32)
    if fill is not None:
        c = [{k: (v if k == "index" else jnp.full(v.shape, fill, v.dtype))
              for k, v in e.items()} for e in c]
    return [dict(e, index=jnp.asarray(index, jnp.int32),
                 valid=jnp.asarray(valid, jnp.int32)) for e in c]


def _error(got, want):
    return ref.logit_error(got, want)["max_over_std"]


def test_config_as_published():
    cfg = pf.Phi4FlashConfig.from_hf_config(HF)
    m, w, f, g, x = pf.MAMBA, pf.WINDOW, pf.FULL, pf.GMU, pf.CROSS
    assert cfg.kinds == (m, w) * 8 + (m, f) + (g, x) * 7
    assert (cfg.d_inner, cfg.dt_rank, cfg.head_dim) == (5120, 160, 64)
    assert abs(cfg.param_count() / 3852.6e6 - 1) < 1e-3
    assert ref.geometry(cfg)["kinds"] == cfg.kinds
    # three cache entries, each kind's buffers stacked over its layers:
    # 9 states, 8 rings, the ONE layer that grows; 14 layers hold nothing
    state, rings, full = jax.eval_shape(
        lambda: pf.Phi4Flash(cfg).init_cache(1, 16384))
    assert state["ssm"].shape == (1, 9, 16, 5120)
    assert state["ssm"].dtype == jnp.float32
    assert state["conv"].shape == (1, 9, 3, 5120)
    assert rings["k1"].shape == (1, 512, 8, 10, 64)
    assert rings["v"].shape == (1, 512, 8, 10, 128)
    assert full["k1"].shape == (1, 16384, 640)
    assert full["v"].shape == (1, 16384, 1280)
    shapes = jax.eval_shape(lambda: pf.Phi4Flash(cfg).init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32)))["params"]
    assert {k: v["mlp_w2"].shape[:-2] for k, v in shapes.items()
            if isinstance(v, dict) and "mlp_w2" in v} == {
        "pair_mamba": (8,), "pair_window": (8,), "mamba_last": (),
        "full": (), "cross_gmu": (7,), "cross_attn": (7,)}


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", False), ("mb_per_layer", 4), ("mlp_bias", True),
    ("lm_head_bias", True), ("hidden_act", "gelu"),
    ("rope_scaling", {"type": "yarn"})])
def test_what_is_not_implemented_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        pf.Phi4FlashConfig.from_hf_config(dict(HF, **{key: value}))


def test_forward_is_the_references(toy):
    cfg, params, ids, logits = toy
    reference = ref.Reference(ref.geometry(cfg))
    for row in range(2):
        want = reference.logits(params, ids[row], last=ids.shape[1])
        assert _error(logits[row], want) < TIGHT


def test_prefill_then_decode_through_the_cache(toy, step):
    """One-shot prefill of 23 positions in a call 32 wide (9 of padding:
    the caches it leaves are those of its last REAL position), then
    decode: the logits at every decoded position."""
    cfg, params, ids, logits = toy
    with jax.default_matmul_precision("highest"):
        padded = np.concatenate([ids[:, :23], ids[:, :9]], axis=1)
        got, c = step(padded, _cache(cfg, [0, 0], [23, 23]))
        assert got.shape == (2, 1, cfg.vocab_size)
        assert _error(got[:, 0], logits[:, 22]) < TIGHT
        c = [dict(e, index=jnp.full((2,), 23), valid=jnp.asarray([1, 1]))
             for e in c]
        for t in range(23, 40):
            got, c = step(ids[:, t:t + 1], c)
            assert _error(got[:, 0], logits[:, t]) < TIGHT, t


def test_state_discipline_of_a_call(toy, step):
    """A row whose ``valid`` is 0 keeps ring, tail and state bit for bit; a
    call that starts at 0 starts from zeros whatever the buffers hold."""
    cfg, params, ids, _ = toy
    dirty = _cache(cfg, [5, 5], [0, 1], fill=3.0)
    _, after = step(ids[:, :1], dirty)
    for a, b in zip(after, dirty):
        for key in ("ssm", "conv", "k1", "k2", "v"):
            if key in b and b[key].shape[1] != 64:      # by slot
                np.testing.assert_array_equal(a[key][0], b[key][0])
                assert not np.array_equal(a[key][1], b[key][1])
    want, _ = step(ids[:, :32], _cache(cfg, [0, 0], [23, 23]))
    got, _ = step(ids[:, :32], _cache(cfg, [0, 0], [23, 23], fill=3.0))
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("form,value", [
    ("lambda_learned", False), ("subln", False), ("gmu_memory", False),
    ("memory_shift", 1), ("d_skip", False), ("window_mask", False),
    ("conv_break", 16),
    ("state_dtype", "bfloat16")])
def test_a_reference_with_one_form_left_out_fails(toy, form, value):
    """The comparison sees each form: lambda, the sub-norm, the GMU's
    memory, the D skip, the window, a convolution tail dropped at a chunk
    boundary, and a state kept in bfloat16 (the check's control, small)."""
    cfg, params, ids, logits = toy
    wrong = ref.Reference(dict(ref.geometry(cfg), **{form: value}))
    with jax.disable_jit():
        want = wrong.logits(params, ids[0], last=8)
        sound = ref.Reference(ref.geometry(cfg)).logits(
            params, ids[0], last=8)
    assert _error(logits[0, -8:], sound) < 1e-5
    assert _error(logits[0, -8:], want) > 3e-5
