"""Pipeline parallelism: GPipe schedule == unpipelined model, exactly.

GPipe is mathematically exact (unlike async PP), so the contract is
equality: loss and gradients must match ``model.apply`` to float
tolerance on the 8-device CPU mesh (4 stages x 2 data).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
from llm_in_practise_tpu.parallel import pipeline as pp
from tests import envcaps


@pytest.fixture(scope="module")
def setup():
    cfg = GPTConfig(vocab_size=128, seq_len=32, n_layer=4, n_head=2,
                    embed_dim=64, dropout=0.0, pos_embedding="learned",
                    norm_first=True, tie_weights=False)
    model = GPT(cfg)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 128, (8, 32)), jnp.int32)
    y = jnp.asarray(rng.integers(0, 128, (8, 32)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), x[:1], deterministic=True)[
        "params"]
    return cfg, model, params, x, y


def test_split_merge_roundtrip(setup):
    cfg, model, params, x, y = setup
    stem, stacked = pp.split_gpt_params(params, cfg.n_layer)
    merged = pp.merge_gpt_params(stem, stacked, cfg.n_layer)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(merged)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("n_stages,n_micro", [(2, 4), (4, 2), (4, 8)])
def test_pipeline_loss_matches_reference(setup, n_stages, n_micro):
    cfg, model, params, x, y = setup
    mesh = pp.pipeline_mesh(n_stages)
    stem, stacked = pp.split_gpt_params(params, cfg.n_layer)
    loss_fn = pp.make_pipeline_loss_fn(cfg, mesh, n_micro)
    with mesh:
        loss = jax.jit(loss_fn)(stem, stacked, x, y)
    ref = pp.reference_loss(model, params, x, y)
    np.testing.assert_allclose(float(loss), float(ref), rtol=2e-5)


@pytest.mark.skipif(not envcaps.shard_map_has_check_vma(),
                    reason=envcaps.SHARD_MAP_CHECK_VMA_REASON)
def test_pipeline_grads_match_reference(setup):
    cfg, model, params, x, y = setup
    mesh = pp.pipeline_mesh(4)
    stem, stacked = pp.split_gpt_params(params, cfg.n_layer)
    loss_fn = pp.make_pipeline_loss_fn(cfg, mesh, n_micro=4)
    with mesh:
        g_stem, g_blocks = jax.jit(jax.grad(loss_fn, argnums=(0, 1)))(
            stem, stacked, x, y)
    g_ref = jax.grad(
        lambda p: pp.reference_loss(model, p, x, y))(params)
    ref_stem, ref_blocks = pp.split_gpt_params(g_ref, cfg.n_layer)

    for a, b in zip(jax.tree_util.tree_leaves(g_stem),
                    jax.tree_util.tree_leaves(ref_stem)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g_blocks),
                    jax.tree_util.tree_leaves(ref_blocks)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-5)


def test_bad_divisibility_raises(setup):
    cfg, model, params, x, y = setup
    mesh = pp.pipeline_mesh(4)
    loss_fn = pp.make_pipeline_loss_fn(cfg, mesh, n_micro=3)
    stem, stacked = pp.split_gpt_params(params, cfg.n_layer)
    with pytest.raises(ValueError):
        loss_fn(stem, stacked, x, y)  # 8 % 3 != 0
    with pytest.raises(ValueError):
        pp.make_pipeline_loss_fn(cfg, pp.pipeline_mesh(8), 2)  # 4 layers / 8


def test_dropout_config_rejected(setup):
    cfg, model, params, x, y = setup
    mesh = pp.pipeline_mesh(2)
    with pytest.raises(ValueError, match="deterministic"):
        pp.make_pipeline_loss_fn(cfg.replace(dropout=0.1), mesh, 2)
