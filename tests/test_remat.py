"""Gradient checkpointing (remat): recomputing blocks in backward must be
EXACT — same loss, same gradients — for every model family, including the
MoE's sown aux losses, and must compose with the sharded train step.
(Reference parity: every fine-tune script calls
gradient_checkpointing_enable — Fine-Tuning/qwen3-8b-lora.py:122-144.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_in_practise_tpu.models.deepseek import (
    DeepSeekLike, deepseeklike_config, moe_loss_fn,
)
from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
from llm_in_practise_tpu.models.qwen3 import Qwen3, qwen3_config
from tests import envcaps


def _tree_allclose(a, b, rtol=1e-5, atol=1e-6):
    flat_a = jax.tree_util.tree_leaves(a)
    flat_b = jax.tree_util.tree_leaves(b)
    assert len(flat_a) == len(flat_b)
    for x, y in zip(flat_a, flat_b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


def _loss_and_grads(model, params, x, y):
    def loss_fn(p):
        logits = model.apply({"params": p}, x, deterministic=True)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        ll = jnp.take_along_axis(logp, y[..., None], -1)[..., 0]
        return -ll.mean()
    return jax.jit(jax.value_and_grad(loss_fn))(params)


@pytest.mark.parametrize("family", [
    "gpt",
    pytest.param("qwen3", marks=pytest.mark.skipif(
        not envcaps.shard_map_has_check_vma(),
        reason=envcaps.SHARD_MAP_CHECK_VMA_REASON)),
])
def test_remat_grads_exact(rng, family):
    if family == "gpt":
        cfg = GPTConfig(vocab_size=61, seq_len=32, n_layer=2, n_head=2,
                        embed_dim=32, dropout=0.0, pos_embedding="rope")
        make = lambda c: GPT(c)
    else:
        cfg = qwen3_config(vocab_size=61, n_layer=2)
        make = lambda c: Qwen3(c)
    model = make(cfg)
    params = model.init(rng, jnp.ones((1, 8), jnp.int32))["params"]
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 61)
    y = jnp.roll(x, -1, axis=1)

    loss0, grads0 = _loss_and_grads(model, params, x, y)
    model_r = make(cfg.replace(remat=True))
    loss1, grads1 = _loss_and_grads(model_r, params, x, y)
    np.testing.assert_allclose(float(loss0), float(loss1), rtol=1e-6)
    _tree_allclose(grads0, grads1)


def test_remat_deepseek_moe_aux_losses_survive(rng):
    """The MoE blocks sow aux losses; remat must thread the collection and
    keep the total loss + grads identical."""
    cfg = deepseeklike_config(
        61, embed_dim=32, n_layer=2, n_head=2, seq_len=32, n_experts=4,
        top_k=2, dropout=0.0, first_dense_layers=1)
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 61)
    batch = (x, jnp.roll(x, -1, axis=1))

    results = []
    for remat in (False, True):
        model = DeepSeekLike(cfg.replace(remat=remat))
        params = model.init(rng, jnp.ones((1, 8), jnp.int32))["params"]

        def loss_fn(p):
            loss, _ = moe_loss_fn(p, model.apply, batch,
                                  jax.random.PRNGKey(0))
            return loss
        results.append(jax.jit(jax.value_and_grad(loss_fn))(params))
    (loss0, g0), (loss1, g1) = results
    np.testing.assert_allclose(float(loss0), float(loss1), rtol=1e-6)
    _tree_allclose(g0, g1, rtol=2e-5, atol=1e-5)


def test_remat_with_dropout_rng_threads(rng):
    """Non-deterministic (dropout) forward under remat must run — the
    lifted transform threads the dropout rng into the recompute."""
    cfg = GPTConfig(vocab_size=61, seq_len=32, n_layer=2, n_head=2,
                    embed_dim=32, dropout=0.1, pos_embedding="learned",
                    remat=True)
    model = GPT(cfg)
    params = model.init(rng, jnp.ones((1, 8), jnp.int32))["params"]
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 61)

    def loss_fn(p):
        logits = model.apply({"params": p}, x, deterministic=False,
                             rngs={"dropout": jax.random.PRNGKey(2)})
        return logits.astype(jnp.float32).mean()

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree_util.tree_leaves(grads))


def test_remat_cached_decode_unaffected(rng):
    """Decode (cache present) bypasses remat; outputs equal non-remat."""
    from llm_in_practise_tpu.infer.generate import generate

    cfg = GPTConfig(vocab_size=61, seq_len=64, n_layer=2, n_head=2,
                    embed_dim=32, dropout=0.0, pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(rng, jnp.ones((1, 8), jnp.int32))["params"]
    prompt = jnp.asarray([[3, 1, 4, 1, 5]], jnp.int32)
    a = generate(model, params, prompt, max_new_tokens=8, greedy=True,
                 cache_len=32, cache_dtype=jnp.float32)
    model_r = GPT(cfg.replace(remat=True))
    b = generate(model_r, params, prompt, max_new_tokens=8, greedy=True,
                 cache_len=32, cache_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
