"""Golden-value tests for core ops: attention, RoPE, sinusoidal PE, sampling.

RoPE is checked against a direct transcription of the reference formula
(``DeepSeekLike_spare_MoE_wikitext2.py:131-174``) computed in numpy.
"""

import jax
import jax.numpy as jnp
import numpy as np

from llm_in_practise_tpu.ops.attention import dense_attention, causal_mask
from llm_in_practise_tpu.ops.rope import (
    apply_rotary_emb,
    precompute_cos_sin,
    sinusoidal_embeddings,
)
from llm_in_practise_tpu.infer.sampling import sample_token


def reference_rope_numpy(x, theta=10000.0):
    """Independent numpy RoPE on interleaved even/odd pairs, x: (B,L,H,D)."""
    b, l, h, d = x.shape
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2) / d))
    freqs = np.outer(np.arange(l), inv_freq)  # (L, D/2)
    cos, sin = np.cos(freqs), np.sin(freqs)
    out = np.empty_like(x)
    x_even, x_odd = x[..., 0::2], x[..., 1::2]
    cos_b = cos[None, :, None, :]
    sin_b = sin[None, :, None, :]
    out[..., 0::2] = x_even * cos_b - x_odd * sin_b
    out[..., 1::2] = x_even * sin_b + x_odd * cos_b
    return out


def test_rope_matches_reference_formula():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 7, 3, 8)).astype(np.float32)
    cos, sin = precompute_cos_sin(8, 32)
    got = apply_rotary_emb(jnp.asarray(x), cos, sin)
    want = reference_rope_numpy(x)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


def test_rope_preserves_norm_and_relativity():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((1, 16, 2, 8)).astype(np.float32))
    cos, sin = precompute_cos_sin(8, 64)
    rot = apply_rotary_emb(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(rot), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1),
        atol=1e-4,
    )
    # relative property: <R_m q, R_n k> depends only on m - n
    q = jnp.asarray(rng.standard_normal((1, 16, 1, 8)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((1, 16, 1, 8)).astype(np.float32))
    q = jnp.broadcast_to(q[:, :1], q.shape)  # same q at all positions
    k = jnp.broadcast_to(k[:, :1], k.shape)
    qr = apply_rotary_emb(q, cos, sin)
    kr = apply_rotary_emb(k, cos, sin)
    dots = np.einsum("blhd,bmhd->blm", np.asarray(qr), np.asarray(kr))[0]
    # check diagonal bands are constant
    for off in (0, 3, 7):
        band = np.diagonal(dots, offset=off)
        np.testing.assert_allclose(band, band[0], atol=1e-4)


def test_causal_mask_decode_window():
    m = np.asarray(causal_mask(2, 5))[0, 0]
    # queries at absolute positions 3,4 of a 5-long kv
    assert (m[0, :4] == 0).all() and m[0, 4] < -1e29
    assert (m[1, :] == 0).all()


def test_dense_attention_matches_naive_softmax():
    rng = np.random.default_rng(2)
    q = rng.standard_normal((1, 5, 2, 4)).astype(np.float32)
    k = rng.standard_normal((1, 5, 2, 4)).astype(np.float32)
    v = rng.standard_normal((1, 5, 2, 4)).astype(np.float32)
    out = dense_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    # naive per-head computation
    for h in range(2):
        scores = q[0, :, h] @ k[0, :, h].T / np.sqrt(4)
        mask = np.triu(np.ones((5, 5), bool), 1)
        scores = np.where(mask, -np.inf, scores)
        probs = np.exp(scores - scores.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        want = probs @ v[0, :, h]
        np.testing.assert_allclose(np.asarray(out)[0, :, h], want, atol=1e-5)


def test_attention_kv_length_masks_padding():
    rng = np.random.default_rng(3)
    k_full = jnp.asarray(rng.standard_normal((1, 8, 1, 4)).astype(np.float32))
    v_full = jnp.asarray(rng.standard_normal((1, 8, 1, 4)).astype(np.float32))
    q = jnp.asarray(rng.standard_normal((1, 1, 1, 4)).astype(np.float32))
    # padded cache of len 8 with only 5 valid == truncated cache of len 5
    out_padded = dense_attention(
        q, k_full, v_full, causal=False, kv_length=jnp.array([5])
    )
    out_exact = dense_attention(q, k_full[:, :5], v_full[:, :5], causal=False)
    np.testing.assert_allclose(
        np.asarray(out_padded), np.asarray(out_exact), atol=1e-6
    )


def test_sinusoidal_embeddings_formula():
    pe = np.asarray(sinusoidal_embeddings(10, 6))
    pos, i = 3, 1
    np.testing.assert_allclose(
        pe[pos, 2 * i], np.sin(pos * np.exp(2 * i * -np.log(10000.0) / 6)), atol=1e-6
    )
    np.testing.assert_allclose(
        pe[pos, 2 * i + 1],
        np.cos(pos * np.exp(2 * i * -np.log(10000.0) / 6)),
        atol=1e-6,
    )


def test_sampling_greedy_and_topk():
    logits = jnp.asarray([[0.1, 3.0, 0.2, -1.0]])
    rng = jax.random.PRNGKey(0)
    assert int(sample_token(rng, logits, greedy=True)[0]) == 1
    # top_k=1 is greedy regardless of rng
    for seed in range(5):
        tok = sample_token(jax.random.PRNGKey(seed), logits, top_k=1)
        assert int(tok[0]) == 1
    # top_p tiny keeps only argmax
    for seed in range(5):
        tok = sample_token(jax.random.PRNGKey(seed), logits, top_p=0.01)
        assert int(tok[0]) == 1


class TestDebug:
    def test_seed_everything_deterministic(self):
        from llm_in_practise_tpu.obs.debug import seed_everything

        k1 = seed_everything(42)
        k2 = seed_everything(42)
        assert (np.asarray(k1) == np.asarray(k2)).all()
        assert not (np.asarray(seed_everything(7)) == np.asarray(k1)).all()

    def test_nan_trap_raises_and_resets(self):
        import jax
        import pytest

        from llm_in_practise_tpu.obs.debug import disable_debug, enable_debug

        enable_debug(nans=True)
        try:
            with pytest.raises(FloatingPointError):
                jax.block_until_ready(
                    jnp.log(jnp.zeros(4)) - jnp.log(jnp.zeros(4)))
        finally:
            disable_debug()
        # traps off again: the same expression just yields nan
        out = jnp.log(jnp.zeros(4)) - jnp.log(jnp.zeros(4))
        assert bool(jnp.isnan(out).all())


def test_attention_impl_crossover_heuristic(monkeypatch):
    """The measured dense-vs-flash auto-pick (docs/perf.md finding 3):
    dense for short sequences within the score-memory bound, flash for
    long sequences; decode/cached shapes stay dense regardless."""
    from llm_in_practise_tpu.ops import attention as A

    monkeypatch.setattr(A, "_on_tpu", lambda: True)

    class Q:
        def __init__(self, shape):
            self.shape = shape

    def pick(b, l, h, d, k_shape=None):
        q = Q((b, l, h, d))
        k = Q(k_shape) if k_shape else q
        return A._pick_impl(q, k, None, None, 0.0)

    assert pick(512, 256, 8, 64) == "dense"     # the bench rung
    assert pick(256, 512, 8, 64) == "dense"     # measured dense win (2 GiB)
    assert pick(128, 1024, 8, 64) == "flash"    # dense OOMs here
    assert pick(512, 512, 32, 64) == "flash"    # over the score bound
    # decode: cached KV longer than queries -> dense path regardless
    assert pick(8, 1, 8, 64, k_shape=(8, 512, 8, 64)) == "dense"
