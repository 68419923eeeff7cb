"""Flash-attention kernel vs the dense XLA reference — forward and gradients.

The kernels run in Pallas interpreter mode on CPU (same kernel logic the TPU
compiles), checked against ``ops.attention.dense_attention`` which the rest
of the test suite already trusts.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_in_practise_tpu.ops.attention import dense_attention, dot_product_attention
from llm_in_practise_tpu.ops.flash_attention import (
    flash_attention,
    pick_blocks,
)


def _qkv(key, b, l, h, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (b, l, h, d)
    return (
        jax.random.normal(kq, shape, dtype),
        jax.random.normal(kk, shape, dtype),
        jax.random.normal(kv, shape, dtype),
    )


@pytest.mark.parametrize("l", [128, 256])
def test_forward_matches_dense(l):
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, l, 2, 64)
    ref = dense_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_forward_unpadded_lengths():
    # 100 is not a multiple of the 128 tile: exercises the padding path
    q, k, v = _qkv(jax.random.PRNGKey(1), 1, 100, 2, 64)
    ref = dense_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_multiblock_online_softmax():
    # L=384 with block 128 → 3 kv blocks per final q block: the running
    # (m, l, acc) rescale is actually exercised
    q, k, v = _qkv(jax.random.PRNGKey(2), 1, 384, 1, 64)
    ref = dense_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_gradients_match_dense():
    q, k, v = _qkv(jax.random.PRNGKey(3), 2, 256, 2, 64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, interpret=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5,
            err_msg=f"grad d{name} mismatch",
        )


def test_gradients_unpadded_lengths():
    q, k, v = _qkv(jax.random.PRNGKey(4), 1, 200, 2, 64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, interpret=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_bfloat16_inputs():
    q, k, v = _qkv(jax.random.PRNGKey(5), 1, 128, 2, 64, jnp.bfloat16)
    ref = dense_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )


def test_scale_override():
    q, k, v = _qkv(jax.random.PRNGKey(6), 1, 128, 1, 64)
    ref = dense_attention(q, k, v, causal=True, scale=0.5)
    out = flash_attention(q, k, v, scale=0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_noncausal_rejected():
    q, k, v = _qkv(jax.random.PRNGKey(7), 1, 128, 1, 64)
    with pytest.raises(NotImplementedError):
        flash_attention(q, k, v, causal=False)


def test_dispatch_still_dense_on_cpu():
    # dot_product_attention auto-picks dense off-TPU; flash only when forced
    q, k, v = _qkv(jax.random.PRNGKey(8), 1, 128, 1, 64)
    out = dot_product_attention(q, k, v, causal=True, impl="auto")
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


# ------------------------------------------- tiles by shape, dtype, K/V groups
def _grouped(key, b, l, h, hk, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    return (
        jax.random.normal(kq, (b, l, h, d), dtype),
        jax.random.normal(kk, (b, l, hk, d), dtype),
        jax.random.normal(kv, (b, l, hk, d), dtype),
    )


def _check_against_dense(q, k, v, seed, out_atol=2e-5, grad_atol=5e-5,
                         cotangent=1.0, **blocks):
    """Forward, and the gradients of sum(out * w) (a cotangent of order
    one an element), against ``dense_attention`` on the same inputs."""
    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, interpret=True, **blocks)
    dense = lambda q, k, v: dense_attention(q, k, v, causal=True)  # noqa: E731
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    out = flash(q, k, v)
    assert out.dtype == q.dtype
    np.testing.assert_allclose(f32(out), f32(dense(q, k, v)), atol=out_atol)
    w = cotangent * jax.random.normal(jax.random.PRNGKey(seed), out.shape)
    grads = lambda attn: jax.grad(  # noqa: E731
        lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) * w),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, x, name in zip(grads(flash), grads(dense), (q, k, v), "qkv"):
        assert a.shape == x.shape and a.dtype == x.dtype
        np.testing.assert_allclose(
            f32(a), f32(b), atol=grad_atol, err_msg=f"grad d{name} mismatch")


@pytest.mark.parametrize("l_pad,d,dtype,want", [
    (128, 128, jnp.bfloat16, 128),
    (512, 128, jnp.bfloat16, 512),
    (640, 128, jnp.bfloat16, 640),      # 5 x 128: only itself divides it
    (768, 128, jnp.bfloat16, 768),
    (1024, 128, jnp.bfloat16, 1024),    # the QLoRA cell: one tile a head
    (1152, 128, jnp.bfloat16, 384),     # 9 x 128: the largest divisor
    (1664, 128, jnp.bfloat16, 128),     # 13 x 128: nothing but the lane tile
    (2048, 128, jnp.bfloat16, 1024),
    (8192, 64, jnp.bfloat16, 1024),
    (1024, 64, jnp.float32, 1024),      # 256 KiB a tile: still whole
    (1024, 128, jnp.float32, 512),      # float32 halves the cap
    (2048, 256, jnp.bfloat16, 512),     # and so does a 256-wide head
    (2048, 256, jnp.float32, 256),
])
def test_tile_rule(l_pad, d, dtype, want):
    assert pick_blocks(l_pad, d, dtype) == (want, want)
    assert l_pad % want == 0 and want % 128 == 0


@pytest.mark.parametrize("l,h,d", [
    (640, 2, 128),      # one 640 tile: bands of 256, 256 and 128
    (1000, 2, 128),     # padded to 1,024: one tile in four bands
    (1152, 1, 128),     # three 384 tiles a side
    (2048, 1, 128),     # two 1,024 tiles a side: banded, plain and dead
    (1024, 2, 64),      # heads moved to the front (64 is no lane tile)
])
def test_new_tiles_forward_and_gradients(l, h, d):
    _check_against_dense(*_qkv(jax.random.PRNGKey(9), 1, l, h, d), seed=10)


@pytest.mark.parametrize("block_q,block_k", [(128, 256), (256, 128),
                                             (512, 256)])
def test_unequal_blocks(block_q, block_k):
    # explicit rectangular blocks: masked where the diagonal crosses them,
    # never banded
    _check_against_dense(*_qkv(jax.random.PRNGKey(11), 1, 512, 2, 128),
                         seed=12, block_q=block_q, block_k=block_k)


@pytest.mark.parametrize("h,hk,d", [(4, 4, 128), (8, 2, 128), (5, 1, 128),
                                    (4, 1, 64)])
def test_grouped_kv_forward_and_gradients(h, hk, d):
    # K/V stay at their own heads on BOTH sides: the dense path contracts
    # them grouped, the kernel finds them by query head // group
    _check_against_dense(
        *_grouped(jax.random.PRNGKey(13), 2, 384, h, hk, d), seed=14)


@pytest.mark.parametrize("l,h,hk", [(128, 2, 2), (1024, 5, 1)])
def test_bfloat16_forward_and_gradients(l, h, hk):
    # test_bfloat16_inputs' tolerance, on the gradients too
    _check_against_dense(
        *_grouped(jax.random.PRNGKey(15), 1, l, h, hk, 128, jnp.bfloat16),
        seed=16, out_atol=3e-2, grad_atol=3e-2, cotangent=0.25)


def test_mismatched_kv_rejected():
    q, k, v = _grouped(jax.random.PRNGKey(17), 1, 128, 4, 3, 64)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, interpret=True)


def test_dispatch_hands_grouped_kv_to_flash(monkeypatch):
    # impl="flash" with fewer K/V heads: no repeat before the kernel
    from llm_in_practise_tpu.ops import flash_attention as fa

    seen = {}
    real = fa.flash_attention

    def spy(q, k, v, **kw):
        seen["kv_heads"] = k.shape[2], v.shape[2]
        return real(q, k, v, interpret=True, **kw)

    monkeypatch.setattr(fa, "flash_attention", spy)
    q, k, v = _grouped(jax.random.PRNGKey(18), 1, 256, 4, 2, 64)
    out = dot_product_attention(q, k, v, causal=True, impl="flash")
    assert seen["kv_heads"] == (2, 2)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
