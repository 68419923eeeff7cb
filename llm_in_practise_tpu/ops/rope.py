"""Rotary position embeddings (RoPE), both reference formulations.

The reference implements RoPE twice: via complex ``freqs_cis``
(``DeepSeekLike_wikitext2.py:122-160``) and via interleaved cos/sin
(``DeepSeekLike_spare_MoE_wikitext2.py:131-174``). Both are the same rotation;
we implement the interleaved-pair form (even/odd lanes rotated together) as
the canonical one, precomputing cos/sin tables once per model.

Layout: q/k are ``(batch, length, heads, head_dim)``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def precompute_cos_sin(
    head_dim: int, max_seq_len: int, theta: float = 10000.0
) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables of shape (max_seq_len, head_dim // 2), fp32."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    positions = jnp.arange(max_seq_len, dtype=jnp.float32)
    freqs = jnp.outer(positions, inv_freq)
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rotary_emb(
    x: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    *,
    positions: jax.Array | None = None,
    interleaved: bool = True,
) -> jax.Array:
    """Rotate feature pairs of x: (B, L, H, D).

    ``interleaved=True`` pairs even/odd lanes (the reference's formulation);
    ``interleaved=False`` pairs lane ``i`` with ``i + D/2`` — the HF
    "rotate_half" layout used by Qwen/Llama checkpoints. Same rotation,
    different lane permutation; the cos/sin tables are shared.

    ``positions``: optional (B, L) absolute positions (for KV-cached decode);
    defaults to ``arange(L)``.
    """
    b, l, _, d = x.shape
    if positions is None:
        cos_l = cos[:l][None, :, None, :]  # (1, L, 1, D/2)
        sin_l = sin[:l][None, :, None, :]
    else:
        cos_l = cos[positions][:, :, None, :]  # (B, L, 1, D/2)
        sin_l = sin[positions][:, :, None, :]
    xf = x.astype(jnp.float32)
    if interleaved:
        x_pairs = xf.reshape(b, l, x.shape[2], d // 2, 2)
        x_even, x_odd = x_pairs[..., 0], x_pairs[..., 1]
        rot_even = x_even * cos_l - x_odd * sin_l
        rot_odd = x_even * sin_l + x_odd * cos_l
        out = jnp.stack([rot_even, rot_odd], axis=-1).reshape(x.shape)
    else:
        x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
        out = jnp.concatenate(
            [x1 * cos_l - x2 * sin_l, x2 * cos_l + x1 * sin_l], axis=-1
        )
    return out.astype(x.dtype)


def sinusoidal_embeddings(max_len: int, dim: int) -> jax.Array:
    """Classic fixed sinusoidal position table (max_len, dim).

    Parity with ``get_sinusoidal_embeddings`` —
    reference ``GPTLike_wikitext2_fixed_pe.py:178-190``.
    """
    position = jnp.arange(max_len, dtype=jnp.float32)[:, None]
    div_term = jnp.exp(
        jnp.arange(0, dim, 2, dtype=jnp.float32) * (-jnp.log(10000.0) / dim)
    )
    pe = jnp.zeros((max_len, dim), dtype=jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(position * div_term))
    pe = pe.at[:, 1::2].set(jnp.cos(position * div_term))
    return pe


# --- YaRN (arXiv:2309.00071) as DeepSeek-V3's modeling code applies it ------


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """``yarn_get_mscale``: ``0.1 * mscale * ln(factor) + 1`` (1 at
    ``factor`` <= 1)."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, *, factor: float,
                  original_max_len: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> jax.Array:
    """The ``dim // 2`` rotary frequencies under YaRN: ``f_i =
    theta^(-2i/dim)`` where a dimension turns more than ``beta_fast``
    times over ``original_max_len`` positions (kept), ``f_i / factor``
    where it turns fewer than ``beta_slow`` times (interpolated), and
    the linear ramp between the two correction dimensions in between
    (``yarn_find_correction_range`` / ``yarn_linear_ramp_mask``)."""
    def correction_dim(rotations: float) -> float:
        return (dim * math.log(original_max_len / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001       # the published guard against a zero-width ramp
    extra = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    inter = extra / factor
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp       # 1: the frequency stays as it is
    return inter * (1.0 - keep) + extra * keep


def precompute_yarn_cos_sin(
    dim: int, max_seq_len: int, theta: float, *, factor: float,
    original_max_len: int, beta_fast: float = 32.0, beta_slow: float = 1.0,
    mscale: float = 1.0, mscale_all_dim: float = 0.0,
) -> tuple[jax.Array, jax.Array]:
    """:func:`precompute_cos_sin` with YaRN's frequencies; the tables are
    scaled by ``yarn_mscale(factor, mscale) / yarn_mscale(factor,
    mscale_all_dim)`` (1 where the two are equal, as in DeepSeek-V3)."""
    inv_freq = yarn_inv_freq(dim, theta, factor=factor,
                             original_max_len=original_max_len,
                             beta_fast=beta_fast, beta_slow=beta_slow)
    freqs = jnp.outer(jnp.arange(max_seq_len, dtype=jnp.float32), inv_freq)
    m = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
    return jnp.cos(freqs) * m, jnp.sin(freqs) * m


def yarn_attention_scale(qk_dim: int, factor: float,
                         mscale_all_dim: float) -> float:
    """The softmax scale that goes with those tables: ``qk_dim^-1/2 *
    m^2`` with ``m = yarn_mscale(factor, mscale_all_dim)`` (``m`` = 1,
    the plain scale, when ``mscale_all_dim`` is 0)."""
    m = yarn_mscale(factor, mscale_all_dim) if mscale_all_dim else 1.0
    return qk_dim ** -0.5 * m * m
