"""Scaled dot-product causal attention with a pluggable implementation.

This is the single dispatch point for attention in the framework. The
reference computes attention three ways (``nn.MultiheadAttention`` + triu mask
— ``GPTLike_wikitext2_learned_pe.py:118-130``; explicit matmul+mask in MLA —
``DeepSeekLike_spare_MoE_wikitext2.py:212-226``; torch SDPA inside
``nn.TransformerEncoder``). Here everything funnels through
:func:`dot_product_attention`, which picks:

- ``dense`` — pure-XLA einsum attention (works everywhere, incl. CPU tests)
- ``flash`` — Pallas TPU flash-attention kernel (O(L) memory, MXU-tiled)
- ``auto``  — flash on TPU when shapes allow, dense otherwise

Convention: q/k/v are ``(batch, length, heads, head_dim)`` (flax layout).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def causal_mask(
    q_len: int, kv_len: int, dtype=jnp.float32,
    q_offset: jax.Array | int | None = None, block: int = 1,
) -> jax.Array:
    """Additive causal mask of shape (1|B, 1, q_len, kv_len).

    ``block`` > 1 gives the block-causal mask of block-diffusion models
    (models/sdar_moe.py): query ``i`` sees key ``j`` iff
    ``j // block <= i // block`` — bidirectional inside a block of
    absolute positions, causal across blocks. ``block`` = 1 is the plain
    causal mask, traced exactly as before.

    ``q_offset`` is the absolute position of the first query. Default places
    the query block at the end of the kv sequence (plain decode); a KV-cached
    prefill passes the cache write index so queries mid-buffer mask both
    future prompt positions and unwritten cache slots. A ``(B,)`` vector
    offset gives per-sequence positions (continuous-batching decode, where
    every slot is at a different depth in its cache).
    """
    if q_offset is None:
        q_offset = kv_len - q_len
    q_offset = jnp.asarray(q_offset)
    if q_offset.ndim == 1:  # per-batch offsets -> (B, q_len) query positions
        q_pos = jnp.arange(q_len)[None, :] + q_offset[:, None]
        if block > 1:   # a query sees up to the last position of its block
            q_pos = q_pos - q_pos % block + (block - 1)
        allowed = jnp.arange(kv_len)[None, None, :] <= q_pos[:, :, None]
        return jnp.where(allowed, 0.0, NEG_INF).astype(dtype)[:, None]
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    if block > 1:
        q_pos = q_pos - q_pos % block + (block - 1)
    kv_pos = jnp.arange(kv_len)[None, :]
    allowed = kv_pos <= q_pos
    return jnp.where(allowed, 0.0, NEG_INF).astype(dtype)[None, None]


def dense_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    bias: jax.Array | None = None,
    kv_length: jax.Array | None = None,
    dropout_rate: float = 0.0,
    dropout_rng: jax.Array | None = None,
    scale: float | None = None,
    q_offset: jax.Array | int | None = None,
    block: int = 1,
) -> jax.Array:
    """Reference XLA attention. q: (B, Lq, H, D), k/v: (B, Lk, H, D).

    ``kv_length``: optional (B,) valid kv lengths (for padded KV caches).
    ``q_offset``: absolute position of the first query (KV-cached prefill).
    ``block``: block length of the causal mask (see :func:`causal_mask`).
    """
    b, q_len, n_head, head_dim = q.shape
    kv_len, n_kv = k.shape[1], k.shape[2]
    scale = scale if scale is not None else head_dim ** -0.5
    if n_kv != n_head:
        # GQA: contract against the kv heads DIRECTLY — a jnp.repeat
        # broadcast before the einsum materializes groups x the KV bytes
        # in HBM, which measured as the cached-decode bottleneck at 8B
        # (~256 MB/layer/step — docs/perf.md Finding 14). bias is the
        # one caller-facing shape that would need regrouping; no GQA
        # caller passes one, so fail loudly rather than guess.
        if n_head % n_kv or bias is not None:
            raise ValueError(
                f"grouped attention needs n_head ({n_head}) divisible by "
                f"kv heads ({n_kv}) and no bias")
        g = n_head // n_kv
        q5 = q.reshape(b, q_len, n_kv, g, head_dim)
        # (B, Hkv, G, Lq, Lk) logits in f32 for numerical stability.
        logits = jnp.einsum(
            "bqhgd,bkhd->bhgqk", q5, k,
            preferred_element_type=jnp.float32) * scale
        if causal:
            logits = logits + causal_mask(
                q_len, kv_len, q_offset=q_offset, block=block)[:, :, None]
        if kv_length is not None:
            kv_pos = jnp.arange(kv_len)[None, None, None, None, :]
            valid = kv_pos < kv_length[:, None, None, None, None]
            logits = jnp.where(valid, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        if dropout_rate > 0.0 and dropout_rng is not None:
            keep = jax.random.bernoulli(
                dropout_rng, 1.0 - dropout_rate, probs.shape)
            probs = probs * keep / (1.0 - dropout_rate)
        probs = probs.astype(v.dtype)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
        return out.reshape(b, q_len, n_head, head_dim)
    # (B, H, Lq, Lk) logits in f32 for numerical stability.
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if causal:
        logits = logits + causal_mask(q_len, kv_len, q_offset=q_offset,
                                      block=block)
    if kv_length is not None:
        kv_pos = jnp.arange(kv_len)[None, None, None, :]
        valid = kv_pos < kv_length[:, None, None, None]
        logits = jnp.where(valid, logits, NEG_INF)
    if bias is not None:
        logits = logits + bias
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = probs * keep / (1.0 - dropout_rate)
    probs = probs.astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    bias: jax.Array | None = None,
    kv_length: jax.Array | None = None,
    dropout_rate: float = 0.0,
    dropout_rng: jax.Array | None = None,
    scale: float | None = None,
    q_offset: jax.Array | int | None = None,
    impl: str = "auto",
    block: int = 1,
) -> jax.Array:
    """Attention entry point used by every model in the framework.

    ``block`` > 1 (block-causal mask) always takes the dense path: the
    flash and sequence-parallel kernels mask by position, not by block."""
    if block > 1:
        impl = "dense"
    if impl == "auto":
        impl = _pick_impl(q, k, bias, kv_length, dropout_rate, causal)
    if impl in ("ring", "ulysses"):
        # sequence-parallel schemes share one eligibility contract: full
        # (uncached) self-attention under an active sp_context mesh
        from llm_in_practise_tpu.ops import ring_attention as ra

        if (bias is None and kv_length is None and dropout_rate == 0.0
                and q_offset is None and k.shape[1] == q.shape[1]
                and ra.active_sp_mesh() is not None):
            if impl == "ring":
                return ra.context_ring_attention(
                    q, k, v, causal=causal, scale=scale)
            from llm_in_practise_tpu.ops import ulysses as ul

            return ul.context_ulysses_attention(
                q, k, v, causal=causal, scale=scale)
        impl = "dense"  # decode/cached paths fall back (KV not seq-sharded)
    if impl == "flash":
        from llm_in_practise_tpu.ops import flash_attention as fa

        if (causal and bias is None and kv_length is None
                and dropout_rate == 0.0 and q_offset is None
                and k.shape[:2] == q.shape[:2]
                and k.shape[3] == q.shape[3]
                and q.shape[2] % k.shape[2] == 0):
            return fa.flash_attention(q, k, v, causal=causal, scale=scale)
        impl = "dense"  # flash kernel doesn't cover these yet
    return dense_attention(
        q, k, v,
        causal=causal, bias=bias, kv_length=kv_length,
        dropout_rate=dropout_rate, dropout_rng=dropout_rng, scale=scale,
        q_offset=q_offset, block=block,
    )


@functools.cache
def _on_tpu() -> bool:
    # a failed device query raises: neither the kernel choice nor
    # interpret mode may hide that the chip is missing
    return jax.devices()[0].platform == "tpu"


def interpret_default() -> bool:
    """Pallas ``interpret`` for a kernel called without an explicit one:
    compiled on the TPU, interpreted on any other platform (what an
    explicit CPU run gets; tests that want it pass it explicitly)."""
    return not _on_tpu()


def _pick_impl(q, k, bias, kv_length, dropout_rate, causal=True) -> str:
    if (
        not _on_tpu()
        or not causal
        or bias is not None
        or kv_length is not None
        or dropout_rate
        or k.shape[:2] != q.shape[:2]      # same batch and length
        or k.shape[3] != q.shape[3]        # same head_dim
        or q.shape[2] % k.shape[2]         # heads = kv heads x groups
    ):
        return "dense"
    batch, q_len, n_head, head_dim = q.shape
    # Measured on one v5e chip (GPTLike 6L/512d training step, 8 heads of
    # 64) against the kernel of its day (128 x 128 tiles, float32 MXU
    # operands): XLA's fused dense attention won on short sequences —
    # 357K vs 253K tok/s at L=256, +23% at L=512. The flip side is the
    # dense path's f32 score materialization, B·H·L² bytes ×2 held for
    # the backward: at L=1024 training batches it no longer compiles.
    # The kernel since re-measured (tools/flash_bakeoff.py, docs/perf.md
    # Finding 3; bf16, B 8, 40 / 8 heads of 128, forward + backward of one
    # call): at L=1024 3.0 ms where that older kernel took 32.3 and dense,
    # alone, 17.7; at 768 2.0 against dense's 9.5; at 512 1.3 against 4.6;
    # at 256 dense wins, 0.56 against 0.72. The crossover stays at 512:
    # the shape that set it has 64-wide heads, which take the kernel's
    # heads-in-front layout, and that side is not re-measured.
    # Gate dense on BOTH that length crossover and an absolute
    # score-memory bound so wide-and-batchy shapes at L<=512 don't trade
    # the kernel's O(L) memory for an HBM blowup.
    score_bytes = 4 * batch * n_head * q_len * q_len
    # 2 GiB inclusive: the measured dense win at L=512/B=256/H=8 sits
    # exactly at the bound (and compiled + ran), so it stays admitted
    if q_len <= 512 and score_bytes <= (1 << 31):
        return "dense"
    if q_len % 128 == 0 and head_dim in (64, 128, 256):
        return "flash"
    return "dense"
