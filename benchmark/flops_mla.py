"""Operations and bytes that attention over a LATENT cache needs (multi-head
latent attention, DeepSeek-V2/V3), computed from the rows' TRUE lengths and
the same whatever form or kernel implements the path (the benchmark's own
arithmetic, beside ``flops.py``).

A layer's cache row is ``latent`` = ``kv_lora_rank + qk_rope_head_dim``
values (576 at the published sizes), shared by all heads.

Decode, one query a row, in the ABSORBED form's count (the only form that
reads nothing but the cache): a (query, key) pair of one head is a
``latent``-wide dot for the score and a ``kv_lora_rank``-wide
multiply-add for the sum, 2 FLOPs a multiply-add; every attended cache row
is read once (the least any schedule moves: all heads share it).

Prefill, a chunk of queries against a row, in the NAIVE form's count: a
pair of one head is a ``qk_nope + qk_rope`` dot and a ``v``-wide
multiply-add (192 + 128 = 320); the latent rows the chunk can see are read
once. Decompressing them is not counted: a form that spends time on it is
charged for that time.
"""

from __future__ import annotations


def decode_cost(attended_rows: float, layers: int, n_head: int,
                kv_lora_rank: int, qk_rope_head_dim: int,
                bytes_per_el: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of ``layers`` layers' attention over
    ``attended_rows`` cache rows (summed over the rows of the batch and
    the steps: each row's true length)."""
    latent = kv_lora_rank + qk_rope_head_dim
    flops = 2.0 * n_head * (latent + kv_lora_rank) * attended_rows * layers
    return flops, float(attended_rows * latent * bytes_per_el * layers)


def chunk_pairs(start: int, length: int) -> int:
    """(query, key) pairs of ``length`` queries at positions ``start ..``
    under the causal mask: query ``i`` sees ``start + i + 1`` keys."""
    return length * start + length * (length + 1) // 2


def prefill_cost(qk_pairs: float, keys_read: float, layers: int,
                 n_head: int, qk_nope_head_dim: int, qk_rope_head_dim: int,
                 v_head_dim: int, kv_lora_rank: int,
                 bytes_per_el: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of ``layers`` layers' causal chunk-against-row
    attention: ``qk_pairs`` (query, key) pairs (:func:`chunk_pairs`
    summed over the chunk rows) and ``keys_read`` latent rows (each chunk
    row's ``start + length``)."""
    per_pair = 2.0 * n_head * (qk_nope_head_dim + qk_rope_head_dim
                               + v_head_dim)
    latent = kv_lora_rank + qk_rope_head_dim
    return (per_pair * qk_pairs * layers,
            float(keys_read * latent * bytes_per_el * layers))
