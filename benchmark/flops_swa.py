"""Operations and bytes that grouped-query attention with keys wider than
values needs, with or without a BAND (a sliding window), computed from the
rows' TRUE lengths and the same whatever kernel implements the path (the
benchmark's own arithmetic, beside ``flops.py`` and ``flops_mla.py``).

A (query, key) pair of one query head is a ``head_dim``-wide dot for the
score and a ``v_head_dim``-wide multiply-add for the sum, 2 FLOPs a
multiply-add: 2 x 64 x (192 + 128) FLOPs a pair a layer at the published
sizes. Every key row a stretch can see is read once, all its K/V heads:
``kv_heads x (192 + 128)`` values (the least any schedule moves: the query
heads of a group share it). The sink adds a logit a head and is not
counted.

A query at position ``p`` sees ``p + 1`` keys under the causal mask alone
and ``min(p + 1, window)`` under the band.
"""

from __future__ import annotations


def causal_pairs(start: int, length: int) -> int:
    """(query, key) pairs of ``length`` queries at positions ``start ..``
    under the causal mask."""
    return length * start + length * (length + 1) // 2


def band_pairs(start: int, length: int, window: int) -> int:
    """The same under a band of ``window`` (the query's own position and
    the ``window - 1`` before it)."""
    ramp = min(max(window - 1 - start, 0), length)
    return (ramp * (2 * (start + 1) + ramp - 1) // 2
            + (length - ramp) * window)


def band_keys(start: int, length: int, window: int) -> int:
    """Key rows a stretch of ``length`` queries at ``start ..`` reads
    under the band: its own and the ``window - 1`` before its first."""
    return length + min(start, window - 1)


def attention_cost(pairs: float, key_rows: float, layers: int, n_head: int,
                   kv_heads: int, head_dim: int, v_head_dim: int,
                   bytes_per_el: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of ``layers`` layers' attention over ``pairs``
    (query, key) pairs a query head and ``key_rows`` key rows read (both
    summed over the rows of the batch and the steps)."""
    width = head_dim + v_head_dim
    return (2.0 * n_head * width * pairs * layers,
            float(key_rows * kv_heads * width * bytes_per_el * layers))
