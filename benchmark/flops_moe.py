"""Operations and bytes the grouped expert matmuls of a routed layer need,
computed from shapes and from what the router chose (the benchmark's own
arithmetic, beside ``flops.py``).

One layer's expert work for ``assignments`` (token, expert) pairs: three
matmuls a pair (gate and up: hidden x width; down: width x hidden), 2 FLOPs
a multiply-add. Bytes: every expert that received a token streams its three
matrices once (the least any schedule moves; an expert no token chose costs
nothing), plus the activations: each pair's hidden row in (twice: gate and
up read it), two width rows out and one in, one hidden row out.
"""

from __future__ import annotations


def grouped_experts_cost(assignments: float, experts_touched: float,
                         hidden: int, width: int,
                         bytes_per_el: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one layer's three grouped matmuls."""
    flops = assignments * 3 * 2.0 * hidden * width
    weights = experts_touched * 3.0 * hidden * width
    activations = assignments * (3.0 * hidden + 3.0 * width)
    return flops, (weights + activations) * bytes_per_el
