"""Operations and bytes that the algorithm needs, computed from shapes.

The benchmark's own copies (originals: ``obs/cost.py::flops_per_token``,
``matmul_param_count``): a later PR that changes the program's accounting
does not move the yardstick.
"""

from __future__ import annotations


def matmul_params(hidden: int, intermediate: int, n_head: int,
                  n_kv_head: int, head_dim: int, n_layer: int,
                  vocab: int) -> int:
    """Weight elements that run as matrix multiplications for every token
    of a dense Qwen3-style decoder with a tied head: q, k, v, o, gate, up,
    down per layer, plus the head (the embedding GATHER is no matmul)."""
    q, kv = n_head * head_dim, n_kv_head * head_dim
    per_layer = hidden * (q + 2 * kv) + q * hidden + 3 * hidden * intermediate
    return n_layer * per_layer + vocab * hidden


def qlora_flops_per_token(m: int, n_layer: int, seq: int,
                          attn_dim: int) -> float:
    """FLOPs one trained token requires under QLoRA. A weight element
    costs 2 FLOPs forward; causal attention attends seq/2 keys on average,
    4*(seq/2)*D per layer forward (QK^T and AV). The frozen base has no
    weight gradient: matmuls cost 2x forward (forward + dX), attention 3x.
    Recomputation under remat and the LoRA factors' own FLOPs (r=8, under
    0.1%) do not count."""
    matmul_fwd = 2.0 * m
    attn_fwd = 2.0 * n_layer * seq * attn_dim
    return 2.0 * matmul_fwd + 3.0 * attn_fwd


def flash_attention_cost(batch: int, q_len: int, kv_len: int, n_head: int,
                         n_kv_head: int, head_dim: int, *, causal: bool,
                         bytes_per_el: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one forward flash-attention call. FLOPs:
    2*D per (query, key) pair for QK^T and 2*D for AV, over the pairs the
    mask keeps (causal with q_len == kv_len keeps q*(q+1)/2). Bytes: Q and
    O once, K and V once per KV head (the least any schedule moves)."""
    if causal:
        if q_len > kv_len:
            raise ValueError("causal attention needs q_len <= kv_len")
        pairs = q_len * (kv_len - q_len) + q_len * (q_len + 1) // 2
    else:
        pairs = q_len * kv_len
    flops = 4.0 * head_dim * pairs * n_head * batch
    els = batch * head_dim * (2 * q_len * n_head + 2 * kv_len * n_kv_head)
    return flops, float(els * bytes_per_el)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak_flops: float, peak_bw: float) -> tuple[float, str]:
    """Share (%) of the roofline a kernel reached: the least time the chip
    could take (the larger of FLOPs/peak and bytes/bandwidth) over the
    time it took, and which of the two bounds it."""
    t_c, t_m = flops / peak_flops, nbytes / peak_bw
    bound = "compute" if t_c >= t_m else "memory"
    return 100.0 * max(t_c, t_m) / seconds, bound
