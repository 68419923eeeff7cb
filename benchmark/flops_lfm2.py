"""Operations the layers of LFM2-MoE need (``models/lfm2_moe.py``: gated
short-convolution mixers, grouped-query attention in every fourth layer,
routed experts all held), computed from the configuration's sizes and the
engine's step-statistics counters: the benchmark's own arithmetic, beside
``flops.py``, ``flops_moe.py`` and ``flops_swa.py``.

A position that passes the model multiplies by, a layer: a conv mixer's
``W_in`` (d x 3d) and ``W_out`` (d x d); an attention mixer's ``W_q``,
``W_k``, ``W_v``, ``W_o``; a dense ffn's three matrices of
``intermediate_size``, or the router (d x experts) and the three matrices
of the ``top_k`` experts it chose; 2 FLOPs a multiply-add. The gate -
convolve - gate itself is 2 + 2 x taps element-wise operations a channel a
position (7 of 16,384 thousand a layer: counted, and nothing beside the
matmuls). Attention: a (query, key) pair of one query head is a ``head_dim``
dot for the score and one for the sum. The tied head (d x vocabulary) is
paid by the positions whose logits are read: every decoded row and one a
chunk trip.
"""

from __future__ import annotations

from benchmark import flops_swa

CONV, FULL = "conv", "full_attention"


def sizes(config: dict) -> dict:
    """Plain numbers of a configuration's ``config.json`` keys."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    kinds = list(config["layer_types"])[:config["num_hidden_layers"]]
    dense = int(config["num_dense_layers"])
    return {"hidden": d, "head_dim": d // heads, "n_head": heads,
            "n_kv_head": config["num_key_value_heads"],
            "taps": config["conv_L_cache"],
            "vocab": config["vocab_size"],
            "intermediate": config["intermediate_size"],
            "width": config["moe_intermediate_size"],
            "experts": config["num_experts"],
            "top_k": config["num_experts_per_tok"],
            "conv_layers": kinds.count(CONV),
            "attention_layers": kinds.count(FULL),
            "dense_layers": min(dense, len(kinds)),
            "routed_layers": max(len(kinds) - dense, 0)}


def position_flops(g: dict) -> float:
    """Matmul and mixer FLOPs of ONE position through every layer (the
    head and the attention's pairs apart)."""
    d, hd = g["hidden"], g["head_dim"]
    q, kv = g["n_head"] * hd, g["n_kv_head"] * hd
    conv = 2.0 * (3 * d * d + d * d) + (2 + 2 * g["taps"]) * d
    attn = 2.0 * (d * (q + 2 * kv) + q * d)
    dense = 2.0 * 3 * d * g["intermediate"]
    routed = 2.0 * (d * g["experts"] + g["top_k"] * 3 * d * g["width"])
    return (g["conv_layers"] * conv + g["attention_layers"] * attn
            + g["dense_layers"] * dense + g["routed_layers"] * routed)


def attention_cost(pairs: float, key_rows: float, g: dict
                   ) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of the attention layers over ``pairs`` (query,
    key) pairs a query head and ``key_rows`` key rows read, at TRUE
    lengths (``flops_swa.attention_cost``, keys as wide as values)."""
    return flops_swa.attention_cost(
        pairs, key_rows, g["attention_layers"], g["n_head"], g["n_kv_head"],
        g["head_dim"], g["head_dim"])


def step_flops(c: dict, g: dict, chunk: int) -> float:
    """Model FLOPs of the work a window's counters record: the REAL prompt
    tokens its chunk rows advanced and the live rows its decode steps
    advanced (never a chunk's padding nor an idle row), the attention at
    true lengths, the head for every decoded row and one row a chunk trip
    (``chunk``: the chunk's width, which turns capacity into trips)."""
    decoded = c.get("conv_state_rows_advanced", 0)
    rows = c.get("prefill_chunk_tokens", 0) + decoded
    trips = c.get("prefill_chunk_capacity", 0) / chunk
    pairs = c.get("prefill_global_pairs", 0) + c.get(
        "global_tokens_attended", 0)
    return (rows * position_flops(g)
            + (decoded + trips) * 2.0 * g["hidden"] * g["vocab"]
            + attention_cost(pairs, 0, g)[0])
