"""Operations and bytes of a model with recurrent (selective-scan) layers,
a cross-decoder and differential attention (Phi-4-mini-flash-reasoning),
computed from the rows' TRUE counts and the same whatever implements each
path (the benchmark's own arithmetic, beside ``flops.py``, ``flops_mla.py``
and ``flops_swa.py``). ``g`` is the reference's geometry plus the widths
(:func:`sizes`).

**The scan.** A position of one layer updates ``d_inner x d_state`` state
elements: ``dt * A``, ``exp``, ``* S``, ``(dt x) * B``, ``+``, ``* C``,
``+`` = 7 operations each (the ``exp`` counted as one). It reads ``xc`` and
``dt`` and writes ``y`` (``d_inner`` float32 each: the recurrence is a
float32 computation whatever feeds it) and reads ``B`` and ``C`` (``d_state``
float32 each); the state is read and written once a chunk row, which is
left out (16 positions' worth in 2,048). No matrix unit can take the
recurrence, so against the chip's published peaks the least time is the
bytes': the share says how far the kernel is from streaming its inputs,
and the kernel is bound by the vector and transcendental units
(ops/selective_scan.py).

**Differential attention.** A (query, key) pair of one query head is a
``head_dim`` dot and a ``2 head_dim`` multiply-add: ``2 x n_head x 3
head_dim`` FLOPs a pair a layer (2 x 40 x 192 at the published sizes). A
key row read is its two keys and its value, ``n_kv_head x 2 head_dim``
values = 5,120 B, once a READER: each layer that attends the shared view
reads it, so the bytes count every reader's pass and a later program that
reads the view once for several layers reads above today's share, up to
the number of readers times it, and stays under 100% as long as it still
reads the view at least once.

**The whole step.** A position through the self-decoder multiplies every
matmul weight of layers ``0 .. half + 1`` once, one through the
cross-decoder those of the other layers and the tied head: 2 FLOPs a
weight, plus the attention pairs and the scans above.
"""

from __future__ import annotations

import math


def sizes(config: dict) -> dict:
    """The widths from the configuration's published keys and the family's
    constants (``assumed`` in the configuration file)."""
    d, layers = config["hidden_size"], config["num_hidden_layers"]
    return {"d": d, "layers": layers, "half": layers // 2,
            "inter": config["intermediate_size"],
            "vocab": config["vocab_size"],
            "n_head": config["num_attention_heads"],
            "n_kv_head": config["num_key_value_heads"],
            "head_dim": d // config["num_attention_heads"],
            "d_inner": 2 * d, "d_state": 16, "dt_rank": math.ceil(d / 16)}


def scan_cost(tokens: float, layers: int, g: dict) -> tuple[float, float]:
    """(operations, HBM bytes) of ``layers`` layers' scan over ``tokens``
    real positions."""
    di, n = g["d_inner"], g["d_state"]
    return (7.0 * di * n * tokens * layers,
            4.0 * (3 * di + 2 * n) * tokens * layers)


def attention_cost(pairs: float, key_rows: float, layers: int,
                   g: dict) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of ``layers`` layers' differential attention
    over ``pairs`` (query, key) pairs a query head and ``key_rows`` key
    rows read (both summed over rows and steps)."""
    hd = g["head_dim"]
    return (2.0 * g["n_head"] * 3 * hd * pairs * layers,
            2.0 * g["n_kv_head"] * 2 * hd * key_rows * layers)


def matmul_weights(g: dict) -> tuple[int, int]:
    """Matmul weights a position multiplies in the self-decoder and in the
    cross-decoder (the tied head with the second)."""
    d, di, hd = g["d"], g["d_inner"], g["head_dim"]
    mlp = 3 * d * g["inter"]
    mamba = (d * 2 * di + di * d + di * (g["dt_rank"] + 2 * g["d_state"])
             + g["dt_rank"] * di)
    q = g["n_head"] * hd
    attn = d * (q + 2 * g["n_kv_head"] * hd) + q * d
    cross, gmu = 2 * d * q, 2 * d * di
    half = g["half"]
    n_mamba, n_window = half // 2 + 1, half // 2
    n_cross = (g["layers"] - half - 2) // 2
    self_dec = ((half + 2) * mlp + n_mamba * mamba + (n_window + 1) * attn)
    cross_dec = ((g["layers"] - half - 2) * mlp + n_cross * (cross + gmu)
                 + d * g["vocab"])
    return self_dec, cross_dec


def step_flops(counters: dict, g: dict) -> float:
    """Model FLOPs of the work the counters name (``serve/step_stats.py``:
    rows through each decoder, attention pairs at true lengths, scanned
    and advanced positions)."""
    self_w, cross_w = matmul_weights(g)
    n_window = g["half"] // 2
    n_mamba = n_window + 1
    pairs = (counters.get("prefill_band_pairs", 0) * n_window
             + counters.get("window_rows_attended", 0) * n_window
             + counters.get("prefill_global_pairs", 0)
             # true lengths x readers already
             + counters.get("shared_kv_rows_attended", 0))
    attn, _ = attention_cost(pairs, 0, 1, g)
    scan, _ = scan_cost(counters.get("ssm_scan_tokens", 0)
                        + counters.get("ssm_state_rows_advanced", 0),
                        n_mamba, g)
    return (2.0 * self_w * counters.get("self_decoder_rows", 0)
            + 2.0 * cross_w * counters.get("cross_decoder_rows", 0)
            + attn + scan)
