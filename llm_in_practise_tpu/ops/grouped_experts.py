"""Dropless grouped expert layer: every token goes through exactly the
experts its router chose, and no other.

``MoEFeedForward`` (models/deepseek.py, the teaching model) runs every
expert over every token at inference and drops overflow tokens in
training. This is the serving form of a routed layer (SDAR / Qwen3-MoE,
models/sdar_moe.py): route in float32, sort the ``N * k`` (token, expert)
assignments by expert, multiply each expert's rows by that expert's
weights in one grouped matmul over the STACKED weights, unsort, and sum
each token's ``k`` results under its routing weights. There is no
capacity, so nothing is dropped; shapes are static (``N * k`` rows
whatever the routing).

The grouped matmul is the Pallas megablox kernel
(``jax.experimental.pallas.ops.tpu.megablox.gmm``) with whole-K,
whole-N tiles. Timed on one v5e chip against ``jax.lax.ragged_dot``
(``tools/moe_bakeoff.py``, PR 29) for 128 experts of 2048 x 768: the three
matmuls of a layer take 2.29 ms against 5.29 ms at 64 tokens and 2.47 ms
against 5.70 ms at 256, against 1.47 ms to stream the 1.21 GB of weights
once; the kernel's default 128^3 tiles take 12.9 ms. Off the TPU the
kernel runs in Pallas interpret mode.

The leading axis of the stacked weights is "the experts held here": all
of the router's, since nothing is expert-parallel yet.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm

from llm_in_practise_tpu.ops.attention import interpret_default

# The name the expert layer's operations carry on the device plane
# (benchmark/metrics/moe_grouped_matmul_roofline.json matches it).
SCOPE = "moe_grouped_experts"
ROW_TILE = 128      # assignments a grid step of the kernel covers


def route(x: jax.Array, w_router: jax.Array, top_k: int, *,
          norm_topk: bool = True) -> tuple[jax.Array, jax.Array]:
    """Top-``top_k`` routing of ``x`` (N, hidden) over ``w_router``
    (hidden, n_experts): softmax over ALL experts in float32 (the matmul
    at ``highest`` precision: a bf16 pass flips near-tied experts), then
    the ``top_k`` largest, renormalised over themselves when
    ``norm_topk``. Returns ``(ids (N, k) int32, weights (N, k) f32)``."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, top_k)
    if norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return ids.astype(jnp.int32), weights


def _grouped_matmul(rows, weights, sizes):
    """``rows`` (M, K) sorted by group x ``weights`` (G, K, N) -> (M, N)."""
    _, k, n = weights.shape
    return gmm(rows, weights, sizes, rows.dtype, (ROW_TILE, k, n),
               interpret=interpret_default())


def grouped_expert_ffn(x: jax.Array, ids: jax.Array, weights: jax.Array,
                       w_gate: jax.Array, w_up: jax.Array,
                       w_down: jax.Array) -> jax.Array:
    """``sum_j weights[n, j] * down_e(silu(gate_e(x[n])) * up_e(x[n]))``
    with ``e = ids[n, j]``. ``x`` (N, hidden); ``ids`` / ``weights``
    (N, k), every id below E; ``w_gate`` / ``w_up`` (E, hidden, width);
    ``w_down`` (E, width, hidden). Returns (N, hidden)."""
    n_tok, k = ids.shape
    with jax.named_scope(SCOPE):
        flat = ids.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.bincount(
            flat, length=w_gate.shape[0]).astype(jnp.int32)
        m = n_tok * k
        pad = -m % ROW_TILE
        rows = jnp.take(x, order // k, axis=0)
        if pad:
            rows = jnp.pad(rows, ((0, pad), (0, 0)))
        gate = _grouped_matmul(rows, w_gate, sizes)
        up = _grouped_matmul(rows, w_up, sizes)
        out = _grouped_matmul(jax.nn.silu(gate) * up, w_down, sizes)[:m]
        unsorted = jnp.take(out.astype(jnp.float32), jnp.argsort(order),
                            axis=0)
        y = jnp.sum(unsorted.reshape(n_tok, k, -1)
                    * weights.astype(jnp.float32)[..., None], axis=1)
    return y.astype(x.dtype)
