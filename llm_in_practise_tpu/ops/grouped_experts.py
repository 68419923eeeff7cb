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
of the router's (SDAR), or one chip's share of an expert-parallel layer
(``held=(first id, count)``, DeepSeek-V3's 16 of 256): the layer routes
over every expert, drops the assignments of the experts it does not hold
before the sort, and computes its own experts' part of the result. What
the absent experts would add is left out; nothing here stands in for the
chips that hold them or for their exchange.
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
HELD_ROWS_FACTOR = 4    # rows buffer of a held share, in expected loads
TILE_ELEMENTS = 2 * 1024 * 1024     # of one weight tile in fast memory


def route(x: jax.Array, w_router: jax.Array, top_k: int, *,
          norm_topk: bool = True, scoring: str = "softmax",
          bias: jax.Array | None = None, n_group: int = 1,
          topk_group: int = 1, scale: float = 1.0,
          norm_eps: float = 1e-20) -> tuple[jax.Array, jax.Array]:
    """Top-``top_k`` routing of ``x`` (N, hidden) over ``w_router``
    (hidden, n_experts), the scores in float32 (the matmul at ``highest``
    precision: a bf16 pass flips near-tied experts). Returns ``(ids (N,
    k) int32, weights (N, k) f32)``.

    ``scoring="softmax"``: softmax over ALL experts, the ``top_k``
    largest, renormalised over themselves when ``norm_topk``.

    ``scoring="sigmoid"`` (DeepSeek-V3's ``noaux_tc``): ``s =
    sigmoid(logits)``; ``bias`` (n_experts,) is added to SELECT only;
    the experts form ``n_group`` equal groups, a group scores the sum of
    its two largest biased scores, and only the ``topk_group`` best
    groups stay eligible; the ``top_k`` largest biased scores among them
    are chosen; the weights are the UNBIASED ``s`` of the chosen,
    renormalised when ``norm_topk`` (``s_e / (sum of the chosen s +
    norm_eps)``: ``1e-20`` as DeepSeek-V3 publishes it, ``1e-6`` in
    ``models/lfm2_moe.py``'s family), times ``scale``. The softmax form
    divides by the bare sum."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        weights, ids = jax.lax.top_k(probs, top_k)
        if norm_topk:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return ids.astype(jnp.int32), weights
    if scoring != "sigmoid":
        raise ValueError(f"scoring must be 'softmax' or 'sigmoid', got "
                         f"{scoring!r}")
    scores = jax.nn.sigmoid(logits)
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    n, e = choice.shape
    if n_group > 1:
        grouped = choice.reshape(n, n_group, e // n_group)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, best = jax.lax.top_k(group_score, topk_group)
        keep = jnp.zeros((n, n_group), bool).at[
            jnp.arange(n)[:, None], best].set(True)
        choice = jnp.where(jnp.repeat(keep, e // n_group, axis=1), choice,
                           -jnp.inf)
    _, ids = jax.lax.top_k(choice, top_k)
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if norm_topk:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + norm_eps)
    return ids.astype(jnp.int32), weights * scale


def _tile(k: int, n: int) -> tuple[int, int]:
    """The kernel's (K, N) tile for a (k, n) expert matrix: the whole
    matrix where it has at most ``TILE_ELEMENTS`` elements (SDAR's 2048 x
    768), else the widest lane-aligned divisors of each side that keep
    the tile under that (7168 x 2048 -> 1792 x 1024, 2048 x 7168 -> 2048
    x 1024): two buffers of it must fit the chip's fast memory."""
    if k * n <= TILE_ELEMENTS:
        return k, n

    def widest(side, cap):
        return max((d for d in range(128, min(side, cap) + 1, 128)
                    if side % d == 0), default=side)

    tn = widest(n, 1024)
    return widest(k, TILE_ELEMENTS // tn), tn


def _grouped_matmul(rows, weights, sizes):
    """``rows`` (M, K) sorted by group x ``weights`` (G, K, N) -> (M, N)."""
    _, k, n = weights.shape
    return gmm(rows, weights, sizes, rows.dtype, (ROW_TILE, *_tile(k, n)),
               interpret=interpret_default())


def grouped_expert_ffn(x: jax.Array, ids: jax.Array, weights: jax.Array,
                       w_gate: jax.Array, w_up: jax.Array,
                       w_down: jax.Array, *,
                       held: tuple[int, int] | None = None,
                       n_experts: int | None = None) -> jax.Array:
    """``sum_j weights[n, j] * down_e(silu(gate_e(x[n])) * up_e(x[n]))``
    with ``e = ids[n, j]``. ``x`` (N, hidden); ``ids`` / ``weights``
    (N, k); ``w_gate`` / ``w_up`` (E, hidden, width); ``w_down`` (E,
    width, hidden). Returns (N, hidden).

    ``held`` is None: the E stacked experts are all the router scores,
    every id is below E. ``held=(first, count)``: they are experts
    ``first .. first + count - 1`` of a wider router (``count`` = E);
    the sum runs over the assignments to those experts only
    (:func:`_held_expert_ffn`; ``n_experts`` is the router's width,
    from which the expected local load follows)."""
    if held is not None:
        return _held_expert_ffn(x, ids, weights, w_gate, w_up, w_down,
                                held, n_experts)
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


def _local_ids(ids: jax.Array, held: tuple[int, int]) -> jax.Array:
    """Flat assignments as indices into the held experts; an absent
    expert's take the sentinel ``count``."""
    first, count = held
    local = ids.reshape(-1) - first
    return jnp.where((local >= 0) & (local < count), local, count)


def _counts(local: jax.Array, count: int) -> jax.Array:
    return jnp.bincount(local, length=count + 1)[:count].astype(jnp.int32)


def held_counts(ids: jax.Array, held: tuple[int, int]) -> jax.Array:
    """Assignments each held expert receives: (count,) int32."""
    return _counts(_local_ids(ids, held), held[1])


def _held_expert_ffn(x, ids, weights, w_gate, w_up, w_down, held,
                     n_experts):
    """The held experts' part of the layer. The assignments to absent
    experts are dropped BEFORE the sort (they take the sentinel group
    ``count``, which sorts last and has no weights), so the gathered
    rows and the kernel's tiles follow the LOCAL assignments. Shapes are
    static, so the rows buffer has a size chosen in advance:
    ``HELD_ROWS_FACTOR`` times the expected local load ``N k count /
    n_experts``. A router promises no such bound (every one of a token's
    k experts may be held here), so a ``cond`` on the true local count
    takes the full ``N k`` rows when it is exceeded: nothing is ever
    dropped."""
    first, count = held
    if count != w_gate.shape[0]:
        raise ValueError(f"held count {count} != stacked experts "
                         f"{w_gate.shape[0]}")
    n_tok, k = ids.shape
    m = n_tok * k
    with jax.named_scope(SCOPE):
        local = _local_ids(ids, held)
        order = jnp.argsort(local, stable=True)
        sizes = _counts(local, count)
        n_local = jnp.sum(sizes)
        w_flat = weights.astype(jnp.float32).reshape(-1)

        def run(n_rows):
            sel = order[:n_rows]
            tok = sel // k
            rows = jnp.take(x, tok, axis=0)
            pad = -n_rows % ROW_TILE
            if pad:
                rows = jnp.pad(rows, ((0, pad), (0, 0)))
            gate = _grouped_matmul(rows, w_gate, sizes)
            up = _grouped_matmul(rows, w_up, sizes)
            out = _grouped_matmul(jax.nn.silu(gate) * up, w_down,
                                  sizes)[:n_rows]
            # rows past the local count were never written by the kernel
            live = (jnp.arange(n_rows) < n_local)[:, None]
            out = jnp.where(live, out.astype(jnp.float32)
                            * jnp.take(w_flat, sel)[:, None], 0.0)
            return jnp.zeros((n_tok, x.shape[1]), jnp.float32).at[tok].add(
                out)

        small = m if n_experts is None else -(
            -HELD_ROWS_FACTOR * m * count // (n_experts * ROW_TILE)
        ) * ROW_TILE
        if small >= m:
            y = run(m)
        else:
            y = jax.lax.cond(n_local <= small, lambda: run(small),
                             lambda: run(m))
    return y.astype(x.dtype)
