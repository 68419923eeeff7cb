"""Pallas TPU flash attention — causal, O(L) memory, MXU-tiled.

The reference computes attention as dense matmul + materialized triu mask
(``GPTLike_wikitext2_learned_pe.py:118-130``, MLA explicit matmul
``DeepSeekLike_spare_MoE_wikitext2.py:212-226``), which is O(L²) HBM. The
TPU idiom is blockwise online-softmax attention: K/V blocks are streamed
through VMEM by the Pallas pipeline (one ``(block, D)`` tile per grid step —
VMEM holds only the current tiles plus per-row accumulators, so sequence
length is bounded by HBM, not VMEM), and the (L, L) score matrix is never
materialized. Backward is the FlashAttention-2 split: recompute block scores
from the saved per-row logsumexp, one kernel for dK/dV (parallel over KV
blocks) and one for dQ (parallel over Q blocks).

Accumulators live in VMEM scratch and persist across the innermost grid
dimension (TPU grids execute sequentially, innermost fastest). Causally dead
blocks are skipped with ``pl.when`` and fetch nothing (their index maps stay
on the nearest live block); the mask's iota/select runs only on blocks the
diagonal crosses.

Tiles follow the shape (:func:`pick_blocks`): the grid's steps, not the
MXU, bound a 128 x 128 tiling at training lengths. The matmuls take their
operands in the inputs' dtype and accumulate in float32; the softmax, the
running statistics and every accumulator are float32.

Layout: the entry point takes the framework-wide ``(B, L, H, D)`` with K/V
at their own head count (grouped-query attention: query head ``h`` reads
K/V head ``h // group``; dK/dV sum over a group's query heads inside the
kernel). Where ``D`` is whole lane tiles the kernels read each head's
``D``-wide lane slice of ``(B, L, H·D)`` in place; otherwise heads move to
the front. Length is padded to the 128 tile. Causal-only (the only masking
the models need — non-causal paths stay on the dense XLA implementation in
``ops/attention.py``).

On non-TPU backends the kernels run in Pallas interpreter mode so the exact
kernel logic is unit-testable on the 8-device CPU mesh (SURVEY §4).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_in_practise_tpu.ops.attention import interpret_default

NEG_INF = -1e30
_LANE = 128
_SUBLANE = 8  # lse/delta carry a replicated sublane dim to satisfy TPU tiling
# Largest tile side, and the rows of a band of the tile the diagonal
# crosses (tools/flash_bakeoff.py, docs/perf.md Finding 3).
_MAX_BLOCK = 1024
_BAND = 256
_NT = (((1,), (1,)), ((), ()))  # contract the last dim of both: A @ B^T


def pick_blocks(l_pad: int, head_dim: int, dtype) -> tuple[int, int]:
    """``(block_q, block_k)`` for a padded length: the largest multiple of
    128 that divides it, up to 1,024 — halved where one head's tile
    (``block x head_dim`` elements of ``dtype``) would pass 256 KiB, so the
    double-buffered operand tiles of a backward kernel and its float32
    score tiles stay inside the default scoped VMEM."""
    cap = _MAX_BLOCK
    while cap > _LANE and cap * head_dim * jnp.dtype(dtype).itemsize > (1 << 18):
        cap //= 2
    block = max(t for t in range(_LANE, cap + 1, _LANE) if l_pad % t == 0)
    return block, block


def _causal(shape, q_axis, q0, k0):
    """``k0 + key <= q0 + query`` over a tile whose ``q_axis`` counts
    queries and whose other axis counts keys, as one difference against
    one scalar."""
    qpos = jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kpos = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return kpos - qpos <= q0 - k0


def _by_diagonal(tile, q0, q_len, k0, k_len):
    """Run ``tile(masked)`` for a block of queries ``q0 ..`` and keys
    ``k0 ..``: masked where the diagonal crosses it, plain where it lies
    wholly under it, not at all above it."""
    below = k0 + k_len - 1 <= q0
    live = k0 <= q0 + q_len - 1
    pl.when(live & jnp.logical_not(below))(lambda: tile(True))
    pl.when(below)(lambda: tile(False))


def _bands(masked, block_q, block_k, by_keys=False):
    """``(queries, keys)`` slices of the pieces a block is worked in: whole,
    or, where the diagonal crosses a SQUARE block (it then starts on the
    diagonal), in bands of ``_BAND`` queries that stop at their own last
    key (``by_keys``: of ``_BAND`` keys that start at their own first
    query). The upper triangle is half a square block's matmul work and an
    eighth or less of a band's."""
    if not masked or block_q != block_k:
        return [(slice(0, block_q), slice(0, block_k))]
    bands = [(r, min(r + _BAND, block_q)) for r in range(0, block_q, _BAND)]
    if by_keys:
        return [(slice(lo, block_q), slice(lo, hi)) for lo, hi in bands]
    return [(slice(lo, hi), slice(0, hi)) for lo, hi in bands]


def _lanes(x, width):
    """A ``(rows, 128)`` lane-replicated statistic at ``width`` lanes."""
    if width <= _LANE:
        return x[:, :width]
    return jnp.tile(x, (1, width // _LANE))


def _to_lanes(col):
    """``(rows, 128)`` lane-replicated -> ``(8, rows)``: rows become lanes."""
    return col.T[:_SUBLANE]


# --------------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, block_q, block_k):
    """Grid (b, h, n_q, n_kv), kv innermost; acc/m/l scratch persists over
    kv. m and l are held replicated over 128 lanes: a row statistic that
    lives in one lane costs masked stores and a lane broadcast a block."""
    qi, ki = pl.program_id(2), pl.program_id(3)
    n_kv = pl.num_programs(3)
    d = acc_ref.shape[1]

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def tile(masked):
        for rows, keys in _bands(masked, block_q, block_k):
            vb = v_ref[keys, :]                                  # (bk, D)
            s = scale * jax.lax.dot_general(
                q_ref[rows, :], k_ref[keys, :], _NT,
                preferred_element_type=jnp.float32,
            )                                                    # (bq, bk)
            if masked:
                s = jnp.where(_causal(
                    s.shape, 0, qi * block_q + rows.start, ki * block_k),
                    s, NEG_INF)
            m_prev = m_ref[rows, :]                              # (bq, 128)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
            p = jnp.exp(s - _lanes(m_new, s.shape[1]))
            alpha = jnp.exp(m_prev - m_new)
            l_ref[rows, :] = (l_ref[rows, :] * alpha
                              + jnp.sum(p, axis=1)[:, None])
            m_ref[rows, :] = m_new
            acc_ref[rows, :] = acc_ref[rows, :] * _lanes(alpha, d) + (
                jax.lax.dot(p.astype(vb.dtype), vb,
                            preferred_element_type=jnp.float32))

    _by_diagonal(tile, qi * block_q, block_q, ki * block_k, block_k)

    @pl.when(ki == n_kv - 1)
    def _():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / _lanes(l, d)).astype(o_ref.dtype)
        lse_ref[...] = _to_lanes(m_ref[...] + jnp.log(l))


# -------------------------------------------------------------------- backward
def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                   dq_ref, delta_ref, dq_acc, lse_col, delta_col,
                   *, scale, block_q, block_k):
    """Grid (b, h, n_q, n_kv), kv innermost; dq scratch persists over kv,
    and so do the q block's lse (turned from lanes to rows once) and its
    delta = rowsum(dO * O), which leaves in lse's layout for the dK/dV
    kernel."""
    qi, ki = pl.program_id(2), pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(ki == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        lse_col[...] = jnp.broadcast_to(
            lse_ref[0:1, :], (_LANE, block_q)).T                 # (bq, 128)
        delta = jnp.sum(
            do_ref[...].astype(jnp.float32) * o_ref[...].astype(jnp.float32),
            axis=1)[:, None]
        delta_col[...] = jnp.broadcast_to(delta, delta_col.shape)
        delta_ref[...] = _to_lanes(delta_col[...])

    def tile(masked):
        for rows, keys in _bands(masked, block_q, block_k):
            kb = k_ref[keys, :]
            s = scale * jax.lax.dot_general(
                q_ref[rows, :], kb, _NT, preferred_element_type=jnp.float32)
            if masked:
                s = jnp.where(_causal(
                    s.shape, 0, qi * block_q + rows.start, ki * block_k),
                    s, NEG_INF)
            p = jnp.exp(s - _lanes(lse_col[rows, :], s.shape[1]))
            dp = jax.lax.dot_general(
                do_ref[rows, :], v_ref[keys, :], _NT,
                preferred_element_type=jnp.float32)
            ds = p * (dp - _lanes(delta_col[rows, :], s.shape[1]))
            dq_acc[rows, :] += jax.lax.dot(
                ds.astype(kb.dtype), kb, preferred_element_type=jnp.float32)

    _by_diagonal(tile, qi * block_q, block_q, ki * block_k, block_k)

    @pl.when(ki == n_kv - 1)
    def _():
        dq_ref[...] = (scale * dq_acc[...]).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, block_q, block_k):
    """Grid (b, h_kv, n_kv, group, n_q), a K/V head's query heads and their
    q blocks innermost; dk/dv scratch persists over both. Scores are held
    transposed, ``(bk, bq)``: every matmul is then plain or ``A @ B^T``, and
    lse/delta broadcast as the rows they are stored as."""
    ki, gi, qj = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    last = (gi == pl.num_programs(3) - 1) & (qj == pl.num_programs(4) - 1)

    @pl.when((gi == 0) & (qj == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile(masked):
        for rows, keys in _bands(masked, block_q, block_k, by_keys=True):
            qb, dob = q_ref[rows, :], do_ref[rows, :]            # (bq, D)
            s = scale * jax.lax.dot_general(
                k_ref[keys, :], qb, _NT, preferred_element_type=jnp.float32,
            )                                                    # (bk, bq)
            if masked:
                s = jnp.where(_causal(
                    s.shape, 1, qj * block_q + rows.start,
                    ki * block_k + keys.start), s, NEG_INF)
            p = jnp.exp(s - lse_ref[0:1, rows])
            dv_acc[keys, :] += jax.lax.dot(
                p.astype(dob.dtype), dob, preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                v_ref[keys, :], dob, _NT, preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[0:1, rows])
            dk_acc[keys, :] += jax.lax.dot(
                ds.astype(qb.dtype), qb, preferred_element_type=jnp.float32)

    _by_diagonal(tile, qj * block_q, block_q, ki * block_k, block_k)

    @pl.when(last)
    def _():
        dk_ref[...] = (scale * dk_acc[...]).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


# ------------------------------------------------------------------ the calls
def _head_tile(rows, d, heads, in_lanes, at):
    """One head's ``(rows, d)`` tile of an array that holds ``heads`` heads
    as lane slices of ``(B, L, heads·d)`` (``in_lanes``) or in front,
    ``(B·heads, L, d)``; ``at(*grid ids) -> (batch, head, row block)``."""
    def index(*ids):
        b, h, blk = at(*ids)
        return (b, blk, h) if in_lanes else (b * heads + h, blk, 0)
    return pl.BlockSpec((None, rows, d), index)


def _row_stat(block_q, at):
    """lse / delta, ``(B, H, 8, L)``: a q block's values along the lanes."""
    def index(*ids):
        b, h, blk = at(*ids)
        return (b, h, 0, blk)
    return pl.BlockSpec((None, None, _SUBLANE, block_q), index)


def _semantics(grid, reduced):
    return pltpu.CompilerParams(dimension_semantics=(
        ("parallel",) * (len(grid) - reduced) + ("arbitrary",) * reduced))


class _Call(NamedTuple):
    """What the three kernels' calls share; static under ``custom_vjp``."""
    scale: float
    block_q: int
    block_k: int
    batch: int
    heads: int
    kv_heads: int
    head_dim: int
    in_lanes: bool
    interpret: bool

    def tile(self, rows, heads, at):
        return _head_tile(rows, self.head_dim, heads, self.in_lanes, at)

    def kernel(self, body):
        return functools.partial(body, scale=self.scale,
                                 block_q=self.block_q, block_k=self.block_k)

    def by_q_block(self, length):
        """Grid (b, h, n_q, n_kv) of the forward and dQ kernels, and their
        q / kv / row-statistic blocks; a causally dead kv block stays on
        its row's last live one, so it is not fetched."""
        block_q, block_k = self.block_q, self.block_k
        g = self.heads // self.kv_heads

        def q_at(b, h, i, j):
            return b, h, i

        def kv_at(b, h, i, j):
            return b, h // g, jnp.minimum(
                j, ((i + 1) * block_q - 1) // block_k)

        grid = (self.batch, self.heads, length // block_q, length // block_k)
        return (grid, self.tile(block_q, self.heads, q_at),
                self.tile(block_k, self.kv_heads, kv_at),
                _row_stat(block_q, q_at))


def _flash_fwd_call(cfg, q, k, v):
    L = q.shape[1]
    grid, q_tile, kv_tile, stat = cfg.by_q_block(L)
    return pl.pallas_call(
        cfg.kernel(_fwd_kernel),
        grid=grid,
        in_specs=[q_tile, kv_tile, kv_tile],
        out_specs=[q_tile, stat],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(
                (cfg.batch, cfg.heads, _SUBLANE, L), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((cfg.block_q, cfg.head_dim), jnp.float32),
            pltpu.VMEM((cfg.block_q, _LANE), jnp.float32),
            pltpu.VMEM((cfg.block_q, _LANE), jnp.float32),
        ],
        compiler_params=_semantics(grid, 1),
        interpret=cfg.interpret,
    )(q, k, v)


def _flash_bwd_call(cfg, q, k, v, out, lse, do):
    L = q.shape[1]
    block_q, block_k, d = cfg.block_q, cfg.block_k, cfg.head_dim
    g = cfg.heads // cfg.kv_heads

    grid, q_tile, kv_tile, stat = cfg.by_q_block(L)
    dq, delta = pl.pallas_call(
        cfg.kernel(_bwd_dq_kernel),
        grid=grid,
        in_specs=[q_tile, kv_tile, kv_tile, q_tile, q_tile, stat],
        out_specs=[q_tile, stat],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(lse.shape, jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
        ],
        compiler_params=_semantics(grid, 1),
        interpret=cfg.interpret,
    )(q, k, v, out, do, lse)

    def q_of_kv(b, hk, j, gi, i):  # a dead block stays on the first live one
        return b, hk * g + gi, jnp.maximum(i, (j * block_k) // block_q)

    def kv_of_kv(b, hk, j, gi, i):
        return b, hk, j

    q_tile = cfg.tile(block_q, cfg.heads, q_of_kv)
    kv_tile = cfg.tile(block_k, cfg.kv_heads, kv_of_kv)
    stat = _row_stat(block_q, q_of_kv)
    grid = (cfg.batch, cfg.kv_heads, L // block_k, g, L // block_q)
    dk, dv = pl.pallas_call(
        cfg.kernel(_bwd_dkv_kernel),
        grid=grid,
        in_specs=[q_tile, kv_tile, kv_tile, q_tile, stat, stat],
        out_specs=[kv_tile, kv_tile],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_semantics(grid, 2),
        interpret=cfg.interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ------------------------------------------------------------------ custom vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_core(cfg, q, k, v):
    out, _ = _flash_fwd_call(cfg, q, k, v)
    return out


def _flash_core_fwd(cfg, q, k, v):
    out, lse = _flash_fwd_call(cfg, q, k, v)
    return out, (q, k, v, out, lse)


def _flash_core_bwd(cfg, res, do):
    return _flash_bwd_call(cfg, *res, do)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Causal flash attention over ``(B, L, H, D)`` q and ``(B, L, Hkv, D)``
    k/v, ``H`` a multiple of ``Hkv``.

    Sequence length is padded to the 128 tile internally; padded KV columns
    fall after every real query position so the causal mask excludes them,
    and padded query rows are sliced off on return. ``block_q``/``block_k``
    default to :func:`pick_blocks` and must divide the padded length.
    """
    if not causal:
        raise NotImplementedError("flash kernel is causal-only; use dense")
    b, L, h, d = q.shape
    hk = k.shape[2]
    if (k.shape != v.shape or k.shape != (b, L, hk, d) or h % hk):
        raise ValueError(
            "flash kernel requires k/v of one shape, q's batch, length and "
            f"head_dim, and heads a multiple of theirs: {q.shape} {k.shape} "
            f"{v.shape}")
    scale = scale if scale is not None else d ** -0.5
    if interpret is None:
        interpret = interpret_default()

    L_pad = max(_LANE, -(-L // _LANE) * _LANE)
    auto_q, auto_k = pick_blocks(L_pad, d, q.dtype)
    block_q = min(block_q or auto_q, L_pad)
    block_k = min(block_k or auto_k, L_pad)
    if L_pad % block_q or L_pad % block_k:
        raise ValueError(
            f"block_q={block_q}/block_k={block_k} must divide padded length {L_pad}"
        )
    # a head is a lane slice of (B, L, H·D) where D is whole lane tiles (a
    # free reshape); a narrower head cannot be a block, so heads move to the
    # front, (B·H, L, D)
    in_lanes = d % _LANE == 0

    def rows(x):
        n = x.shape[2]
        x = (x.reshape(b, L, n * d) if in_lanes
             else jnp.moveaxis(x, 2, 1).reshape(b * n, L, d))
        if L_pad != L:
            x = jnp.pad(x, ((0, 0), (0, L_pad - L), (0, 0)))
        return x

    cfg = _Call(float(scale), block_q, block_k, b, h, hk, d, in_lanes,
                bool(interpret))
    out = _flash_core(cfg, rows(q), rows(k), rows(v))[:, :L]
    if in_lanes:
        return out.reshape(b, L, h, d)
    return jnp.moveaxis(out.reshape(b, h, L, d), 1, 2)
