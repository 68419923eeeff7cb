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
dimension (TPU grids execute sequentially, innermost fastest); causally dead
blocks are skipped with ``pl.when``.

Layout: kernels operate on ``(batch·heads, L, D)``; the public entry point
takes the framework-wide ``(B, L, H, D)`` and handles padding to the 128
tile. Causal-only (the only masking the models need — non-causal paths stay
on the dense XLA implementation in ``ops/attention.py``).

On non-TPU backends the kernels run in Pallas interpreter mode so the exact
kernel logic is unit-testable on the 8-device CPU mesh (SURVEY §4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_in_practise_tpu.ops.attention import interpret_default

NEG_INF = -1e30
_LANE = 128
_SUBLANE = 8  # lse/delta carry a replicated sublane dim to satisfy TPU tiling


def _positions(block_q, block_k):
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return rows, cols


# --------------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, block_q, block_k):
    """Grid (bh, n_q, n_kv), kv innermost; acc/m/l scratch persists over kv."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: kv block is live iff its first key position <= last query pos
    @pl.when(ki * block_k <= (qi + 1) * block_q - 1)
    def _():
        q = q_ref[0].astype(jnp.float32) * scale                 # (bq, D)
        kb = k_ref[0].astype(jnp.float32)                        # (bk, D)
        vb = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                        # (bq, bk)
        rows, cols = _positions(block_q, block_k)
        s = jnp.where(ki * block_k + cols <= qi * block_q + rows, s, NEG_INF)
        m_prev = m_ref[:, 0:1]
        l_prev = l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, 0:1] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[:, 0:1] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, vb, preferred_element_type=jnp.float32
        )

    @pl.when(ki == n_kv - 1)
    def _():
        l = jnp.maximum(l_ref[:, 0:1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse = (m_ref[:, 0:1] + jnp.log(l))[:, 0]
        lse_ref[0] = jnp.broadcast_to(lse[None, :], (_SUBLANE, block_q))


def _flash_fwd_call(q, k, v, *, scale, block_q, block_k, interpret):
    bh, L, d = q.shape
    n_q, n_kv = L // block_q, L // block_k
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k
        ),
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, _SUBLANE, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, L, d), q.dtype),
            jax.ShapeDtypeStruct((bh, _SUBLANE, L), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# -------------------------------------------------------------------- backward
def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, block_q, block_k):
    """Grid (bh, n_kv, n_q), q innermost; dk/dv scratch persists over q."""
    ki, qj = pl.program_id(1), pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(qj == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # causal: this q block sees the kv block iff its last query >= first key
    @pl.when((qj + 1) * block_q - 1 >= ki * block_k)
    def _():
        kb = k_ref[0].astype(jnp.float32)                        # (bk, D)
        vb = v_ref[0].astype(jnp.float32)
        qb = q_ref[0].astype(jnp.float32)                        # (bq, D)
        dob = do_ref[0].astype(jnp.float32)
        lse_b = lse_ref[0, 0:1, :].T
        delta_b = delta_ref[0, 0:1, :].T
        s = scale * jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        rows, cols = _positions(block_q, block_k)
        s = jnp.where(ki * block_k + cols <= qj * block_q + rows, s, NEG_INF)
        p = jnp.exp(s - lse_b)                                   # (bq, bk)
        dv_acc[...] += jax.lax.dot_general(
            p, dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_b) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qj == n_q - 1)
    def _():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, scale, block_q, block_k):
    """Grid (bh, n_q, n_kv), kv innermost; dq scratch persists over kv."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(ki * block_k <= (qi + 1) * block_q - 1)
    def _():
        qb = q_ref[0].astype(jnp.float32)
        dob = do_ref[0].astype(jnp.float32)
        lse_b = lse_ref[0, 0:1, :].T
        delta_b = delta_ref[0, 0:1, :].T
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        s = scale * jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        rows, cols = _positions(block_q, block_k)
        s = jnp.where(ki * block_k + cols <= qi * block_q + rows, s, NEG_INF)
        p = jnp.exp(s - lse_b)
        dp = jax.lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_b) * scale
        dq_acc[...] += jax.lax.dot(ds, kb, preferred_element_type=jnp.float32)

    @pl.when(ki == n_kv - 1)
    def _():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd_call(q, k, v, out, lse, do, *, scale, block_q, block_k, interpret):
    bh, L, d = q.shape
    n_q, n_kv = L // block_q, L // block_k
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, None, :], (bh, _SUBLANE, L))

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, block_q=block_q, block_k=block_k
        ),
        grid=(bh, n_kv, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, _SUBLANE, block_q), lambda b, i, j: (b, 0, j)),
            pl.BlockSpec((1, _SUBLANE, block_q), lambda b, i, j: (b, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, L, d), q.dtype),
            jax.ShapeDtypeStruct((bh, L, d), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, block_q=block_q, block_k=block_k
        ),
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, _SUBLANE, block_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, _SUBLANE, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, L, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ------------------------------------------------------------------ custom vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_core(cfg, q, k, v):
    out, _ = _flash_core_fwd(cfg, q, k, v)
    return out


def _flash_core_fwd(cfg, q, k, v):
    scale, block_q, block_k, interpret = cfg
    out, lse = _flash_fwd_call(
        q, k, v, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return out, (q, k, v, out, lse)


def _flash_core_bwd(cfg, res, do):
    scale, block_q, block_k, interpret = cfg
    q, k, v, out, lse = res
    return _flash_bwd_call(
        q, k, v, out, lse, do,
        scale=scale, block_q=block_q, block_k=block_k, interpret=interpret,
    )


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = _LANE,
    block_k: int = _LANE,
    interpret: bool | None = None,
) -> jax.Array:
    """Causal flash attention over ``(B, L, H, D)`` q/k/v.

    Sequence length is padded to the 128 tile internally; padded KV columns
    fall after every real query position so the causal mask excludes them,
    and padded query rows are sliced off on return. ``block_q``/``block_k``
    must divide the padded length.
    """
    if not causal:
        raise NotImplementedError("flash kernel is causal-only; use dense")
    b, L, h, d = q.shape
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError("flash kernel requires identical q/k/v shapes")
    scale = scale if scale is not None else d ** -0.5
    if interpret is None:
        interpret = interpret_default()

    L_pad = max(_LANE, -(-L // _LANE) * _LANE)
    block_q, block_k = min(block_q, L_pad), min(block_k, L_pad)
    if L_pad % block_q or L_pad % block_k:
        raise ValueError(
            f"block_q={block_q}/block_k={block_k} must divide padded length {L_pad}"
        )

    def to3(x):
        x = jnp.moveaxis(x, 2, 1).reshape(b * h, L, d)
        if L_pad != L:
            x = jnp.pad(x, ((0, 0), (0, L_pad - L), (0, 0)))
        return x

    cfg = (float(scale), block_q, block_k, bool(interpret))
    out = _flash_core(cfg, to3(q), to3(k), to3(v))
    out = out[:, :L].reshape(b, h, L, d)
    return jnp.moveaxis(out, 1, 2)
