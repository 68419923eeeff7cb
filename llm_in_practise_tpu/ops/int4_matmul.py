"""Pallas TPU fused W4A16 matmul — the GPTQ/AWQ serving kernel.

The reference serves its GPTQ/AWQ exports through vLLM's W4A16 CUDA
kernels (Marlin — ``Quantization/LLM-Compressor/GPTQ/eval_qwen3_4b_gptq.py:
11-21`` loads ``quantization="compressed-tensors"``). This is the TPU
counterpart over the in-tree :class:`~llm_in_practise_tpu.quant.int4.
Int4Tensor` format (groups along K, packed ``(K//2, N)`` with adjacent-K
nibble pairs).

Mosaic won't lower the sublane interleave that unpacking adjacent-K pairs
wants, so the contraction splits instead: ``Σ_k x[k]·W[k] =
Σ_i x[2i]·W_hi[i] + Σ_i x[2i+1]·W_lo[i]`` — the activations are split
into even/odd K columns *outside* the kernel (cheap, activation-sized),
and each packed byte tile feeds two MXU dots, read once. Group scales and
zero-points expand along sublanes with the broadcast-reshape Mosaic does
support (both nibble halves of a byte row share a group when
``group_size`` is even, which every real group size is).

``int4_matmul`` is a drop-in for :func:`..quant.int4.dequant_matmul`:
same math, but the bf16 weight never materializes in HBM. The custom VJP
propagates to ``x`` only (quantized weights are frozen exports).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_in_practise_tpu.ops.attention import interpret_default
from llm_in_practise_tpu.ops.nf4_matmul import XLA_FALLBACKS, _pick_block
from llm_in_practise_tpu.quant import int4
from llm_in_practise_tpu.quant.int4 import Int4Tensor


def _expand_groups(v, rows, cols):
    """(rows//r, cols) per-group values → (rows, cols) row-repeated."""
    g = v.shape[0]
    rep = rows // g
    return jnp.broadcast_to(v[:, None, :], (g, rep, cols)).reshape(rows, cols)


def _dequant_halves(p, scales, zeros, block_kh, block_n):
    """packed (bkh, bn) + group params → (W_hi, W_lo) f32, each (bkh, bn).

    Row ``i`` of the packed tile holds codes for K rows ``2i`` (hi nibble)
    and ``2i+1`` (lo); both share the group of row ``i`` since the group
    size is even.
    """
    pi = p.astype(jnp.int32)
    s = _expand_groups(scales, block_kh, block_n)
    z = _expand_groups(zeros, block_kh, block_n)
    w_hi = (((pi >> 4) & 0xF).astype(jnp.float32) - z) * s
    w_lo = ((pi & 0xF).astype(jnp.float32) - z) * s
    return w_hi, w_lo


def _fwd_kernel(xe_ref, xo_ref, wp_ref, s_ref, z_ref, o_ref, acc_ref,
                *, block_m, block_n, block_kh):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w_hi, w_lo = _dequant_halves(
        wp_ref[...], s_ref[...], z_ref[...], block_kh, block_n)
    dot = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_ref[...] += dot(xe_ref[...].astype(jnp.bfloat16),
                        w_hi.astype(jnp.bfloat16))
    acc_ref[...] += dot(xo_ref[...].astype(jnp.bfloat16),
                        w_lo.astype(jnp.bfloat16))

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _bwd_kernel(dy_ref, wp_ref, s_ref, z_ref, dxe_ref, dxo_ref,
                acc_e, acc_o, *, block_m, block_n, block_kh):
    ni = pl.program_id(2)

    @pl.when(ni == 0)
    def _():
        acc_e[...] = jnp.zeros_like(acc_e)
        acc_o[...] = jnp.zeros_like(acc_o)

    w_hi, w_lo = _dequant_halves(
        wp_ref[...], s_ref[...], z_ref[...], block_kh, block_n)
    dot_t = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dy = dy_ref[...].astype(jnp.bfloat16)
    acc_e[...] += dot_t(dy, w_hi.astype(jnp.bfloat16))
    acc_o[...] += dot_t(dy, w_lo.astype(jnp.bfloat16))

    @pl.when(ni == pl.num_programs(2) - 1)
    def _():
        dxe_ref[...] = acc_e[...].astype(dxe_ref.dtype)
        dxo_ref[...] = acc_o[...].astype(dxo_ref.dtype)


def _plan(t: Int4Tensor, m: int):
    k, n = t.shape
    gs = t.group_size
    if k % 2 or gs % 2 or k % gs:
        return None
    kh, gh = k // 2, gs // 2
    bn = _pick_block(n, 512)
    bkh = _pick_block(kh, 512)
    bm = 512 if m >= 512 else 256 if m >= 256 else 128
    if not bn or not bkh or bkh % gh:
        return None
    return bm, bn, bkh, gh


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def int4_matmul(x, t: Int4Tensor, out_dtype=None, interpret=None):
    """``x @ decode(t)`` streaming the weight in packed int4 form.

    x: (..., K); t: Int4Tensor (K, N). Falls back to dequant+matmul for
    shapes the tiling can't cover. VJP propagates to ``x`` only.
    """
    return _int4_matmul_fwd(x, t, out_dtype, interpret)[0]


def _int4_matmul_fwd(x, t, out_dtype, interpret):
    out_dtype = out_dtype or x.dtype
    interpret = interpret_default() if interpret is None else interpret
    *lead, k = x.shape
    n = t.shape[1]
    m = int(np.prod(lead)) if lead else 1
    plan = _plan(t, m)
    if plan is None:
        XLA_FALLBACKS["int4"] += 1
        out = x @ int4.decode(t, jnp.bfloat16).astype(x.dtype)
        return out.astype(out_dtype), (x.shape, jnp.zeros((0,), x.dtype), t, None)
    bm, bn, bkh, gh = plan
    kh = k // 2
    x2 = x.reshape(m, k)
    pad_m = (-m) % bm
    if pad_m:
        x2 = jnp.pad(x2, ((0, pad_m), (0, 0)))
    x3 = x2.reshape(-1, kh, 2)
    xe, xo = x3[:, :, 0], x3[:, :, 1]
    grid = (x2.shape[0] // bm, n // bn, kh // bkh)
    kernel = functools.partial(
        _fwd_kernel, block_m=bm, block_n=bn, block_kh=bkh)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bkh), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bm, bkh), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bkh, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bkh // gh, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bkh // gh, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((x2.shape[0], n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xe, xo, t.packed, t.scales.astype(jnp.float32),
      t.zeros.astype(jnp.float32))
    return (out[:m].reshape(*lead, n),
            (x.shape, jnp.zeros((0,), x.dtype), t, plan))


def _int4_matmul_bwd(out_dtype, interpret, res, dy):
    x_shape, dtype_carrier, t, plan = res
    x_dtype = dtype_carrier.dtype
    interpret = interpret_default() if interpret is None else interpret
    *lead, k = x_shape
    n = t.shape[1]
    if plan is None:
        dx = dy @ int4.decode(t, jnp.bfloat16).astype(dy.dtype).T
        return (dx.astype(x_dtype).reshape(x_shape), None)
    bm, bn, bkh, gh = plan
    kh = k // 2
    m = int(np.prod(lead)) if lead else 1
    dy2 = dy.reshape(m, n)
    pad_m = (-m) % bm
    if pad_m:
        dy2 = jnp.pad(dy2, ((0, pad_m), (0, 0)))
    grid = (dy2.shape[0] // bm, kh // bkh, n // bn)
    kernel = functools.partial(
        _bwd_kernel, block_m=bm, block_n=bn, block_kh=bkh)
    dxe, dxo = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, kk, j: (i, j)),
            pl.BlockSpec((bkh, bn), lambda i, kk, j: (kk, j)),
            pl.BlockSpec((bkh // gh, bn), lambda i, kk, j: (kk, j)),
            pl.BlockSpec((bkh // gh, bn), lambda i, kk, j: (kk, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bkh), lambda i, kk, j: (i, kk)),
            pl.BlockSpec((bm, bkh), lambda i, kk, j: (i, kk)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((dy2.shape[0], kh), x_dtype),
            jax.ShapeDtypeStruct((dy2.shape[0], kh), x_dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bkh), jnp.float32),
                        pltpu.VMEM((bm, bkh), jnp.float32)],
        interpret=interpret,
    )(dy2, t.packed, t.scales.astype(jnp.float32),
      t.zeros.astype(jnp.float32))
    dx = jnp.stack([dxe, dxo], axis=-1).reshape(dy2.shape[0], k)
    return (dx[:m].astype(x_dtype).reshape(x_shape), None)


int4_matmul.defvjp(_int4_matmul_fwd, _int4_matmul_bwd)
