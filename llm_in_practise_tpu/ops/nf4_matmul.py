"""Pallas TPU fused NF4 dequant-matmul — the bitsandbytes kernel, TPU-shaped.

The reference's QLoRA forward runs bitsandbytes CUDA kernels that
dequantize the NF4 base on the fly inside the matmul
(``Fine-Tuning/qwen3-14b-qlora-dist-deepspeed.py:101-107``). The pure-JAX
path (:func:`llm_in_practise_tpu.quant.nf4.dequantize`) materializes the
bf16 weight in HBM first — 4x the weight traffic of the 4-bit stream. This
kernel keeps the weight packed all the way into VMEM and dequantizes tiles
right before the MXU dot, shaped by what Mosaic actually lowers:

- **Layout** (``NF4Tensor`` ``"kblock"``): absmax blocks along K (bnb
  parity — its 64-blocks run along torch's ``in`` dim), absmax ``(K//64,
  N)``; nibbles pair column ``i`` with column ``N//2 + i`` (split-half), so
  hi/lo unpack yields two *contiguous column halves* — no lane interleave,
  which Mosaic won't lower. The kernel computes the two halves as two MXU
  dots into a ``(bm, 2, bnh)`` output block; ``reshape(M, N)`` outside is
  the identity column order.
- **Scales**: the ``(bk//64, bnh)`` absmax tile expands to ``(bk, bnh)``
  with a broadcast-reshape along sublanes (supported), never a gather.
- **Codebook**: the 16-entry NF4 table is a 4-level binary select tree on
  the code bits (15 vectorized selects) — TPU-friendly where a 16-entry
  gather is not.
- **Pipeline**: grid ``(M/bm, NH/bnh, K/bk)``, K innermost; f32
  accumulators persist in VMEM scratch across K steps.
- **Backward** (QLoRA: base frozen, gradient flows to x only):
  ``dx = dy @ dequant(W)^T`` streams the same packed tiles, so the bf16
  weight never exists in HBM in either direction.

On non-TPU backends the kernels run in Pallas interpreter mode (same
logic, CPU-testable); :func:`nf4_matmul` falls back to dequant+matmul for
flat-layout tensors and shapes the tiling can't cover, and counts each
such trace in :data:`XLA_FALLBACKS`.
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_in_practise_tpu.ops.attention import interpret_default
from llm_in_practise_tpu.quant import nf4
from llm_in_practise_tpu.quant.nf4 import NF4Tensor

_NF4_VALS = tuple(float(v) for v in np.asarray(nf4.NF4_CODE))


#: Traces that left a fused kernel for plain XLA dequant+matmul because
#: the tiling plan was ``None`` (flat-layout tensors, dims that no
#: 128-multiple block divides), by kernel name. A transformer matmul at
#: published widths must never land here: ``chip_smoke.py`` asserts it
#: stays empty.
XLA_FALLBACKS: collections.Counter = collections.Counter()


def _codes_to_vals(codes):
    """16-entry NF4 codebook lookup as a binary select tree (int32 → f32)."""
    vals = [jnp.full(codes.shape, v, jnp.float32) for v in _NF4_VALS]
    for bit in range(4):
        b = ((codes >> bit) & 1) == 1
        vals = [jnp.where(b, vals[2 * j + 1], vals[2 * j])
                for j in range(len(vals) // 2)]
    return vals[0]


def _expand_scale(am, block_k, block_nh):
    """(bk//64, bnh) absmax → (bk, bnh) by repeating each row BLOCK times
    (broadcast + leading-dim merge — the Mosaic-supported expansion)."""
    g = block_k // nf4.BLOCK
    return jnp.broadcast_to(
        am[:, None, :], (g, nf4.BLOCK, block_nh)
    ).reshape(block_k, block_nh)


def _dequant_halves(p, am_hi, am_lo, block_k, block_nh):
    """packed (bk, bnh) + absmax halves → (W_hi, W_lo), each (bk, bnh)."""
    pi = p.astype(jnp.int32)
    w_hi = _codes_to_vals((pi >> 4) & 0xF) * _expand_scale(am_hi, block_k, block_nh)
    w_lo = _codes_to_vals(pi & 0xF) * _expand_scale(am_lo, block_k, block_nh)
    return w_hi, w_lo


def _fwd_kernel(x_ref, wp_ref, am_ref, o_ref, acc_hi, acc_lo,
                *, block_m, block_nh, block_k):
    """o[m, {hi,lo}, nh] = Σ_k x[m, k]·W[k, ·]; grid (m, nh, k), k innermost."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc_hi[...] = jnp.zeros_like(acc_hi)
        acc_lo[...] = jnp.zeros_like(acc_lo)

    w_hi, w_lo = _dequant_halves(
        wp_ref[...], am_ref[:, 0, :], am_ref[:, 1, :], block_k, block_nh)
    x = x_ref[...].astype(jnp.bfloat16)
    # one wide MXU dot over the lane-concatenated halves
    w = jnp.concatenate([w_hi, w_lo], axis=1).astype(jnp.bfloat16)
    acc = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    acc_hi[...] += acc[:, :block_nh]
    acc_lo[...] += acc[:, block_nh:]

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        o_ref[:, 0, :] = acc_hi[...].astype(o_ref.dtype)
        o_ref[:, 1, :] = acc_lo[...].astype(o_ref.dtype)


def _bwd_kernel(dy_ref, wp_ref, am_ref, dx_ref, acc_ref,
                *, block_m, block_nh, block_k):
    """dx[m, k] = Σ_n dy[m, n]·W[k, n]; grid (m, k, nh), nh innermost."""
    ni = pl.program_id(2)

    @pl.when(ni == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w_hi, w_lo = _dequant_halves(
        wp_ref[...], am_ref[:, 0, :], am_ref[:, 1, :], block_k, block_nh)
    dot_t = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_ref[...] += dot_t(dy_ref[:, 0, :].astype(jnp.bfloat16),
                          w_hi.astype(jnp.bfloat16))
    acc_ref[...] += dot_t(dy_ref[:, 1, :].astype(jnp.bfloat16),
                          w_lo.astype(jnp.bfloat16))

    @pl.when(ni == pl.num_programs(2) - 1)
    def _():
        dx_ref[...] = acc_ref[...].astype(dx_ref.dtype)


def _pick_block(dim: int, target: int) -> int:
    """Largest divisor of ``dim`` ≤ target that is a multiple of 128."""
    for cand in range(min(target, dim) // 128 * 128, 127, -128):
        if dim % cand == 0:
            return cand
    return 0


def _plan(t: NF4Tensor, blocks, m: int = 128):
    """Resolve (bm, bnh, bk) tile sizes; None → caller falls back."""
    if t.layout != "kblock":
        return None
    k, n = t.shape
    if blocks is not None:
        bm, bnh, bk = blocks
    else:
        bnh = _pick_block(n // 2, 512)
        bk = _pick_block(k, 512)
        bm = 512 if m >= 512 else 256 if m >= 256 else 128
        if not bnh or not bk or bk % nf4.BLOCK:
            return None
    if (n // 2) % bnh or k % bk or bk % nf4.BLOCK:
        return None
    return bm, bnh, bk


def _call_fwd(x2, packed, absmax3, *, bm, bnh, bk, out_dtype, interpret):
    m, k = x2.shape
    nh = packed.shape[1]
    grid = (m // bm, nh // bnh, k // bk)
    kernel = functools.partial(
        _fwd_kernel, block_m=bm, block_nh=bnh, block_k=bk)
    out3 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bnh), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk // nf4.BLOCK, 2, bnh),
                         lambda i, j, kk: (kk, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, 2, bnh), lambda i, j, kk: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((m, 2, nh), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bnh), jnp.float32),
                        pltpu.VMEM((bm, bnh), jnp.float32)],
        interpret=interpret,
    )(x2, packed, absmax3)
    # (M, 2, NH) row-major == [cols 0..NH) then [NH..N) — identity order
    return out3.reshape(m, 2 * nh)


def _call_bwd(dy2, packed, absmax3, *, bm, bnh, bk, out_dtype, interpret):
    m, n = dy2.shape
    k, nh = packed.shape
    grid = (m // bm, k // bk, nh // bnh)
    kernel = functools.partial(
        _bwd_kernel, block_m=bm, block_nh=bnh, block_k=bk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, 2, bnh), lambda i, kk, j: (i, 0, j)),
            pl.BlockSpec((bk, bnh), lambda i, kk, j: (kk, j)),
            pl.BlockSpec((bk // nf4.BLOCK, 2, bnh),
                         lambda i, kk, j: (kk, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, kk, j: (i, kk)),
        out_shape=jax.ShapeDtypeStruct((m, k), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
        interpret=interpret,
    )(dy2.reshape(m, 2, n // 2), packed, absmax3)


def _layout_arrays(t: NF4Tensor):
    packed, absmax = nf4.kblock_arrays(t)       # (K, NH) u8, (K//64, N) f32
    n = t.shape[1]
    absmax3 = absmax.reshape(-1, 2, n // 2)     # [:, 0]=hi half, [:, 1]=lo
    return packed, absmax3


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def nf4_matmul(x, t: NF4Tensor, out_dtype=None, blocks=None, interpret=None):
    """``x @ dequant(t)`` with the weight streamed in 4-bit form.

    x: (..., K); t: NF4Tensor of shape (K, N). Returns (..., N). The base is
    a frozen constant (QLoRA): the VJP propagates to ``x`` only.
    """
    return _nf4_matmul_fwd(x, t, out_dtype, blocks, interpret)[0]


def _nf4_matmul_fwd(x, t, out_dtype, blocks, interpret):
    out_dtype = out_dtype or x.dtype
    interpret = interpret_default() if interpret is None else interpret
    *lead, k = x.shape
    n = t.shape[1]
    m = int(np.prod(lead)) if lead else 1
    plan = _plan(t, blocks, m)
    if plan is None:
        XLA_FALLBACKS["nf4"] += 1
        out = x @ nf4.dequantize(t, jnp.bfloat16).astype(x.dtype)
        return out.astype(out_dtype), (x.shape, jnp.zeros((0,), x.dtype), t, None)
    bm, bnh, bk = plan
    x2 = x.reshape(m, k)
    pad_m = (-m) % bm
    if pad_m:
        x2 = jnp.pad(x2, ((0, pad_m), (0, 0)))
    packed, absmax3 = _layout_arrays(t)
    out = _call_fwd(x2, packed, absmax3, bm=bm, bnh=bnh, bk=bk,
                    out_dtype=out_dtype, interpret=interpret)
    return out[:m].reshape(*lead, n), (x.shape, jnp.zeros((0,), x.dtype), t, plan)


def _nf4_matmul_bwd(out_dtype, blocks, interpret, res, dy):
    x_shape, dtype_carrier, t, plan = res
    x_dtype = dtype_carrier.dtype
    interpret = interpret_default() if interpret is None else interpret
    *lead, k = x_shape
    n = t.shape[1]
    if plan is None:
        dx = dy @ nf4.dequantize(t, jnp.bfloat16).astype(dy.dtype).T
        return (dx.astype(x_dtype).reshape(x_shape), None)
    bm, bnh, bk = plan
    m = int(np.prod(lead)) if lead else 1
    dy2 = dy.reshape(m, n)
    pad_m = (-m) % bm
    if pad_m:
        dy2 = jnp.pad(dy2, ((0, pad_m), (0, 0)))
    packed, absmax3 = _layout_arrays(t)
    dx = _call_bwd(dy2, packed, absmax3, bm=bm, bnh=bnh, bk=bk,
                   out_dtype=x_dtype, interpret=interpret)
    return (dx[:m].reshape(x_shape), None)


nf4_matmul.defvjp(_nf4_matmul_fwd, _nf4_matmul_bwd)
