"""Pallas TPU fused W8A16 matmul — int8 weights streamed at memory speed.

The 4-bit kernels (:mod:`.nf4_matmul`, :mod:`.int4_matmul`) pay a
per-element VPU tax in the inner loop — nibble unpack plus codebook
select-tree (NF4) or affine rescale (int4) — which measured as the
decode bottleneck at 8B scale (``docs/perf.md`` Finding 9: ~4% of HBM
peak). Int8 removes the whole tax: the weight tile loads as int8,
converts to bf16 with ONE native cast (int8 magnitudes ≤ 127 are exact
in bf16), and feeds the MXU; the per-out-channel scale applies to the
f32 accumulator once per OUTPUT element after the K loop, because
column-wise scaling commutes with the contraction
(``x @ (q·s) == (x @ q)·s``). The backward folds the scale into ``dy``
outside the kernel (``dx = (dy·s) @ qᵀ``), so neither direction ever
expands scales in the inner loop and the bf16 weight never exists in
HBM.

Grid/pipeline mirror the sibling kernels: ``(M/bm, N/bn, K/bk)`` with K
innermost and an f32 VMEM accumulator. On non-TPU backends the kernel
runs in Pallas interpreter mode; shapes the tiling can't cover fall back
to dequant+matmul. The custom VJP propagates to ``x`` only (quantized
weights are frozen exports).

**Status: probe infrastructure, not a production path.** With dequant
reduced to one convert, XLA's own fusion schedules the thin decode
matmul BETTER than this hand tiling (77 vs 100 ms/token on the 8B
16-slot step; tile-size sweeps flat — ``INT8_TILE_PROBE.json``,
``docs/perf.md`` Finding 11), so ``peft/fused.py::fused_kernel_matmul``
deliberately routes Int8Tensor to the XLA dequant matmul even on the
kernels path. The kernel stays in-tree to keep that negative result
reproducible (``tools/tpu_int8_tile_probe.py``) and is smoke-tested on
real TPU by ``tests/test_int8.py::test_kernel_matmul_on_tpu`` (skipped
elsewhere).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_in_practise_tpu.ops.attention import interpret_default
from llm_in_practise_tpu.ops.nf4_matmul import _pick_block
from llm_in_practise_tpu.quant import int8
from llm_in_practise_tpu.quant.int8 import Int8Tensor


def _fwd_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref,
                *, block_m, block_n, block_k):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = q_ref[...].astype(jnp.bfloat16)          # exact for |q| <= 127
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.bfloat16), w,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


def _bwd_kernel(dys_ref, q_ref, dx_ref, acc_ref,
                *, block_m, block_n, block_k):
    """dx[m, k] = Σ_n (dy·s)[m, n] · q[k, n]; grid (m, k, n), n innermost.
    The scale is already folded into ``dys`` by the caller."""
    ni = pl.program_id(2)

    @pl.when(ni == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        dys_ref[...].astype(jnp.bfloat16), q_ref[...].astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )

    @pl.when(ni == pl.num_programs(2) - 1)
    def _():
        dx_ref[...] = acc_ref[...].astype(dx_ref.dtype)


# Target tile sizes. Tunable at module level (the tile probe tool sweeps
# them): larger tiles cut the program count — the launch/fence overhead
# per grid step is what dominates THIN-activation (decode) matmuls, where
# each weight byte is read exactly once regardless of tiling.
_TGT_N = 512
_TGT_K = 512


def _plan(t: Int8Tensor, m: int):
    if len(t.shape) != 2:
        return None      # stacked 3-D leaves are sliced before use
    k, n = t.shape
    bn = _pick_block(n, _TGT_N)
    bk = _pick_block(k, _TGT_K)
    bm = 512 if m >= 512 else 256 if m >= 256 else 128
    if not bn or not bk:
        return None
    return bm, bn, bk


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def int8_matmul(x, t: Int8Tensor, out_dtype=None, interpret=None):
    """``x @ decode(t)`` with the weight streamed in int8 form.

    x: (..., K); t: Int8Tensor (K, N). Returns (..., N). VJP propagates
    to ``x`` only.
    """
    return _int8_matmul_fwd(x, t, out_dtype, interpret)[0]


def _int8_matmul_fwd(x, t, out_dtype, interpret):
    out_dtype = out_dtype or x.dtype
    interpret = interpret_default() if interpret is None else interpret
    *lead, k = x.shape
    n = t.shape[1]
    m = int(np.prod(lead)) if lead else 1
    plan = _plan(t, m)
    if plan is None:
        out = x @ int8.decode(t, jnp.bfloat16).astype(x.dtype)
        return out.astype(out_dtype), (x.shape, jnp.zeros((0,), x.dtype), t, None)
    bm, bn, bk = plan
    x2 = x.reshape(m, k)
    pad_m = (-m) % bm
    if pad_m:
        x2 = jnp.pad(x2, ((0, pad_m), (0, 0)))
    grid = (x2.shape[0] // bm, n // bn, k // bk)
    kernel = functools.partial(
        _fwd_kernel, block_m=bm, block_n=bn, block_k=bk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((x2.shape[0], n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x2, t.q, t.scale.astype(jnp.float32).reshape(1, n))
    return (out[:m].reshape(*lead, n),
            (x.shape, jnp.zeros((0,), x.dtype), t, plan))


def _int8_matmul_bwd(out_dtype, interpret, res, dy):
    x_shape, dtype_carrier, t, plan = res
    x_dtype = dtype_carrier.dtype
    interpret = interpret_default() if interpret is None else interpret
    *lead, k = x_shape
    n = t.shape[1]
    if plan is None:
        dx = dy @ int8.decode(t, jnp.bfloat16).astype(dy.dtype).T
        return (dx.astype(x_dtype).reshape(x_shape), None)
    bm, bn, bk = plan
    m = int(np.prod(lead)) if lead else 1
    dys = (dy.reshape(m, n).astype(jnp.float32)
           * t.scale.astype(jnp.float32)[None, :])
    pad_m = (-m) % bm
    if pad_m:
        dys = jnp.pad(dys, ((0, pad_m), (0, 0)))
    grid = (dys.shape[0] // bm, k // bk, n // bn)
    kernel = functools.partial(
        _bwd_kernel, block_m=bm, block_n=bn, block_k=bk)
    dx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, kk, j: (i, j)),
            pl.BlockSpec((bk, bn), lambda i, kk, j: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, kk, j: (i, kk)),
        out_shape=jax.ShapeDtypeStruct((dys.shape[0], k), x_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
        interpret=interpret,
    )(dys, t.q)
    return (dx[:m].reshape(x_shape), None)


int8_matmul.defvjp(_int8_matmul_fwd, _int8_matmul_bwd)
