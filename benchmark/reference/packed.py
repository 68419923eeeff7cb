"""Packed weights -> float32, written from the formats' descriptions and
not by calling the program's decoders, so that a fault in
``quant.int8.decode`` or ``quant.nf4.dequantize`` moves the system under
test and not the reference (``benchmark/tests`` holds the two together on
random tensors at the parent commit).

int8 (``quant/int8.py::Int8Tensor``): per-output-channel symmetric,
``w = q * scale`` with ``q`` (in, out) int8 and ``scale`` (out,) float32.

NF4 (``quant/nf4.py::NF4Tensor``; QLoRA, Dettmers et al. 2023, as
bitsandbytes stores it): blocks of 64 weights, each scaled by its absmax
into [-1, 1] and snapped to the 16 normal-float quantiles; two 4-bit codes
to a byte; the absmax stream itself stored as uint8 in blocks of 256 with
a float32 scale per block and one float32 mean offset. Layout ``kblock``
(every (K, N) matmul kernel): absmax blocks run along K, ``packed[k, i]``
holds ``code[k, i]`` in its high nibble and ``code[k, N/2 + i]`` in its
low one, ``absmax`` is (K/64, N). Layout ``flat``: row-major blocks of 64,
adjacent codes in one byte, high nibble first.
"""

from __future__ import annotations

import math

import jax.numpy as jnp

# The NF4 data type of the QLoRA paper, appendix E.
NF4_QUANTILES = (
    -1.0, -0.6961928009986877, -0.5250730514526367, -0.39491748809814453,
    -0.28444138169288635, -0.18477343022823334, -0.09105003625154495, 0.0,
    0.07958029955625534, 0.16093020141124725, 0.24611230194568634,
    0.33791524171829224, 0.44070982933044434, 0.5626170039176941,
    0.7229568362236023, 1.0)
WEIGHTS_PER_ABSMAX = 64
ABSMAX_PER_SCALE = 256


def int8_to_f32(t):
    return t.q.astype(jnp.float32) * t.scale.astype(jnp.float32)[..., None, :]


def nf4_to_f32(t):
    table = jnp.asarray(NF4_QUANTILES, jnp.float32)
    n_absmax = t.absmax_q.shape[0]
    scale = jnp.repeat(t.absmax_scale.astype(jnp.float32),
                       ABSMAX_PER_SCALE)[:n_absmax]
    absmax = ((t.absmax_q.astype(jnp.float32) - 128.0) * scale
              + t.absmax_offset.astype(jnp.float32))
    high = (t.packed >> 4).astype(jnp.int32)
    low = (t.packed & 0xF).astype(jnp.int32)
    if t.layout == "kblock":
        k, n = t.shape
        values = table[jnp.concatenate([high, low], axis=1)]       # (K, N)
        per_row = jnp.repeat(absmax.reshape(k // WEIGHTS_PER_ABSMAX, n),
                             WEIGHTS_PER_ABSMAX, axis=0)
        return values * per_row
    if t.layout != "flat":
        raise ValueError(f"unknown NF4 layout {t.layout!r}")
    values = table[jnp.stack([high.reshape(-1), low.reshape(-1)],
                             axis=1).reshape(-1)]
    size = math.prod(t.shape)
    per_weight = jnp.repeat(absmax, WEIGHTS_PER_ABSMAX)[:values.shape[0]]
    return (values * per_weight)[:size].reshape(t.shape)
