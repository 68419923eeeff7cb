"""Mamba-1's selective scan with a CARRIED state (arXiv:2312.00752), for
the serving path of ``models/phi4flash.py``: a chunk of a prompt starts
from the slot's state and leaves the state of its last real position.

Per channel ``c`` and state ``n`` (``S`` float32, ``A = -exp(A_log)``):

    S_t[n, c] = exp(dt_t[c] * A[n, c]) * S_{t-1}[n, c] + dt_t[c] * x_t[c] * B_t[n]
    y_t[c]    = sum_n S_t[n, c] * C_t[n] + D[c] * x_t[c]

**The state is kept states-major**, ``(N, channels)``: the channels (5,120)
are the chip's lanes, whole tiles; channels-major ``(channels, 16)`` would
pad sixteen floats to a lane tile of 128 and hold (and move) eight times
the bytes.

**A position that is not real does not advance the state**: the callers
set ``dt`` to 0 there, and ``exp(0 * A) * S + 0 * x * B`` is ``S`` bit for
bit, so neither form here takes a ``valid`` of its own
(:func:`mask_steps`).

- :func:`chunk_scan`: the Pallas kernel, :data:`CHUNK_KERNEL` on the device
  plane. Grid (batch, channel blocks, time blocks), time innermost; a
  channel block is ``(block_c / 128, 128)``, ONE float32 register a state
  at 1,024 channels, so the block's sixteen states ride the loop over a time
  block in registers and the output block of the state stays resident over
  the time axis. ``B_t[n]`` and ``C_t[n]`` are scalars, read from scalar
  memory. Every state element costs one ``exp`` and six multiply-adds a
  position and no matrix unit: the kernel is bound by the vector and
  transcendental units, not by bytes (tools/ssm_bakeoff.py has the
  ladder).
- :func:`chunk_scan_reference`: the same recurrence as a ``lax.scan`` over
  positions, for the CPU (tests, the rehearsal) and as the kernel's check.
- :func:`state_update`: ONE position a row in plain XLA under
  :data:`UPDATE_SCOPE`, the decode plane's form.
- :func:`selective_scan`: the three behind one call, chosen by the length
  and the platform.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_in_practise_tpu.ops.attention import _on_tpu

CHUNK_KERNEL = "ssm_chunk_scan"
UPDATE_SCOPE = "ssm_state_update"
_LANE = 128
# tiles (tools/ssm_bakeoff.py): positions a grid step walks, channels a
# block holds (1,024 = one float32 register a state)
BLOCK_T, BLOCK_C = 256, 1024


def mask_steps(dt, valid):
    """``dt`` (B, L, C) with the positions at or past ``valid`` (B,) set
    to 0: those steps leave the state as it is."""
    if valid is None:
        return dt
    live = jnp.arange(dt.shape[1])[None, :] < valid[:, None]
    return jnp.where(live[:, :, None], dt, 0.0)


def _scan_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, d_ref, s0_ref, y_ref,
                 s_ref, *, block_t, n_state, unroll):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _():
        s_ref[...] = s0_ref[...]

    a = [a_ref[n] for n in range(n_state)]
    skip = d_ref[...]

    def steps(i, state):
        # ``unroll`` positions a trip, written out: the loop's own
        # ``unroll`` is all or nothing here
        for j in range(unroll):
            t = i * unroll + j
            dt = dt_ref[t]
            x = x_ref[t]
            u = dt * x
            y = skip * x
            out = []
            for n in range(n_state):
                s = jnp.exp(dt * a[n]) * state[n] + u * b_ref[t, n]
                y = y + s * c_ref[t, n]
                out.append(s)
            y_ref[t] = y
            state = tuple(out)
        return state

    state = jax.lax.fori_loop(
        0, block_t // unroll, steps,
        tuple(s_ref[n] for n in range(n_state)))
    for n in range(n_state):
        s_ref[n] = state[n]


def can_tile(length: int, channels: int) -> bool:
    """Whether :func:`chunk_scan` takes these sizes: whole lane tiles of
    channels, a length of whole sublane tiles."""
    return channels % _LANE == 0 and length % 8 == 0


def chunk_scan(x, dt, b, c, a, d, s0, *, block_t: int | None = None,
               block_c: int | None = None, unroll: int = 4,
               interpret: bool | None = None):
    """``x`` (B, L, C) the convolved input, ``dt`` (B, L, C) float32 (0
    where a position is not real), ``b`` / ``c`` (B, L, N) float32, ``a``
    (N, C) float32 (negative), ``d`` (C,), ``s0`` (B, N, C) float32.
    Returns ``y`` (B, L, C) float32 and the state after the last position
    (B, N, C) float32."""
    bt, length, chan = x.shape
    n_state = a.shape[0]
    if not can_tile(length, chan):
        raise ValueError(f"chunk_scan: {length} positions x {chan} channels "
                         "are not whole tiles")
    groups = chan // _LANE
    rows = min((block_c or BLOCK_C) // _LANE, groups)
    while groups % rows:
        rows -= 1
    block_t = min(block_t or BLOCK_T, length)
    while length % block_t:
        block_t //= 2
    unroll = max(u for u in (1, 2, 4, 8) if u <= unroll and block_t % u == 0)
    f32 = jnp.float32

    def lanes(t):       # (..., C) -> (..., C / 128, 128)
        return t.astype(f32).reshape(t.shape[:-1] + (groups, _LANE))

    scalars = pl.BlockSpec((None, block_t, n_state),
                           lambda bi, ci, ti: (bi, ti, 0),
                           memory_space=pltpu.SMEM)
    steps = pl.BlockSpec((None, block_t, rows, _LANE),
                         lambda bi, ci, ti: (bi, ti, ci, 0))
    state = pl.BlockSpec((None, n_state, rows, _LANE),
                         lambda bi, ci, ti: (bi, 0, ci, 0))
    y, s1 = pl.pallas_call(
        functools.partial(_scan_kernel, block_t=block_t, n_state=n_state,
                          unroll=unroll),
        grid=(bt, groups // rows, length // block_t),
        in_specs=[
            scalars, scalars, steps, steps,
            pl.BlockSpec((n_state, rows, _LANE),
                         lambda bi, ci, ti: (0, ci, 0)),
            pl.BlockSpec((rows, _LANE), lambda bi, ci, ti: (ci, 0)),
            state,
        ],
        out_specs=[steps, state],
        out_shape=[
            jax.ShapeDtypeStruct((bt, length, groups, _LANE), f32),
            jax.ShapeDtypeStruct((bt, n_state, groups, _LANE), f32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=(not _on_tpu()) if interpret is None else interpret,
        name=CHUNK_KERNEL,
    )(b.astype(f32), c.astype(f32), lanes(x), lanes(dt), lanes(a), lanes(d),
      lanes(s0))
    return y.reshape(bt, length, chan), s1.reshape(bt, n_state, chan)


def chunk_scan_reference(x, dt, b, c, a, d, s0):
    """:func:`chunk_scan`'s recurrence, one position a ``lax.scan`` step."""
    f32 = jnp.float32
    x, dt, b, c = (t.astype(f32) for t in (x, dt, b, c))
    a, d = a.astype(f32), d.astype(f32)

    def step(s, at):
        xt, dtt, bt, ct = at                    # (B, C) (B, C) (B, N) (B, N)
        s = (jnp.exp(dtt[:, None, :] * a[None]) * s
             + (dtt * xt)[:, None, :] * bt[:, :, None])
        return s, jnp.sum(s * ct[:, :, None], axis=1) + d * xt

    s1, y = jax.lax.scan(step, s0.astype(f32), tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), s1


def state_update(x, dt, b, c, a, d, s0):
    """One position a row: ``x`` / ``dt`` (B, C), ``b`` / ``c`` (B, N),
    ``s0`` (B, N, C). Returns ``(y (B, C), state (B, N, C))``, float32."""
    f32 = jnp.float32
    with jax.named_scope(UPDATE_SCOPE):
        x, dt = x.astype(f32), dt.astype(f32)
        s = (jnp.exp(dt[:, None, :] * a.astype(f32)[None]) * s0.astype(f32)
             + (dt * x)[:, None, :] * b.astype(f32)[:, :, None])
        y = jnp.sum(s * c.astype(f32)[:, :, None], axis=1) + d.astype(f32) * x
    return y, s


def selective_scan(x, dt, b, c, a, d, s0):
    """The scan of ``L`` positions from ``s0``: :func:`state_update` for
    one, else the kernel on the TPU where the sizes are whole tiles, else
    the ``lax.scan``."""
    if x.shape[1] == 1:
        y, s1 = state_update(x[:, 0], dt[:, 0], b[:, 0], c[:, 0], a, d, s0)
        return y[:, None, :], s1
    if _on_tpu() and can_tile(x.shape[1], x.shape[2]):
        with jax.named_scope(CHUNK_KERNEL):
            return chunk_scan(x, dt, b, c, a, d, s0)
    return chunk_scan_reference(x, dt, b, c, a, d, s0)
