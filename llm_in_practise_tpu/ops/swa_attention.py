"""Grouped-query attention whose keys are wider than its values, with an
optional BAND (a sliding window) and an optional SINK (MiMo-V2's two layer
kinds, ``models/mimo_v2.py``).

A query at absolute position ``i`` sees the key at ``j`` when ``0 <= j <=
i`` and, in a window layer, ``i - j < window`` (itself and the ``window -
1`` before it). A window layer's head ``h`` has a learned sink logit
``b_h`` that joins the softmax's denominator and adds no value: ``p_ij =
exp(s_ij) / (exp(b_h) + sum_j' exp(s_ij'))``, in code :func:`join` of the
keys' partial result with ``(0, b_h)``.

- :func:`flash_partial`: the Pallas kernel, ``ops/mla_attention.py``'s
  running-softmax kernel (grouped heads, ``Dq != Dv``, absolute starts, a
  log-sum-exp out) with a static ``window``. With a band the key axis of
  the grid is only as long as the blocks a query block's band can touch,
  and the ``kv_map`` names the band's FIRST live block as it names the
  last, so the blocks under the band are neither computed nor fetched. A
  sibling of that kernel and not an option of it: DeepSeek-V3's programs
  keep their lowered text.
- :func:`prefill_attention`: a stretch of queries over ``[cached keys ‖
  the stretch's own]``. A global layer's cached keys are the view the
  stretch was written into; a window layer's are its ring, at most
  ``window`` rows, put in order and attended by the first ``window``
  queries only (dense, a ``window x window`` corner), joined with the
  kernel's result over the stretch's own keys. That corner is dense
  float32 and stays only where it is small (:data:`RING_CORNER_MAX`
  scores a head, read off the shapes): a ring LONGER than a tile (a
  window of 4,096 over 2,048-token chunks, ``models/afmoe.py``) goes
  through the kernel, ``[the ring's live rows in position order ‖ the
  stretch's own keys]`` as ONE stretch of keys under the band
  (:func:`ring_stretch`), the blocks outside the band skipped as ever.
- :func:`decode_attention` / :func:`ring_decode_attention`: one query a
  row, XLA einsums over the gathered view of FLAT rows (``Hk * 192`` and
  ``Hk * 128`` wide, as the pool's pages hold them) / over the ring,
  under :data:`GLOBAL_DECODE_SCOPE` / :data:`WINDOW_DECODE_SCOPE`.
- :func:`paged_decode_attention`: the same one query a row with NO view:
  a Pallas kernel that walks the pool's pages where they lie, to each
  row's true length (a decode program of a model that ``reads_pages``).

**The ring.** A window layer's cache is ``(B, R, Hk, D)`` with ``R =
min(max_len, window)``: row ``p mod R`` holds position ``p``. Which row
holds what follows from the row's index alone: before position ``n`` is
written, row ``r`` holds ``(n - 1) - ((n - 1 - r) mod R)``, live when that
is ``>= 0``. A slot's last tenant's rows are masked by position, never
cleared. :func:`ring_write` writes the last ``R`` REAL positions of a
stretch (``valid`` of its ``L`` are real; padding writes nothing).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_in_practise_tpu.ops.attention import interpret_default
from llm_in_practise_tpu.ops.mla_attention import NEG_INF, join

WINDOW_DECODE_SCOPE = "window_decode_attention"
GLOBAL_DECODE_SCOPE = "global_decode_attention"
# the kernels' names on the device plane
WINDOW_KERNEL = "window_prefill_flash"
GLOBAL_KERNEL = "global_prefill_flash"
# tiles (tools/swa_bakeoff.py): a global layer's chunk against a view, a
# window layer's chunk against its own keys
GLOBAL_BLOCKS = (1024, 1024)
WINDOW_BLOCKS = (256, 256)
# a window layer's chunk over [its ring ‖ its own keys] (a call of its
# own on the device plane), and the tiles of a band LONGER than a tile of
# WINDOW_BLOCKS: a band of 128 half fills a (256, 256) tile, a band of
# 4,096 is 16 of them, and wider key blocks are fewer grid steps
# (tools/swa_bakeoff.py --geometry trinity: 2,048 queries under a band of
# 4,096 over 6,144 keys, folded: 6.48 ms at (256, 256), 4.20 at (256,
# 512), 3.63 at (512, 512), 3.10 at (256, 1024); (512, 1024) does not fit
# the chip's fast memory)
WINDOW_RING_KERNEL = "window_ring_prefill_flash"
LONG_BAND_BLOCKS = (256, 1024)
# the most scores a head that the dense ring corner may hold (min(L,
# window) x ring rows, float32, every head at once): a tile. MiMo-V2's
# 128 x 128 lies under it; 2,048 x 4,096 would be 1.6 GB a layer
RING_CORNER_MAX = 256 * 256
_LANE, _SUBLANE = 128, 8
_PAGED_VMEM = 32 * 1024 * 1024


def window_blocks(window: int) -> tuple[int, int]:
    """The band kernel's tiles by the band's length."""
    return WINDOW_BLOCKS if window <= WINDOW_BLOCKS[1] else LONG_BAND_BLOCKS


def band_blocks(block_q: int, block_k: int, window: int) -> int:
    """Key blocks the band of one query block can touch, whatever the
    alignment of the two starts: its ``block_q + window - 1`` key
    positions begin anywhere inside a block."""
    return (block_q + window - 2) // block_k + 2


def _flash_kernel(qs_ref, ks_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                  acc_ref, m_ref, l_ref, *, scale, block_q, block_k,
                  window, n_key_blocks):
    """Grid (batch, heads or K/V heads, q blocks, key steps), key steps
    innermost; acc / m / l persist over them. A q tile holds ``block_q``
    positions of one query head, or of ALL the query heads of one K/V
    head one after another (``fold`` of them: ``fold * block_q`` rows, the
    K/V block fetched once for the group). Query row ``r`` of q block
    ``qi`` is at ``qs[b] + qi * block_q + r % block_q``; key step ``ki``
    reads key block
    ``first + ki`` (``first`` = the band's first live block, 0 without a
    band), whose column ``c`` is at ``ks[b] + (first + ki) * block_k +
    c``."""
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    n_steps = pl.num_programs(3)
    q0 = qs_ref[b] + qi * block_q
    first = 0
    if window is not None:
        first = jnp.clip((q0 - (window - 1) - ks_ref[b]) // block_k, 0,
                         n_key_blocks - 1)
    kb = first + ki
    k0 = ks_ref[b] + kb * block_k

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when((k0 <= q0 + block_q - 1) & (kb < n_key_blocks))
    def _():
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        tile = q_ref.shape[0]
        # how far each key lies behind its query: the tile's own part is
        # the same every step, the blocks' starts a scalar
        behind = (q0 - k0) + (
            jax.lax.broadcasted_iota(jnp.int32, (tile, block_k), 0) % block_q
            - jax.lax.broadcasted_iota(jnp.int32, (tile, block_k), 1))
        live = behind >= 0
        if window is not None:
            live &= behind < window
        s = jnp.where(live, s, NEG_INF)
        m_prev, l_prev = m_ref[:, 0:1], l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a row whose keys so far are all masked keeps m = NEG_INF: its
        # masked scores must weigh exp(-huge) = 0, not exp(0)
        p = jnp.exp(s - jnp.maximum(m_new, 0.1 * NEG_INF))
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, 0:1] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[:, 0:1] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_steps - 1)
    def _():
        # a row that saw no key of this call keeps m = NEG_INF and l = 0:
        # its output is 0 and its lse ~ -1e30, which the join ignores
        l = jnp.maximum(l_ref[:, 0:1], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse = (m_ref[:, 0:1] + jnp.log(l))[:, 0]
        lse_ref[...] = jnp.broadcast_to(lse[None, :],
                                        (_SUBLANE, lse.shape[0]))


def flash_partial(q, k, v, q_start, k_start, *, scale: float,
                  window: int | None = None, block_q: int | None = None,
                  block_k: int | None = None, fold: bool | None = None,
                  interpret: bool | None = None, name: str | None = None,
                  out_dtype=None):
    """Attention of ``q`` (B, H, Lq, Dq) over ONE stretch of keys ``k``
    (B, Hk, Lk, Dq) / values ``v`` (B, Hk, Lk, Dv), ``H`` a multiple of
    ``Hk``; ``q_start`` / ``k_start`` (B,) are the absolute positions of
    the first query and the first key, neither negative. Returns the
    stretch's own softmax-normalised output (B, H, Lq, Dv) and its
    log-sum-exp (B, H, Lq) float32, for :func:`join`. The output is in
    ``out_dtype`` (default: the queries'; a caller that SUBTRACTS two
    outputs asks for float32: the difference amplifies the rounding).

    ``fold`` (default: under a band): a q tile holds ``block_q`` positions
    of every query head of one K/V head, so a grid step multiplies
    ``group * block_q`` rows by one K/V block. A band's live work a
    (head, q block) is a few small tiles and the grid's steps are what it
    costs: folding makes them ``group`` times fewer
    (tools/swa_bakeoff.py)."""
    b, h, lq, dq = q.shape
    hk, lk, dv = k.shape[1], k.shape[2], v.shape[-1]
    group = h // hk
    fold = (window is not None) if fold is None else fold
    tiles = GLOBAL_BLOCKS if window is None else window_blocks(window)
    block_q = min(block_q or tiles[0], lq)
    block_k = min(block_k or tiles[1], lk)
    if lq % block_q or lk % block_k:
        raise ValueError(f"lengths ({lq}, {lk}) must be multiples of the "
                         f"tiles ({block_q}, {block_k})")
    n_q, n_k = lq // block_q, lk // block_k
    n_steps = n_k if window is None else min(
        n_k, band_blocks(block_q, block_k, window))
    per = group if fold else 1      # query heads a q tile holds
    tile = per * block_q
    if fold:
        # (B, Hk, group, n_q, block_q, D) -> tiles of (group, block_q)
        q = q.reshape(b, hk, group, n_q, block_q, dq).transpose(
            0, 1, 3, 2, 4, 5).reshape(b, hk, n_q * tile, dq)

    def kv_map(bi, hi, i, j, qs, ks):
        # key blocks past the last one this q block can see are never
        # computed: name the last live block again, so they are not
        # fetched either; under a band the steps start at its first
        # live block
        q0 = qs[bi] + i * block_q
        last = jnp.clip((q0 + block_q - 1 - ks[bi]) // block_k, 0, n_k - 1)
        first = 0
        if window is not None:
            first = jnp.clip((q0 - (window - 1) - ks[bi]) // block_k, 0,
                             n_k - 1)
        return (bi, hi if fold else hi // group,
                jnp.minimum(first + j, last), 0)

    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, window=window, n_key_blocks=n_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h // per, n_q, n_steps),
            in_specs=[
                pl.BlockSpec((None, None, tile, dq),
                             lambda bi, hi, i, j, qs, ks: (bi, hi, i, 0)),
                pl.BlockSpec((None, None, block_k, dq), kv_map),
                pl.BlockSpec((None, None, block_k, dv), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((None, None, tile, dv),
                             lambda bi, hi, i, j, qs, ks: (bi, hi, i, 0)),
                pl.BlockSpec((None, None, _SUBLANE, tile),
                             lambda bi, hi, i, j, qs, ks: (bi, hi, 0, i)),
            ],
            scratch_shapes=[
                pltpu.VMEM((tile, dv), jnp.float32),
                pltpu.VMEM((tile, _LANE), jnp.float32),
                pltpu.VMEM((tile, _LANE), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h // per, n_q * tile, dv),
                                 out_dtype or q.dtype),
            jax.ShapeDtypeStruct((b, h // per, _SUBLANE, n_q * tile),
                                 jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret_default() if interpret is None else interpret,
        name=name or (GLOBAL_KERNEL if window is None else WINDOW_KERNEL),
    )(jnp.broadcast_to(jnp.asarray(q_start, jnp.int32), (b,)),
      jnp.broadcast_to(jnp.asarray(k_start, jnp.int32), (b,)), q, k, v)
    lse = lse[:, :, 0, :]
    if fold:
        out = out.reshape(b, hk, n_q, group, block_q, dv).transpose(
            0, 1, 3, 2, 4, 5).reshape(b, h, lq, dv)
        lse = lse.reshape(b, hk, n_q, group, block_q).transpose(
            0, 1, 3, 2, 4).reshape(b, h, lq)
    return out, lse


def key_blocks_visited(q_start: int, k_start: int, lq: int, lk: int, *,
                       window: int | None, block_q: int,
                       block_k: int) -> int:
    """Key blocks :func:`flash_partial` computes for one (batch, head):
    the host's count of the kernel's ``pl.when``, for tests and the
    bake-off."""
    n_k = lk // block_k
    visited = 0
    for i in range(lq // block_q):
        q0 = q_start + i * block_q
        first, steps = 0, n_k
        if window is not None:
            first = min(max((q0 - (window - 1) - k_start) // block_k, 0),
                        n_k - 1)
            steps = min(n_k, band_blocks(block_q, block_k, window))
        for j in range(steps):
            kb = first + j
            if kb < n_k and k_start + kb * block_k <= q0 + block_q - 1:
                visited += 1
    return visited


def _flash_padded(q, k, v, q_start, k_start, *, scale, window, name=None,
                  out_dtype=None):
    """:func:`flash_partial` for (B, L, H, D) operands of any length:
    heads first, lengths padded to whole tiles (a padded key lies past
    every real query; a padded query's row is dropped)."""
    lq, lk = q.shape[1], k.shape[1]
    tiles = GLOBAL_BLOCKS if window is None else window_blocks(window)

    def whole(n, tile):
        return -(-n // tile) * tile if n > tile else -(-n // 8) * 8

    pq, pk = whole(lq, tiles[0]) - lq, whole(lk, tiles[1]) - lk
    pad = lambda a, n: jnp.pad(      # noqa: E731
        a, ((0, 0), (0, n), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    out, lse = flash_partial(pad(q, pq), pad(k, pk), pad(v, pk), q_start,
                             k_start, scale=scale, window=window, name=name,
                             out_dtype=out_dtype)
    return out[:, :, :lq].astype(jnp.float32), lse[:, :, :lq]


def _sink_join(out, lse, sink):
    """The sink takes mass and adds no value. ``out`` (B, H, L, Dv)
    float32, ``lse`` (B, H, L), ``sink`` (H,) or None."""
    if sink is None:
        return out
    b_h = jnp.broadcast_to(sink.astype(jnp.float32)[None, :, None],
                           lse.shape)
    return join(out, lse, jnp.zeros_like(out), b_h)[0]


def prefill_attention(q, k, v, q_start, *, scale: float,
                      window: int | None = None, sink=None,
                      cached=None, out_dtype=None):
    """A stretch of queries ``q`` (B, L, H, Dq) at positions ``q_start``
    (scalar or (B,)) ``+ 0 .. L - 1``. Returns (B, L, H, Dv).

    Global layer (``window`` None): ``k`` / ``v`` (B, W, Hk, ·) are the
    WHOLE view from position 0, the stretch's own rows already written
    into it. Window layer: ``k`` / ``v`` (B, L, Hk, ·) are the stretch's
    own, and ``cached`` = ``(ring_k, ring_v)`` the layer's ring as it was
    before the stretch (None: the stretch starts the sequence). The result
    is in ``out_dtype`` (default: the queries')."""
    b, l = q.shape[:2]
    start = jnp.broadcast_to(jnp.asarray(q_start, jnp.int32), (b,))
    to = out_dtype or q.dtype
    if window is None:
        out, lse = _flash_padded(q, k, v, start, 0, scale=scale,
                                 window=None, out_dtype=out_dtype)
        return _sink_join(out, lse, sink).astype(to).transpose(0, 2, 1, 3)
    if cached is not None and (min(l, window) * cached[0].shape[1]
                               > RING_CORNER_MAX):
        # a ring too long for the dense corner: one call over the ring's
        # live rows and the stretch's own, in position order
        keys, k0 = ring_stretch(cached[0], k, start)
        vals, _ = ring_stretch(cached[1], v, start)
        out, lse = _flash_padded(q, keys, vals, start, k0, scale=scale,
                                 window=window, name=WINDOW_RING_KERNEL,
                                 out_dtype=out_dtype)
        return _sink_join(out, lse, sink).astype(to).transpose(0, 2, 1, 3)
    out, lse = _flash_padded(q, k, v, start, start, scale=scale,
                             window=window, out_dtype=out_dtype)
    if cached is not None:
        # only the first window - 1 queries reach back into the ring
        n = min(l, window)
        o_r, lse_r = _ring_corner(q[:, :n], *cached, start, scale=scale,
                                  window=window)
        head, lse_head = join(out[:, :, :n], lse[:, :, :n], o_r, lse_r)
        out = jnp.concatenate([head, out[:, :, n:]], axis=2)
        lse = jnp.concatenate([lse_head, lse[:, :, n:]], axis=2)
    return _sink_join(out, lse, sink).astype(to).transpose(0, 2, 1, 3)


def ring_positions(n, rows: int):
    """Position each of a ring's ``rows`` rows holds before position
    ``n`` (B,) is written: (B, rows), negative where the row holds
    nothing of this sequence."""
    last = n[:, None] - 1
    return last - (last - jnp.arange(rows)[None, :]) % rows


def ring_stretch(ring, new, start):
    """``ring`` (B, R, ...) as it was before a stretch ``new`` (B, L, ...)
    at positions ``start`` (B,) ``+ 0 .. L - 1``, and the stretch, as ONE
    run of ``R + L`` rows in position order from ``k0 = max(start - R,
    0)`` (returned beside it, (B,)): a position before ``start`` comes
    from the ring (all of those are this sequence's: none is negative,
    none older than the ring holds), one from ``start`` on from the
    stretch, and the rows past the stretch's end repeat its last (they
    lie after every query)."""
    rows, l = ring.shape[1], new.shape[1]
    k0 = jnp.maximum(start - rows, 0)
    pos = k0[:, None] + jnp.arange(rows + l)[None, :]       # (B, R + L)
    own = pos - start[:, None]
    src = jnp.where(own >= 0, rows + jnp.clip(own, 0, l - 1), pos % rows)
    both = jnp.concatenate([ring.astype(new.dtype), new], axis=1)
    idx = src.reshape(src.shape + (1,) * (new.ndim - 2))
    return jnp.take_along_axis(both, idx, axis=1), k0


def _ring_corner(q, ring_k, ring_v, start, *, scale, window):
    """The first queries of a stretch (B, n, H, Dq) over the ring as it
    was before the stretch: normalised partial output (B, H, n, Dv)
    float32 and log-sum-exp (B, H, n)."""
    b, n, h, _ = q.shape
    hk = ring_k.shape[2]
    pos = ring_positions(start, ring_k.shape[1])            # (B, R)
    qpos = start[:, None] + jnp.arange(n)[None, :]          # (B, n)
    live = (pos[:, None, :] >= 0) & (
        qpos[:, :, None] - pos[:, None, :] < window)        # (B, n, R)
    qg = q.reshape(b, n, hk, h // hk, -1)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, ring_k.astype(q.dtype),
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(live[:, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(live[:, None, None], jnp.exp(s - m), 0.0)
    total = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bgrqk,bkgd->bgrqd",
                   (p / jnp.maximum(total, 1e-30)).astype(q.dtype),
                   ring_v.astype(q.dtype), preferred_element_type=jnp.float32)
    lse = (m + jnp.log(jnp.maximum(total, 1e-30)))[..., 0]
    return (o.reshape(b, h, n, -1), lse.reshape(b, h, n))


def ring_write(ring, new, start, valid):
    """``ring`` (B, R, ...) after a stretch ``new`` (B, L, ...) at
    positions ``start + 0 .. L - 1`` of which the first ``valid`` (B,)
    are real: every row takes the last real position it stands for, if
    the stretch holds one."""
    rows, l = ring.shape[1], new.shape[1]
    new = new.astype(ring.dtype)
    if l == 1:
        # one position: the row it lands on, kept as it was where the
        # position is not real
        at = start % rows
        old = jax.vmap(lambda r, i: jax.lax.dynamic_index_in_dim(
            r, i, 0, keepdims=True))(ring, at)
        keep = (valid > 0).reshape((-1,) + (1,) * (new.ndim - 1))
        return jax.vmap(lambda r, n, i: jax.lax.dynamic_update_slice_in_dim(
            r, n, i, 0))(ring, jnp.where(keep, new, old), at)
    pos = ring_positions(start + valid, rows)               # (B, R)
    take = pos >= start[:, None]
    src = jnp.clip(pos - start[:, None], 0, l - 1)
    idx = src.reshape(src.shape + (1,) * (new.ndim - 2))
    picked = jnp.take_along_axis(new, idx, axis=1)
    return jnp.where(take.reshape(idx.shape), picked, ring)


def ring_decode_attention(q, ring_k, ring_v, index, *, scale: float,
                          window: int, sink=None):
    """One query a row (B, 1, H, Dq) at position ``index`` (B,) over a
    ring that already holds it. Returns (B, 1, H, Dv)."""
    b, _, h, _ = q.shape
    hk = ring_k.shape[2]
    with jax.named_scope(WINDOW_DECODE_SCOPE):
        pos = ring_positions(index + 1, ring_k.shape[1])    # (B, R)
        live = (pos >= 0) & (index[:, None] - pos < window)
        qg = q[:, 0].reshape(b, hk, h // hk, -1)
        s = jnp.einsum("bgrd,bkgd->bgrk", qg, ring_k.astype(q.dtype),
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(live[:, None, None, :], s, NEG_INF)
        # values, like keys, in the query's precision: a probability of
        # 1 / 4,096 cast to an fp8 ring's dtype would be zero
        out = _softmax_sum(s, ring_v.astype(q.dtype), sink, hk)
    return out.reshape(b, 1, h, -1).astype(q.dtype)


def decode_attention(q, k, v, index, *, scale: float):
    """One query a row (B, 1, H, Dq) at position ``index`` (B,) over a
    view of FLAT rows ``k`` (B, W, Hk * Dq) / ``v`` (B, W, Hk * Dv) that
    already holds it: the keys at or before it. Returns (B, 1, H, Dv).

    The view stays as the pool's pages gave it. Splitting its rows into
    ``(Hk, 192)`` would re-lay the whole view out (192 is 1.5 lane tiles:
    the chip pads such a minor pair to (8, 256), 2.7 times the bytes), so
    the heads are split on the QUERY's side instead: head ``h`` of group
    ``g`` takes a ``Hk * Dq``-wide query that is zero outside the group's
    columns, and reads its group's columns of the ``Hk * Dv``-wide sum.
    ``Hk`` times the multiply-adds of a path that is bound by reading the
    view once for the scores and once for the sum."""
    b, _, h, dq = q.shape
    hk = k.shape[-1] // dq
    dv = v.shape[-1] // hk
    with jax.named_scope(GLOBAL_DECODE_SCOPE):
        own = jnp.eye(hk, dtype=q.dtype)        # (group, group's columns)
        qg = q[:, 0].reshape(b, hk, h // hk, 1, dq)
        q_wide = (qg * own[None, :, None, :, None]).reshape(b, h, hk * dq)
        s = jnp.einsum("bhc,bkc->bhk", q_wide, k.astype(q.dtype),
                       preferred_element_type=jnp.float32) * scale
        live = jnp.arange(k.shape[1])[None, :] <= index[:, None]
        s = jnp.where(live[:, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        wide = jnp.einsum("bhk,bkc->bhc", p, v.astype(q.dtype)).reshape(
            b, hk, h // hk, hk, dv)
        out = jnp.sum(wide * own[None, :, None, :, None], axis=3)
    return out.reshape(b, 1, h, dv).astype(q.dtype)


def paired_decode_attention(qs, ks, v, index, *, scale: float):
    """:func:`decode_attention` for SEVERAL softmaxes that share one view
    of values (differential attention, ``models/phi4flash.py``): query
    ``qs[i]`` (B, 1, H, Dq) reads its own flat keys ``ks[i]`` (B, W, Hk *
    Dq), and all of them the one ``v`` (B, W, Hk * Dv), which is read ONCE
    for their stacked probabilities. Returns a tuple of (B, 1, H, Dv)
    float32: the caller subtracts them."""
    b, _, h, dq = qs[0].shape
    hk = ks[0].shape[-1] // dq
    dv = v.shape[-1] // hk
    n = len(qs)
    with jax.named_scope(GLOBAL_DECODE_SCOPE):
        own = jnp.eye(hk, dtype=qs[0].dtype)
        live = jnp.arange(v.shape[1])[None, :] <= index[:, None]
        ps = []
        for q, k in zip(qs, ks):
            qg = q[:, 0].reshape(b, hk, h // hk, 1, dq)
            q_wide = (qg * own[None, :, None, :, None]).reshape(
                b, h, hk * dq)
            s = jnp.einsum("bhc,bkc->bhk", q_wide, k.astype(q.dtype),
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(live[:, None, :], s, NEG_INF)
            ps.append(jax.nn.softmax(s, axis=-1).astype(q.dtype))
        wide = jnp.einsum("bhk,bkc->bhc", jnp.concatenate(ps, axis=1),
                          v.astype(qs[0].dtype),
                          preferred_element_type=jnp.float32).reshape(
            b, n, hk, h // hk, hk, dv)
        out = jnp.sum(wide * own.astype(jnp.float32)[
            None, None, :, None, :, None], axis=4)
    return tuple(out[:, i].reshape(b, 1, h, dv) for i in range(n))


# --- one query a row over the pages where they lie ----------------------------
# The in-place twins of :func:`decode_attention` and
# :func:`paired_decode_attention`: no gathered view. The pool stays as
# ``serve/paged_kv.py`` stores it by pages, ``(pages, page rows, Hk * D up to
# whole lanes)``, and a slot's block-table row names its pages in position
# order. The kernel copies ``PAGED_DECODE_PAGES`` pages of each buffer into a
# block of fast memory itself (a grid step a page would be ~7,000 steps a
# reader), the next block's copies in flight while this one is computed,
# along a flat list of (row, block) pairs that holds a row's blocks up to its
# true length and nothing of a row of length 0. ONE kernel for one softmax a
# query head (a global layer of ``models/mimo_v2.py``) and for two that share
# a read of the values (``models/phi4flash.py``): how many key pools stand
# beside the value pool, and every row width, are static and read off the
# operands.

# the kernels' names on the device plane, by the softmaxes a query head takes
GLOBAL_PAGED_KERNEL = "global_paged_decode"
PAGED_DECODE_KERNEL = "shared_kv_paged_decode"
# pages a compute block (tools/paged_decode_bakeoff.py; PERF.md, PR 48, PR 49)
PAGED_DECODE_PAGES = 32


def paged_block_pages(max_pages: int, pages_per_block: int | None = None):
    """Pages the kernel copies at once for slots of ``max_pages`` pages."""
    return min(pages_per_block or PAGED_DECODE_PAGES, max_pages)


def paged_decode_work(lengths, page_size: int, max_pages: int,
                      pages_per_block: int | None = None):
    """The kernel's flat work list for rows of ``lengths`` (B,): ``(row,
    block, total)``, int32 ``(B * blocks a slot,)`` twice and ``(1,)``.
    Item ``i < total`` is block ``block[i]`` of row ``row[i]``: rows in
    order, each row's blocks from 0 to the last that holds a live key.
    One list serves every layer that reads the same lengths."""
    b = lengths.shape[0]
    ppb = paged_block_pages(max_pages, pages_per_block)
    n = -(-lengths.astype(jnp.int32) // (page_size * ppb))
    ends = jnp.cumsum(n)
    i = jnp.arange(b * -(-max_pages // ppb), dtype=jnp.int32)
    # the row whose run of blocks holds item i: the rows that end at or
    # before it
    row = jnp.minimum(jnp.sum(i[:, None] >= ends[None, :], axis=1,
                              dtype=jnp.int32), b - 1)
    return row, i - (ends - n)[row], ends[-1:]


def paged_rows(table, start, valid, page_size: int) -> dict:
    """What a decode program's readers of one pool share: the keywords
    ``table``, ``lengths`` and ``work`` of the two entries below for rows
    whose new key lands at ``start`` (B,); a row whose ``valid`` is 0 (idle,
    mid-prefill) reads nothing."""
    lengths = jnp.where(valid > 0, start + 1, 0)
    return dict(table=table, lengths=lengths, work=paged_decode_work(
        lengths, page_size, table.shape[1]))


def _paged_decode_kernel(row_ref, blk_ref, total_ref, len_ref, table_ref,
                         q_ref, *refs, scale, n, heads, group, kv_heads,
                         pools):
    """ONE invocation walks the whole list. ``n`` softmaxes a query head,
    each over a key pool of its own, all over one value pool. ``q_ref`` (B,
    n, Hp, C): every head's query for each softmax (``Hp``: the ``heads``
    up to whole sublane tiles), zero outside its K/V head's columns of a
    key row; ``table_ref`` (B * pages a slot,), flat; ``refs``: the
    ``pools`` pools as they lie, the ``n`` key pools and then the value
    pool (``pools`` = ``n + 1``) or the key pools alone (``pools`` = ``n``:
    the LAST key pool's rows are the values too, their first columns, and
    are copied once), ``o_ref`` (B, heads, n * Dv) float32, a head's ``n``
    results side by side, then a two-block buffer a pool, the copies'
    semaphores, and the running maximum, denominator and sum. The
    softmaxes' scores stack to ``(n Hp, rows)``: the value block is
    multiplied once."""
    hbm, o_ref = refs[:pools], refs[pools]
    bufs = refs[pools + 1:2 * pools + 1]
    sems, m_ref, l_ref, acc_ref = refs[2 * pools + 1:]
    _, ppb, page, _ = bufs[0].shape
    rows = ppb * page
    hp, dv = q_ref.shape[2], o_ref.shape[-1] // n
    max_pages = table_ref.shape[0] // q_ref.shape[0]
    total = total_ref[0]

    def copy(slot, p, at, pool):
        return pltpu.make_async_copy(hbm[pool].at[at], bufs[pool].at[slot, p],
                                     sems.at[slot, pool])

    # the copies are issued by the scalar core, ~37 ns each: a block of 16
    # KB pages is bound by that and not by the bytes (PERF.md, PR 49), so
    # the issue loop is unrolled by 8 (a loop all the same, not (n + 1) *
    # ppb copies spelled out: the program that holds the kernel is traced
    # and lowered in a fraction of the time) ...
    unroll = math.gcd(ppb, 8)

    def start(i, slot):
        base = row_ref[i] * max_pages + blk_ref[i] * ppb

        def page_copies(p, carry):
            for at in (p * unroll + d for d in range(unroll)):
                for pool in range(pools):
                    copy(slot, at, table_ref[base + at], pool).start()
            return carry

        jax.lax.fori_loop(0, ppb // unroll, page_copies, None)

    # ... and a pool's ``ppb`` copies are awaited at ONCE: a semaphore
    # counts bytes, and this descriptor (any pages: it is never started)
    # stands for a whole block of them
    def wait(slot):
        for pool in range(pools):
            pltpu.make_async_copy(hbm[pool].at[pl.ds(0, ppb)],
                                  bufs[pool].at[slot],
                                  sems.at[slot, pool]).wait()

    # a row of length 0 is in no item: zeros
    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(total > 0)
    def _():
        start(0, 0)

    def block(i, carry):
        slot = jax.lax.rem(i, 2)
        b, j = row_ref[i], blk_ref[i]
        length = len_ref[b]

        @pl.when(i + 1 < total)
        def _():
            start(i + 1, 1 - slot)

        @pl.when(j == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        wait(slot)
        q = q_ref[b]                                        # (n, Hp, C)
        s = jnp.concatenate([
            jax.lax.dot_general(
                q[x], bufs[x][slot].reshape(rows, -1).astype(q.dtype),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            for x in range(n)], axis=0) * scale
        live = j * rows + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1) < length
        # a block of the list holds a live key (j * rows < length): the
        # running maximum is a real score from a row's first block on
        s = jnp.where(live, s, NEG_INF)
        m_prev, l_prev = m_ref[:, 0:1], l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, 0:1] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[:, 0:1] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(q.dtype),
            bufs[pools - 1][slot].reshape(rows, -1).astype(q.dtype),
            preferred_element_type=jnp.float32)

        @pl.when((j + 1) * rows >= length)
        def _():
            # row r of a softmax reads K/V head r // group: its Dv columns
            # of the sum over whole value rows
            mine = jax.lax.broadcasted_iota(
                jnp.int32, (n * hp, dv), 0) % hp // group
            out = jnp.zeros((n * hp, dv), jnp.float32)
            for g in range(kv_heads):
                out = jnp.where(mine == g,
                                acc_ref[:, g * dv:(g + 1) * dv], out)
            out = out / l_ref[:, 0:1]
            o_ref[b] = jnp.concatenate(
                [out[x * hp:x * hp + heads] for x in range(n)], axis=-1)
        return carry

    jax.lax.fori_loop(0, total, block, None)


# jitted in its own right: a program's readers (every global layer; the full
# layer and the scanned cross layers) and every program of a process share
# ONE trace of the kernel
@functools.partial(jax.jit, static_argnames=(
    "scale", "kv_heads", "v_dim", "name", "pages_per_block", "interpret"))
def _paged_attention(qs, ks, v, table, lengths, *, scale, kv_heads, v_dim,
                     name, work=None, pages_per_block=None, interpret=None):
    """``len(qs)`` softmaxes a query head over the pool's pages: ``(B,
    H, len(qs) * v_dim)`` float32, the kernel's own result. ``v`` None: the
    last key pool's rows are the values too (their first ``kv_heads *
    v_dim`` columns), and the kernel copies that pool once."""
    b, _, h, dq = qs[0].shape
    dtype = qs[0].dtype
    n, hk, dv = len(qs), kv_heads, v_dim
    page, ck = ks[0].shape[1:]
    pools = ks if v is None else (*ks, v)
    cv = pools[-1].shape[2]
    ppb = paged_block_pages(table.shape[1], pages_per_block)
    if work is None:
        work = paged_decode_work(lengths, page, table.shape[1], ppb)
    # whole blocks of the table, so that a row's last block names pages
    table = jnp.pad(table.astype(jnp.int32),
                    ((0, 0), (0, -table.shape[1] % ppb)))
    sub = 32 // dtype.itemsize
    hp = -(-h // sub) * sub
    with jax.named_scope(GLOBAL_DECODE_SCOPE):
        own = jnp.eye(hk, dtype=dtype)
        wide = jnp.stack([
            (q[:, 0].reshape(b, hk, h // hk, 1, dq)
             * own[None, :, None, :, None]).reshape(b, h, hk * dq)
            for q in qs], axis=1)                           # (B, n, H, C)
        wide = jnp.pad(wide, ((0, 0), (0, 0), (0, hp - h),
                              (0, ck - hk * dq)))
        whole = lambda shape: pl.BlockSpec(       # noqa: E731
            shape, lambda i, *_: (0,) * len(shape))
        return pl.pallas_call(
            functools.partial(_paged_decode_kernel, scale=scale, n=n,
                              heads=h, group=h // hk, kv_heads=hk,
                              pools=len(pools)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(1,),
                in_specs=[whole(wide.shape)]
                + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
                out_specs=whole((b, h, n * dv)),
                scratch_shapes=[
                    *(pltpu.VMEM((2, ppb, page, pool.shape[2]), pool.dtype)
                      for pool in pools),
                    pltpu.SemaphoreType.DMA((2, len(pools))),
                    pltpu.VMEM((n * hp, _LANE), jnp.float32),
                    pltpu.VMEM((n * hp, _LANE), jnp.float32),
                    pltpu.VMEM((n * hp, cv), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((b, h, n * dv), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_PAGED_VMEM),
            interpret=interpret_default() if interpret is None else interpret,
            name=name,
        )(*work, lengths.astype(jnp.int32), table.reshape(-1), wide, *pools)


def paged_decode_attention(q, k, v, table, lengths, *, scale: float,
                           kv_heads: int, v_dim: int, work=None,
                           pages_per_block: int | None = None,
                           interpret: bool | None = None):
    """:func:`decode_attention` over the pool's PAGES where they lie, to
    each row's true length. ``q`` (B, 1, H, Dq); ``k`` / ``v`` the pools
    (pages, page rows, Hk * Dq | Hk * Dv up to whole lanes; ``Hk`` =
    ``kv_heads``, ``Dv`` = ``v_dim``: a pool's rows are padded to whole
    lanes, so their width does not say it); ``table`` (B, pages a slot)
    int32 names each row's pages in position order (past the row's own:
    any page of the pool, its rows are masked); ``lengths`` (B,): row ``b``
    attends positions ``0 .. lengths[b] - 1``, its own included; at 0 it
    reads nothing and gets zeros. ``work``: :func:`paged_decode_work` of
    these lengths, from a caller with several readers
    (:func:`paged_rows`). Returns (B, 1, H, Dv) in the query's dtype."""
    out = _paged_attention(
        (q,), (k,), v, table, lengths, scale=scale, kv_heads=kv_heads,
        v_dim=v_dim, name=GLOBAL_PAGED_KERNEL, work=work,
        pages_per_block=pages_per_block, interpret=interpret)
    return out[:, None].astype(q.dtype)


def paged_paired_decode_attention(qs, ks, v, table, lengths, *, scale: float,
                                  kv_heads: int, work=None,
                                  pages_per_block: int | None = None,
                                  interpret: bool | None = None):
    """:func:`paired_decode_attention` over the pool's pages, as
    :func:`paged_decode_attention` reads them: ``qs`` = ``(q1, q2)`` (B, 1,
    H, Dq), ``ks`` = the two key pools, ``v`` the one value pool, read once
    (``Dv`` = 2 ``Dq``). Returns ``(a1, a2)`` (B, 1, H, Dv) float32."""
    dv = 2 * qs[0].shape[-1]
    out = _paged_attention(
        tuple(qs), tuple(ks), v, table, lengths, scale=scale,
        kv_heads=kv_heads, v_dim=dv, name=PAGED_DECODE_KERNEL, work=work,
        pages_per_block=pages_per_block, interpret=interpret)
    return tuple(out[:, None, :, i * dv:(i + 1) * dv] for i in range(2))


def paired_ring_decode_attention(qs, ring_ks, ring_v, index, *,
                                 scale: float, window: int):
    """:func:`ring_decode_attention` for several softmaxes over their own
    key rings ``ring_ks[i]`` (B, R, Hk, Dq) and ONE ring of values (B, R,
    Hk, Dv), read once. Returns a tuple of (B, 1, H, Dv) float32."""
    b, _, h, _ = qs[0].shape
    hk, n = ring_v.shape[2], len(qs)
    group = h // hk
    with jax.named_scope(WINDOW_DECODE_SCOPE):
        pos = ring_positions(index + 1, ring_v.shape[1])    # (B, R)
        live = (pos >= 0) & (index[:, None] - pos < window)
        ps = []
        for q, ring_k in zip(qs, ring_ks):
            qg = q[:, 0].reshape(b, hk, group, -1)
            s = jnp.einsum("bgrd,bkgd->bgrk", qg, ring_k.astype(q.dtype),
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(live[:, None, None, :], s, NEG_INF)
            ps.append(jax.nn.softmax(s, axis=-1).astype(q.dtype))
        out = jnp.einsum("bgrk,bkgd->bgrd", jnp.concatenate(ps, axis=2),
                         ring_v.astype(qs[0].dtype),
                         preferred_element_type=jnp.float32)
    return tuple(out[:, :, i * group:(i + 1) * group].reshape(b, 1, h, -1)
                 for i in range(n))


def _softmax_sum(s, v, sink, hk):
    """``softmax(s) v`` for scores (B, Hk, G, K) with the sink's logit in
    the denominator."""
    m = jnp.max(s, axis=-1, keepdims=True)
    if sink is not None:
        b_h = sink.astype(jnp.float32).reshape(1, hk, -1, 1)
        m = jnp.maximum(m, b_h)
    p = jnp.exp(s - m)
    total = jnp.sum(p, axis=-1, keepdims=True)
    if sink is not None:
        total = total + jnp.exp(b_h - m)
    p = (p / total).astype(v.dtype)
    return jnp.einsum("bgrk,bkgd->bgrd", p, v)
