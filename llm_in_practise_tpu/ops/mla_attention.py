"""Attention over a LATENT cache (multi-head latent attention, DeepSeek-V2/V3).

A token's cache row in a layer is ``[c_kv (rank) | k_rope (rope_dim)]``:
the RMS-normed compressed key/value latent and ONE rotated rope key shared
by every head. ``w_kvb`` (rank, heads, nope_dim + v_dim) decompresses a
latent into each head's ``[k_nope | v]``. Three forms of the same
attention, equal in exact arithmetic, by the program that runs them:

- :func:`decode_attention`, the ABSORBED form (query length 1) over a
  gathered VIEW: a decode against a contiguous cache, or against a paged
  one whose model does not read its pages (tests, the benchmark's
  reference). ``W^K`` is folded into the query (``q~ = (W^K_h)^T q_nope``,
  rank wide) and ``W^V`` applied after the sum, so attention runs on the
  latent rows as the cache holds them: every head reads the same ``(keys,
  rank + rope_dim)`` view once for the scores and once for the sum, and no
  key or value is ever decompressed. A decode step is bound by that read.
- :func:`paged_decode_attention`, the same absorbed form with NO view (the
  serving engine's decode program and a mixed step's decode half, for a
  model that declares ``reads_pages``): ``ops/swa_attention.py``'s paged
  reader walks the latent pool's pages where they lie, to each row's true
  length. A latent row is key AND value at once, so the reader gets ONE
  pool and copies each page once: all heads score the row's whole width
  and sum its first ``rank`` columns.
- :func:`prefill_attention`, the NAIVE form over key blocks (query length
  of a chunk or a prompt). ``KEY_BLOCK`` keys at a time are decompressed
  to per-head keys (``[k_nope | k_rope]``, 192 wide at the published
  sizes) and values (128), a Pallas flash kernel with a running softmax
  takes the chunk's queries over that block (the score matrix never
  leaves VMEM), and the blocks' partial results are joined by their
  log-sum-exp. The loop's trip count is TRACED: it stops at the last
  block a query can see, so the work follows a row's true length and not
  the width of the view it came in. The absorbed form needs 3.4 times
  the matmul work (2 x 128 x (576 + 512) against 2 x 128 x 320 a
  query-key pair); ``absorbed=True`` runs it through the same kernel
  (the latent as the one key/value head of every query head) for
  ``tools/mla_bakeoff.py``, whose chip timing chose the naive form
  (PERF.md section 6, PR 34).

Both run under a ``jax.named_scope`` (:data:`DECODE_SCOPE`,
:data:`PREFILL_SCOPE`) so the device plane can find their operations
(``benchmark/metrics/mla_*_attention_roofline.json``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_in_practise_tpu.ops.attention import interpret_default

DECODE_SCOPE = "mla_decode_attention"
PREFILL_SCOPE = "mla_prefill_attention"
KERNEL_NAME = "mla_prefill_flash"   # the kernel's name on the device plane
PAGED_KERNEL = "mla_paged_decode"   # the in-place decode reader's
NEG_INF = -1e30
KEY_BLOCK = 4096        # keys decompressed at a time by the prefill form
BLOCK_Q, BLOCK_K = 1024, 1024   # the kernel's tiles (tools/mla_bakeoff.py)
_LANE, _SUBLANE = 128, 8


def _absorb(q_nope, q_rope, w_kvb):
    """``W^K`` folded into one query a row: ``([q~ | q_rope]`` (B, H, rank
    + dr), ``W^V`` (rank, H, dv)) of ``q_nope`` (B, 1, H, dn), ``q_rope``
    (B, 1, H, dr) and ``w_kvb`` (rank, H, dn + dv)."""
    dn = q_nope.shape[-1]
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope[:, 0], w_kvb[..., :dn])
    return (jnp.concatenate([q_lat, q_rope[:, 0].astype(q_lat.dtype)],
                            axis=-1), w_kvb[..., dn:])


def decode_attention(q_nope, q_rope, latent, index, w_kvb, *, rank: int,
                     scale: float):
    """Absorbed attention of ONE query a row. ``q_nope`` (B, 1, H, dn),
    ``q_rope`` (B, 1, H, dr) rotated, ``latent`` (B, W, rank + dr) with
    the query's own row already written at ``index`` (scalar or (B,):
    the query's absolute position; keys beyond it are not attended),
    ``w_kvb`` (rank, H, dn + dv). Returns (B, 1, H, dv)."""
    b, w, _ = latent.shape
    with jax.named_scope(DECODE_SCOPE):
        q_all, w_v = _absorb(q_nope, q_rope, w_kvb)
        s = jnp.einsum("bhc,bkc->bhk", q_all, latent,
                       preferred_element_type=jnp.float32) * scale
        pos = jnp.broadcast_to(jnp.asarray(index), (b,))
        s = jnp.where(jnp.arange(w)[None, None, :] <= pos[:, None, None],
                      s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(latent.dtype)
        # over the row's full width: slicing the rank columns out of the
        # view first would copy the view; the rope columns' sums are
        # dropped after
        o_lat = jnp.einsum("bhk,bkc->bhc", p, latent)[..., :rank]
        out = jnp.einsum("bhc,chd->bhd", o_lat, w_v)
    return out[:, None]


def paged_decode_attention(q_nope, q_rope, pool, w_kvb, *, rank: int,
                           scale: float, table, lengths, work=None,
                           pages_per_block: int | None = None,
                           interpret: bool | None = None):
    """:func:`decode_attention` over the latent pool's PAGES where they
    lie, to each row's true length. ``pool`` (pages, page rows, rank + dr
    up to whole lanes) as ``serve/paged_kv.py`` stores it by pages, the
    queries' own rows already written; ``table`` (B, pages a slot),
    ``lengths`` (B,) and ``work`` as ``swa_attention.paged_rows`` gives
    them (a row of length 0 reads nothing and gets zeros). Returns (B, 1,
    H, dv)."""
    # here and not at the top: that module takes ``join`` from this one
    from llm_in_practise_tpu.ops import swa_attention as swa

    with jax.named_scope(DECODE_SCOPE):
        q_all, w_v = _absorb(q_nope, q_rope, w_kvb)
        # the latent is the one K/V head of every query head: its whole
        # row the key, its first ``rank`` columns the value
        o_lat = swa._paged_attention(
            (q_all[:, None],), (pool,), None, table, lengths, scale=scale,
            kv_heads=1, v_dim=rank, name=PAGED_KERNEL, work=work,
            pages_per_block=pages_per_block, interpret=interpret)
        out = jnp.einsum("bhc,chd->bhd", o_lat.astype(q_all.dtype), w_v)
    return out[:, None]


# ------------------------------------------------------------ the kernel


def _flash_kernel(qs_ref, ks_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                  acc_ref, m_ref, l_ref, *, scale, block_q, block_k):
    """Grid (batch, heads, q blocks, key blocks), key blocks innermost;
    acc / m / l persist over them. Query row ``r`` of q block ``qi`` is
    at absolute position ``qs[b] + qi * block_q + r``, key column ``c``
    of key block ``ki`` at ``ks[0] + ki * block_k + c``; a query sees
    the keys at or before its position."""
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    n_k = pl.num_programs(3)
    q0 = qs_ref[b] + qi * block_q
    k0 = ks_ref[0] + ki * block_k

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(k0 <= q0 + block_q - 1)
    def _():
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(k0 + cols <= q0 + rows, s, NEG_INF)
        m_prev, l_prev = m_ref[:, 0:1], l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, 0:1] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[:, 0:1] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _():
        # a row that saw no key of this call keeps m = NEG_INF and l = 0:
        # its output is 0 and its lse ~ -1e30, which the join ignores
        l = jnp.maximum(l_ref[:, 0:1], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse = (m_ref[:, 0:1] + jnp.log(l))[:, 0]
        lse_ref[...] = jnp.broadcast_to(lse[None, :], (_SUBLANE, block_q))


def flash_partial(q, k, v, q_start, k_start, *, scale: float,
                  block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                  interpret: bool | None = None):
    """Causal attention of ``q`` (B, H, Lq, Dq) over ONE stretch of keys
    ``k`` (B, Hk, Lk, Dq) / values ``v`` (B, Hk, Lk, Dv), ``H`` a
    multiple of ``Hk``; ``q_start`` (B,) and ``k_start`` () are the
    absolute positions of the first query and the first key. Returns the
    stretch's own softmax-normalised output (B, H, Lq, Dv) and its
    log-sum-exp (B, H, Lq) float32, for :func:`join`."""
    b, h, lq, dq = q.shape
    hk, lk, dv = k.shape[1], k.shape[2], v.shape[-1]
    group = h // hk
    block_q, block_k = min(block_q, lq), min(block_k, lk)
    if lq % block_q or lk % block_k:
        raise ValueError(f"lengths ({lq}, {lk}) must be multiples of the "
                         f"tiles ({block_q}, {block_k})")
    n_q, n_k = lq // block_q, lk // block_k

    def kv_map(bi, hi, i, j, qs, ks):
        # key blocks past the last one this q block can see are never
        # computed: name the last live block again, so they are not
        # fetched either
        last = (qs[bi] + (i + 1) * block_q - 1 - ks[0]) // block_k
        return bi, hi // group, jnp.clip(jnp.minimum(j, last), 0, n_k - 1), 0

    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, block_q=block_q,
                          block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h, n_q, n_k),
            in_specs=[
                pl.BlockSpec((None, None, block_q, dq),
                             lambda bi, hi, i, j, qs, ks: (bi, hi, i, 0)),
                pl.BlockSpec((None, None, block_k, dq), kv_map),
                pl.BlockSpec((None, None, block_k, dv), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((None, None, block_q, dv),
                             lambda bi, hi, i, j, qs, ks: (bi, hi, i, 0)),
                pl.BlockSpec((None, None, _SUBLANE, block_q),
                             lambda bi, hi, i, j, qs, ks: (bi, hi, 0, i)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, dv), jnp.float32),
                pltpu.VMEM((block_q, _LANE), jnp.float32),
                pltpu.VMEM((block_q, _LANE), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, lq, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, _SUBLANE, lq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret_default() if interpret is None else interpret,
        name=KERNEL_NAME,
    )(jnp.asarray(q_start, jnp.int32).reshape(b),
      jnp.asarray(k_start, jnp.int32).reshape(1), q, k, v)
    return out, lse[:, :, 0, :]


def join(out_a, lse_a, out_b, lse_b):
    """Two stretches' normalised outputs (float32) and log-sum-exps as
    one: each weighs in by its share of the joint softmax's mass."""
    lse = jnp.logaddexp(lse_a, lse_b)
    return (out_a * jnp.exp(lse_a - lse)[..., None]
            + out_b.astype(jnp.float32) * jnp.exp(lse_b - lse)[..., None],
            lse)


# ------------------------------------------------------------- prefill


def prefill_attention(q_nope, q_rope, latent, q_start, w_kvb, *, rank: int,
                      scale: float, absorbed: bool = False,
                      key_block: int = KEY_BLOCK, block_q: int = BLOCK_Q,
                      block_k: int = BLOCK_K):
    """Causal attention of a stretch of queries over a latent view.
    ``q_nope`` (B, Lq, H, dn), ``q_rope`` (B, Lq, H, dr) rotated;
    ``latent`` (B, W, rank + dr), the queries' own rows already written
    at ``q_start`` (scalar or (B,): the first query's absolute
    position); ``w_kvb`` (rank, H, dn + dv). Returns (B, Lq, H, dv)."""
    b, lq, h, dn = q_nope.shape
    w = latent.shape[1]
    dv = w_kvb.shape[-1] - dn
    dtype = latent.dtype
    with jax.named_scope(PREFILL_SCOPE):
        start = jnp.broadcast_to(jnp.asarray(q_start, jnp.int32), (b,))
        w_k, w_v = w_kvb[..., :dn].astype(dtype), w_kvb[..., dn:].astype(dtype)
        if absorbed:
            q = jnp.concatenate(
                [jnp.einsum("blhd,chd->bhlc", q_nope.astype(dtype), w_k),
                 q_rope.astype(dtype).transpose(0, 2, 1, 3)], axis=-1)
        else:
            q = jnp.concatenate([q_nope, q_rope], axis=-1).astype(
                dtype).transpose(0, 2, 1, 3)
        bq = min(block_q, lq)
        pad_q = -lq % bq
        if pad_q:
            q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        kb = key_block if w % key_block == 0 else w
        bk = block_k if kb % block_k == 0 else kb

        def stretch(j):
            rows = jax.lax.dynamic_slice_in_dim(latent, j * kb, kb, axis=1)
            if absorbed:
                return rows[:, None], rows[:, None, :, :rank]
            c, k_rope = rows[..., :rank], rows[..., rank:]
            k_nope = jnp.einsum("bkc,chd->bhkd", c, w_k)
            v = jnp.einsum("bkc,chd->bhkd", c, w_v)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope[:, None],
                                          (b, h, kb, k_rope.shape[-1]))],
                axis=-1)
            return k, v

        def body(j, carry):
            k, v = stretch(j)
            o, lse = flash_partial(q, k, v, start, j * kb, scale=scale,
                                   block_q=bq, block_k=bk)
            return join(*carry, o, lse)

        d_out = rank if absorbed else dv
        init = (jnp.zeros((b, h, lq + pad_q, d_out), jnp.float32),
                jnp.full((b, h, lq + pad_q), NEG_INF, jnp.float32))
        # the last stretch any query of the batch can see
        n_live = jnp.minimum((jnp.max(start) + lq - 1) // kb + 1, w // kb)
        out, _ = jax.lax.fori_loop(0, n_live, body, init)
        out = out[:, :, :lq].astype(dtype)
        if absorbed:
            out = jnp.einsum("bhlc,chd->bhld", out, w_v)
    return out.transpose(0, 2, 1, 3)
