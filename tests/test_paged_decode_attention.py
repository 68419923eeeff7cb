"""``swa.paged_decode_attention`` / ``swa.paged_paired_decode_attention``:
one query a row over the pool's PAGES where they lie, to each row's true
length, against ``swa.decode_attention`` / ``swa.paired_decode_attention``
over the gathered view of the same pages (interpret mode, tiny shapes: pages
of 8 rows, blocks of 2 pages, 8 pages a slot); and
``mla.paged_decode_attention``, the same kernel over ONE pool whose row is
key and value at once, against ``mla.decode_attention`` over the view."""

import jax.numpy as jnp
import numpy as np
import pytest

from llm_in_practise_tpu.ops import mla_attention as mla
from llm_in_practise_tpu.ops import swa_attention as swa
from llm_in_practise_tpu.serve import paged_kv

PAGE, PER_SLOT, BLOCK, DQ = 8, 8, 2, 16
CACHE, EDGE = PAGE * PER_SLOT, PAGE * BLOCK


def _pools(rng, n_pages, kv_pairs, dtype):
    """``(k1, k2, v)`` by pages, each row's pad columns zero as the pool's
    writers leave them."""
    out = []
    for width in (kv_pairs * DQ, kv_pairs * DQ, kv_pairs * 2 * DQ):
        buf = np.zeros((n_pages, PAGE, paged_kv.lane_whole(width)),
                       np.float32)
        buf[..., :width] = rng.normal(size=(n_pages, PAGE, width))
        out.append((jnp.asarray(buf, dtype), width))
    return out


def _scattered(rng, lengths):
    """Scattered, non-monotone tables over a pool of the rows' pages and
    the trash page (0), which a table names past a row's own pages, as an
    unmapped logical page does."""
    b = len(lengths)
    n_pages = b * PER_SLOT + 1
    table = rng.permutation(np.arange(1, n_pages)).reshape(b, PER_SLOT)
    for row, n in enumerate(lengths):
        table[row, paged_kv.pages_for(n, PAGE):] = paged_kv.TRASH_PAGE
    return n_pages, jnp.asarray(table, jnp.int32)


@pytest.mark.parametrize("lengths, pairs, kv_pairs, dtype", [
    # idle, one key, a page's edge and its neighbours
    ((0, 1, 15, 16, 17), 4, 2, jnp.float32),
    # a block's edge and its neighbours, the whole cache
    ((EDGE - 1, EDGE, EDGE + 1, CACHE), 4, 2, jnp.float32),
    ((EDGE + 1, 0, CACHE, 3 * EDGE - 1), 2, 2, jnp.float32),   # group 1
    ((CACHE, 0, 0, 0), 4, 1, jnp.float32),                     # one live row
    ((0, 0, 0), 4, 2, jnp.float32),                            # none
    ((0, 1, 17, EDGE, CACHE), 4, 2, jnp.bfloat16),
    ((EDGE - 1, CACHE, 5), 2, 2, jnp.bfloat16),
], ids=["page-edges", "block-edges", "group-1", "one-live", "all-idle",
        "bf16", "bf16-group-1"])
def test_pages_in_place_are_the_gathered_view(lengths, pairs, kv_pairs,
                                              dtype):
    rng = np.random.default_rng(len(lengths) + pairs)
    b = len(lengths)
    n_pages, table = _scattered(rng, lengths)
    pools = _pools(rng, n_pages, kv_pairs, dtype)
    lengths = jnp.asarray(lengths, jnp.int32)
    qs = tuple(jnp.asarray(rng.normal(size=(b, 1, pairs, DQ)), dtype)
               for _ in range(2))
    (k1, _), (k2, _), (v, _) = pools
    got = swa.paged_paired_decode_attention(
        qs, (k1, k2), v, table, lengths, scale=DQ ** -0.5, kv_heads=kv_pairs,
        pages_per_block=BLOCK, interpret=True)
    view = [paged_kv.take_pages(buf, table, width) for buf, width in pools]
    want = swa.paired_decode_attention(
        qs, view[:2], view[2], jnp.maximum(lengths - 1, 0), scale=DQ ** -0.5)
    live = np.asarray(lengths) > 0
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for a, r in zip(got, want):
        assert a.shape == r.shape and a.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(a)[live], np.asarray(r)[live],
                                   rtol=tol, atol=tol)
        # a row of length 0 reads nothing: zeros, not the trash page
        assert not np.asarray(a)[~live].any()


@pytest.mark.parametrize("lengths, heads, kv_heads, dq, dv, dtype, pool", [
    # MiMo-V2's global layer: keys of 192 over values of 128, 4 K/V heads
    ((0, 1, EDGE - 1, EDGE, EDGE + 1, CACHE), 8, 4, 192, 128, jnp.float32,
     jnp.float32),
    # Trinity's: 128 / 128 over 8
    ((CACHE, 0, EDGE + 1, 1), 16, 8, 128, 128, jnp.float32, jnp.float32),
    ((0, 0, 0), 8, 4, 192, 128, jnp.float32, jnp.float32),
    # rows narrower than a lane tile: the value width is the caller's
    ((17, CACHE, 0, EDGE), 4, 2, 24, 16, jnp.float32, jnp.float32),
    ((5, CACHE, 0, EDGE), 8, 4, 192, 128, jnp.bfloat16, jnp.bfloat16),
    ((CACHE, 3, EDGE + 1), 16, 8, 128, 128, jnp.bfloat16,
     jnp.float8_e4m3fn),
], ids=["192-over-128", "128-over-128", "all-idle", "padded-rows", "bf16",
        "fp8-pages"])
def test_one_softmax_over_pages_in_place_is_the_gathered_view(
        lengths, heads, kv_heads, dq, dv, dtype, pool):
    """Two layers' pools read along ONE work list, as a program with
    several global layers reads them."""
    rng = np.random.default_rng(len(lengths) + heads)
    b = len(lengths)
    n_pages, table = _scattered(rng, lengths)
    lengths = jnp.asarray(lengths, jnp.int32)
    work = swa.paged_decode_work(lengths, PAGE, PER_SLOT, BLOCK)
    live = np.asarray(lengths) > 0
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for _ in range(2):
        bufs = []
        for width in (kv_heads * dq, kv_heads * dv):
            buf = np.zeros((n_pages, PAGE, paged_kv.lane_whole(width)),
                           np.float32)
            buf[..., :width] = rng.normal(size=(n_pages, PAGE, width))
            bufs.append((jnp.asarray(buf, pool), width))
        q = jnp.asarray(rng.normal(size=(b, 1, heads, dq)), dtype)
        (k, _), (v, _) = bufs
        got = swa.paged_decode_attention(
            q, k, v, table, lengths, scale=dq ** -0.5, kv_heads=kv_heads,
            v_dim=dv, work=work, pages_per_block=BLOCK, interpret=True)
        view_k, view_v = (paged_kv.take_pages(buf, table, width).astype(dtype)
                          for buf, width in bufs)
        want = swa.decode_attention(q, view_k, view_v,
                                    jnp.maximum(lengths - 1, 0),
                                    scale=dq ** -0.5)
        assert got.shape == want.shape == (b, 1, heads, dv)
        assert got.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float32)[live],
            np.asarray(want, np.float32)[live], rtol=tol, atol=tol)
        assert not np.asarray(got, np.float32)[~live].any()


@pytest.mark.parametrize("lengths, heads, rank, dr, dn, dv, dtype", [
    # DeepSeek-V3's row form: 512 + 64 of 640 lanes, 128 heads; a row of
    # length 0, lengths that end mid-page and mid-block, a whole slot
    ((0, 1, PAGE + 3, EDGE - 1, EDGE + 5, CACHE), 128, 512, 64, 16, 16,
     jnp.bfloat16),
    ((EDGE + 1, 0, CACHE, 3 * EDGE - 1), 128, 512, 64, 8, 8, jnp.float32),
    # a toy's row, narrower than a lane tile, heads short of a sublane tile
    ((0, 1, EDGE, EDGE + 1, CACHE), 4, 16, 8, 16, 16, jnp.float32),
    ((5, CACHE, 0, EDGE), 4, 16, 8, 16, 16, jnp.bfloat16),
    ((0, 0, 0), 4, 16, 8, 16, 16, jnp.float32),
], ids=["cell-row-bf16", "cell-row", "toy-row", "toy-row-bf16", "all-idle"])
def test_a_latent_pool_in_place_is_the_gathered_view(
        lengths, heads, rank, dr, dn, dv, dtype):
    """ONE pool, key and value at once (the key its whole row, the value
    its first ``rank`` columns), two layers along one work list, the
    tables scattered; an idle row (``valid`` 0) reads nothing."""
    rng = np.random.default_rng(len(lengths) + heads)
    b, width = len(lengths), rank + dr
    n_pages, table = _scattered(rng, lengths)
    start = jnp.asarray(np.maximum(np.asarray(lengths) - 1, 0), jnp.int32)
    valid = jnp.asarray(np.asarray(lengths) > 0, jnp.int32)
    pages = swa.paged_rows(table, start, valid, PAGE)
    assert pages["lengths"].tolist() == list(lengths)
    pages["work"] = swa.paged_decode_work(pages["lengths"], PAGE, PER_SLOT,
                                          BLOCK)
    live = np.asarray(lengths) > 0
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    scale = (dn + dr) ** -0.5
    for _ in range(2):
        buf = np.zeros((n_pages, PAGE, paged_kv.lane_whole(width)),
                       np.float32)
        buf[..., :width] = rng.normal(size=(n_pages, PAGE, width))
        pool = jnp.asarray(buf, dtype)
        q_nope = jnp.asarray(rng.normal(size=(b, 1, heads, dn)), dtype)
        q_rope = jnp.asarray(rng.normal(size=(b, 1, heads, dr)), dtype)
        w_kvb = jnp.asarray(
            rng.normal(size=(rank, heads, dn + dv)) * rank ** -0.5, dtype)
        got = mla.paged_decode_attention(
            q_nope, q_rope, pool, w_kvb, rank=rank, scale=scale,
            pages_per_block=BLOCK, interpret=True, **pages)
        want = mla.decode_attention(
            q_nope, q_rope, paged_kv.take_pages(pool, table, width), start,
            w_kvb, rank=rank, scale=scale)
        assert got.shape == want.shape == (b, 1, heads, dv)
        assert got.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float32)[live],
            np.asarray(want, np.float32)[live], rtol=tol, atol=tol)
        assert not np.asarray(got, np.float32)[~live].any()


def test_the_work_list_holds_live_blocks_only():
    lengths = jnp.asarray([0, 1, EDGE, EDGE + 1, 0, CACHE], jnp.int32)
    row, blk, total = swa.paged_decode_work(lengths, PAGE, PER_SLOT, BLOCK)
    n = int(total[0])
    assert n == 0 + 1 + 1 + 2 + 0 + PER_SLOT // BLOCK
    assert row[:n].tolist() == [1, 2, 3, 3] + [5] * 4
    assert blk[:n].tolist() == [0, 0, 0, 1, 0, 1, 2, 3]
    assert row.shape == blk.shape == (6 * PER_SLOT // BLOCK,)
