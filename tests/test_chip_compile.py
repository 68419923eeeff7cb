"""The main path's Pallas kernels, compiled at real widths for a TPU v5e
that is described and not attached (``jax.experimental.topologies``).

Interpret-mode tests cannot see what the chip's compiler refuses: a
slice not aligned to the tiling, more fast memory than a kernel may use.
These compiles can, cost no chip time, and guard every later PR. Nothing
runs, so they say nothing about results; ``chip_smoke.py`` does that on
the chip.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or it logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from llm_in_practise_tpu.ops.flash_attention import flash_attention
from llm_in_practise_tpu.ops.int4_matmul import int4_matmul
from llm_in_practise_tpu.ops.nf4_matmul import nf4_matmul
from llm_in_practise_tpu.quant import int4, nf4


@pytest.fixture(scope="module")
def chip():
    """Sharding on one device of a described v5e 2x2 host, with the
    persistent compile cache off around the module: such an entry can
    be written but never read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache.compilation_cache import (
        reset_cache,
    )

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", saved)
    reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _x(m, k, chip):
    return jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=chip)


@pytest.mark.parametrize("k,n", [(4096, 12288), (12288, 4096)])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_nf4_matmul_compiles(chip, k, n, grad):
    t = _shapes(jax.eval_shape(
        nf4.quantize, jax.ShapeDtypeStruct((k, n), jnp.float32)), chip)

    def fwd(x, t):
        return nf4_matmul(x, t, jnp.bfloat16, None, False)

    def bwd(x, t):
        return jax.grad(lambda x: fwd(x, t).astype(jnp.float32).sum())(x)

    # forward at decode rows, gradient at training rows
    _compile(bwd if grad else fwd, _x(1024 if grad else 16, k, chip), t)


def test_int4_matmul_compiles(chip):
    k, n = 4096, 12288
    t = _shapes(jax.eval_shape(
        int4.rtn_quantize, jax.ShapeDtypeStruct((k, n), jnp.float32)), chip)
    _compile(lambda x, t: int4_matmul(x, t, jnp.bfloat16, False),
             _x(16, k, chip), t)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("shape,kv_heads,dtype", [
    ((1, 2048, 32, 128), 32, jnp.bfloat16),   # two 1,024 tiles a side
    ((8, 1024, 40, 128), 8, jnp.bfloat16),    # qwen3-14b-qlora.sft-1k
    ((1, 1152, 8, 128), 2, jnp.bfloat16),     # 384 tiles: 9 x 128
    ((2, 1024, 8, 64), 8, jnp.bfloat16),      # heads in front: 64 lanes
    ((2, 1024, 8, 256), 2, jnp.float32),      # a 256-wide float32 head
], ids=["mha-2k", "qlora-cell", "l1152", "d64", "d256-f32"])
def test_flash_attention_compiles(chip, grad, shape, kv_heads, dtype):
    b, length, _, d = shape
    q = jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    k = jax.ShapeDtypeStruct((b, length, kv_heads, d), dtype, sharding=chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, interpret=False)

    def bwd(q, k, v):
        return jax.grad(
            lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    text = _compile(bwd if grad else fwd, q, k, k)
    # forward + dQ + dK/dV kernels in the backward program
    assert text.count("tpu_custom_call") >= (3 if grad else 1)


@pytest.mark.parametrize("tokens", [64, 4096])
def test_grouped_expert_layer_compiles(chip, tokens, monkeypatch):
    """The dropless expert layer at SDAR-30B-A3B's widths (128 experts of
    2048 x 768, top-8): a block-decode pass's 64 tokens and a batched
    prefill's 4,096. The kernel's whole-K, whole-N tiles must fit the
    chip's fast memory."""
    from llm_in_practise_tpu.ops import grouped_experts as ge

    # the CPU backend would pick interpret mode; compile the kernel itself
    monkeypatch.setattr(ge, "interpret_default", lambda: False)
    e, h, w, k = 128, 2048, 768, 8
    s = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=chip)
    text = _compile(
        ge.grouped_expert_ffn, s((tokens, h), jnp.bfloat16),
        s((tokens, k), jnp.int32), s((tokens, k), jnp.float32),
        s((e, h, w), jnp.bfloat16), s((e, h, w), jnp.bfloat16),
        s((e, w, h), jnp.bfloat16))
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("tokens", [16, 2048])
def test_held_expert_share_compiles(chip, tokens, monkeypatch):
    """One chip's share of DeepSeek-V3's routed layer (16 of 256 experts
    of 7168 x 2048, top-8): a decode step's 16 tokens and a chunk row's
    2,048, both branches of the rows-buffer ``cond``. The weight tiles
    (1792 x 1024 and 2048 x 1024) must fit the chip's fast memory."""
    from llm_in_practise_tpu.ops import grouped_experts as ge

    monkeypatch.setattr(ge, "interpret_default", lambda: False)
    assert ge._tile(2048, 768) == (2048, 768)       # SDAR's: whole
    assert ge._tile(7168, 2048) == (1792, 1024)
    assert ge._tile(2048, 7168) == (2048, 1024)
    e, h, w, k = 16, 7168, 2048, 8
    s = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=chip)
    text = _compile(
        lambda *a: ge.grouped_expert_ffn(*a, held=(0, e), n_experts=256),
        s((tokens, h), jnp.bfloat16), s((tokens, k), jnp.int32),
        s((tokens, k), jnp.float32), s((e, h, w), jnp.bfloat16),
        s((e, h, w), jnp.bfloat16), s((e, w, h), jnp.bfloat16))
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("keys", [4096, 16384])
def test_mla_prefill_attention_compiles(chip, keys, monkeypatch):
    """The blocked MLA prefill form at the published widths: a
    2,048-query chunk over a latent view, 128 heads of 192-wide keys and
    128-wide values decompressed 4,096 keys at a time."""
    from llm_in_practise_tpu.ops import mla_attention as mla

    monkeypatch.setattr(mla, "interpret_default", lambda: False)
    s = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=chip)
    _compile(
        lambda *a: mla.prefill_attention(*a, rank=512, scale=0.1),
        s((1, 2048, 128, 128)), s((1, 2048, 128, 64)), s((1, keys, 576)),
        s((1,), jnp.int32), s((512, 128, 256)))


def test_latent_page_pool_keeps_its_layout(chip):
    """One layer of ``deepseek-v3.long-doc-qa``'s decode program at the
    cell's pool shape, through the engine's own accessors: the view
    gathered a page at a time (``paged_kv.take_pages``), the absorbed
    attention over it, the 16-row write-back (``paged_kv.set_page_rows``),
    the pool donated. The compiler must keep the pool ROW-major at the
    program's entry and lay none of it out again: a pool-shaped ``copy``
    or ``transpose`` is a pass over 1.68 GB of pools a step (PERF.md
    section 6, PR 37)."""
    import re

    from llm_in_practise_tpu.ops import mla_attention as mla
    from llm_in_practise_tpu.serve import paged_kv

    slots, width, page, heads, rank, rope = 16, 16384, 16, 128, 512, 64
    row = rank + rope
    pool = (slots * width // page + 1, page, paged_kv.lane_whole(row))
    assert paged_kv.stored_by_pages([(row,)]) and pool[2] == 640

    def step(buf, page_idx, sidx, pos, q_nope, q_rope, new, w_kvb):
        view = paged_kv.take_pages(buf, page_idx, row)
        view = jax.vmap(lambda v, n, i: jax.lax.dynamic_update_slice(
            v, n, (i, 0)))(view, new, pos)
        out = mla.decode_attention(q_nope, q_rope, view, pos, w_kvb,
                                   rank=rank, scale=0.1)
        rows = jnp.take_along_axis(view, pos[:, None, None], axis=1)
        return out, paged_kv.set_page_rows(buf, sidx, rows[:, 0])

    s = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=chip)
    text = jax.jit(step, donate_argnums=(0,)).lower(
        s(pool), s((slots, width // page), jnp.int32),
        s((slots,), jnp.int32), s((slots,), jnp.int32),
        s((slots, 1, heads, 128)), s((slots, 1, heads, rope)),
        s((slots, 1, row)), s((rank, heads, 256))).compile().as_text()
    dims = ",".join(map(str, pool))
    entry = re.search(r"entry_computation_layout=\{\((.*?)\)->", text, re.S)
    layout = re.search(rf"bf16\[{dims}\]\{{([\d,]+)", entry.group(1))
    assert layout.group(1) == "2,1,0", layout.group(0)
    assert not re.findall(rf"= bf16\[{dims}\]\S* (?:copy|transpose)\(", text)
    # the view's gather takes whole pages
    assert f"slice_sizes={{1,{page},{pool[2]}}}" in text


@pytest.mark.parametrize("keys", [4096, 32768])
def test_global_prefill_attention_compiles(chip, keys, monkeypatch):
    """MiMo-V2.5's global layer: a 2,048-query chunk over a view, 64 heads
    over 4 K/V heads, keys 192 wide over values 128."""
    from llm_in_practise_tpu.ops import swa_attention as swa

    monkeypatch.setattr(swa, "interpret_default", lambda: False)
    s = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=chip)
    text = _compile(
        lambda q, k, v, start: swa.prefill_attention(q, k, v, start,
                                                     scale=0.07),
        s((1, 2048, 64, 192)), s((1, keys, 4, 192)), s((1, keys, 4, 128)),
        s((1,), jnp.int32))
    assert swa.GLOBAL_KERNEL in text


def test_window_prefill_attention_compiles(chip, monkeypatch):
    """Its window layer: the chunk over its own keys under the band (the
    eight query heads of a K/V head in one q tile), the 128-row ring's
    corner and the sink."""
    from llm_in_practise_tpu.ops import swa_attention as swa

    monkeypatch.setattr(swa, "interpret_default", lambda: False)
    s = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=chip)
    text = _compile(
        lambda q, k, v, rk, rv, start, sink: swa.prefill_attention(
            q, k, v, start, scale=0.07, window=128, sink=sink,
            cached=(rk, rv)),
        s((1, 2048, 64, 192)), s((1, 2048, 8, 192)), s((1, 2048, 8, 128)),
        s((1, 128, 8, 192)), s((1, 128, 8, 128)), s((1,), jnp.int32),
        s((64,), jnp.float32))
    assert swa.WINDOW_KERNEL in text


def test_a_ring_longer_than_a_chunk_goes_through_the_kernel(chip,
                                                            monkeypatch):
    """Trinity-Large's window layer (48 heads over 8 K/V heads of 128, a
    window of 4,096 over a 2,048-token chunk): ONE kernel call over [the
    ring ‖ the chunk's own keys], and no dense (queries x ring) score
    tensor in the program (the corner above would be 48 x 2,048 x 4,096
    float32); MiMo-V2's 128-row ring keeps the other path."""
    import re

    from llm_in_practise_tpu.ops import swa_attention as swa

    monkeypatch.setattr(swa, "interpret_default", lambda: False)
    s = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=chip)
    text = _compile(
        lambda q, k, v, rk, rv, start: swa.prefill_attention(
            q, k, v, start, scale=128 ** -0.5, window=4096,
            cached=(rk, rv)),
        s((1, 2048, 48, 128)), s((1, 2048, 8, 128)), s((1, 2048, 8, 128)),
        s((1, 4096, 8, 128)), s((1, 4096, 8, 128)), s((1,), jnp.int32))
    assert swa.WINDOW_RING_KERNEL in text
    assert swa.WINDOW_KERNEL + "." not in text.replace(
        swa.WINDOW_RING_KERNEL, "")
    assert not re.search(r"f32\[1,48,2048,4096\]|f32\[1,8,6,2048,4096\]",
                         text)


def test_global_page_pool_keeps_its_layout(chip):
    """One global layer of ``mimo-v2.5.agent-context``'s decode program at
    the cell's pool shapes, through the engine's own accessors: keys of 4
    x 192 and values of 4 x 128 as ONE vector a row (768 and 512: whole
    lane tiles), gathered a page at a time, attended flat, the 16-row
    write-back, the pools donated. Row-major at the entry, no pool-shaped
    ``copy`` / ``transpose``, whole pages gathered (PERF.md section 6, PR
    39: a ``(4, 192)`` row is stored token-minor and copied twice)."""
    import re

    from llm_in_practise_tpu.ops import swa_attention as swa
    from llm_in_practise_tpu.serve import paged_kv

    slots, width, page, heads, hk = 16, 32768, 16, 64, 4
    rows = {"k": hk * 192, "v": hk * 128}
    assert paged_kv.stored_by_pages([(w,) for w in rows.values()])
    pools = {key: (slots * width // page + 1, page, paged_kv.lane_whole(w))
             for key, w in rows.items()}
    assert pools["k"][2] == 768 and pools["v"][2] == 512

    def step(k_buf, v_buf, page_idx, sidx, pos, q, k_new, v_new):
        views = []
        for buf, new, w in ((k_buf, k_new, rows["k"]),
                            (v_buf, v_new, rows["v"])):
            view = paged_kv.take_pages(buf, page_idx, w)
            views.append(jax.vmap(
                lambda v, n, i: jax.lax.dynamic_update_slice(
                    v, n, (i, 0)))(view, new, pos))
        out = swa.decode_attention(q, *views, pos, scale=0.07)
        back = [paged_kv.set_page_rows(
            buf, sidx, jnp.take_along_axis(
                view, pos[:, None, None], axis=1)[:, 0])
            for buf, view in zip((k_buf, v_buf), views)]
        return out, back

    s = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=chip)
    text = jax.jit(step, donate_argnums=(0, 1)).lower(
        s(pools["k"]), s(pools["v"]), s((slots, width // page), jnp.int32),
        s((slots,), jnp.int32), s((slots,), jnp.int32),
        s((slots, 1, heads, 192)), s((slots, 1, rows["k"])),
        s((slots, 1, rows["v"]))).compile().as_text()
    entry = re.search(r"entry_computation_layout=\{\((.*?)\)->", text, re.S)
    for pool in pools.values():
        dims = ",".join(map(str, pool))
        layout = re.search(rf"bf16\[{dims}\]\{{([\d,]+)", entry.group(1))
        assert layout.group(1) == "2,1,0", layout.group(0)
        assert not re.findall(
            rf"= bf16\[{dims}\]\S* (?:copy|transpose)\(", text)
        assert f"slice_sizes={{1,{page},{pool[2]}}}" in text


def test_the_older_pools_keep_their_shapes():
    """What PR 39 added to the storage rule leaves the 8B, SDAR and latent
    pools as they were: rows of (8, 128) and (4, 128) flat and unpadded, a
    latent row of 576 by pages of 640."""
    from llm_in_practise_tpu.models import deepseek_v3 as dsv3
    from llm_in_practise_tpu.models import qwen3
    from llm_in_practise_tpu.serve import paged_kv

    for hk in (8, 4):
        cfg = qwen3.qwen3_config(vocab_size=64, hidden_size=64, n_layer=1,
                                 n_head=8, n_kv_head=hk, head_dim=128)
        pg = paged_kv.PagedKV(qwen3.Qwen3(cfg), max_slots=2, cache_len=64,
                              page_size=16, pool_tokens=128,
                              dtype=jnp.bfloat16)
        assert (pg.form, pg.by_slot) == ("rows", [False])
        assert {k: v.shape for k, v in pg.kv[0].items()} == {
            "k": (144, hk, 128), "v": (144, hk, 128)}
        assert pg.row_bytes == 2 * hk * 128 * 2 and pg.slot_state_bytes == 0
        pg.close()
    cfg = dsv3.deepseek_v3_config(kv_lora_rank=512, qk_rope_head_dim=64,
                                  n_layer=2)
    pg = paged_kv.PagedKV(dsv3.DeepSeekV3(cfg), max_slots=2, cache_len=64,
                          page_size=16, pool_tokens=128, dtype=jnp.bfloat16)
    assert (pg.form, pg.by_slot) == ("pages", [False, False])
    assert pg.kv[0]["ckv"].shape == (9, 16, 640)
    assert pg.row_bytes == 2 * 640 * 2
    pg.close()


@pytest.mark.parametrize("length,block_t", [(2048, None), (2048, 128)])
def test_ssm_chunk_scan_compiles(chip, length, block_t):
    """The selective scan at Phi-4-mini-flash's widths (5,120 channels x
    16 states, a 2,048-token chunk): B and C in scalar memory (a time
    block of 512 would not fit it)."""
    from llm_in_practise_tpu.ops import selective_scan as ssm

    f32 = jnp.float32
    shapes = ((1, length, 5120), (1, length, 5120), (1, length, 16),
              (1, length, 16), (16, 5120), (5120,), (1, 16, 5120))
    args = [jax.ShapeDtypeStruct(s, f32, sharding=chip) for s in shapes]
    text = _compile(lambda *a: ssm.chunk_scan(*a, block_t=block_t,
                                              interpret=False), *args)
    assert ssm.CHUNK_KERNEL in text


def test_paged_decode_attention_compiles(chip):
    """One reader of Phi-4-mini-flash's paged layer at the cell's shape:
    16 slots of 1,024 pages of 16 rows, a pool of 16,385 pages in three
    by-pages buffers, 20 query pairs on 10 K/V pairs of 64 / 128; the
    pages copied where they lie, 32 a block."""
    from llm_in_practise_tpu.ops import swa_attention as swa

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def reader(q1, q2, k1, k2, v, table, lengths):
        return swa.paged_paired_decode_attention(
            (q1, q2), (k1, k2), v, table, lengths, scale=0.125, kv_heads=10,
            interpret=False)

    text = _compile(reader, arg((16, 1, 20, 64)), arg((16, 1, 20, 64)),
                    arg((16385, 16, 640)), arg((16385, 16, 640)),
                    arg((16385, 16, 1280)), arg((16, 1024), jnp.int32),
                    arg((16,), jnp.int32))
    # by its name, and by the result the cell's reader finds it by
    assert swa.PAGED_DECODE_KERNEL in text and "f32[16,20,256]" in text
    # no view: nothing of (16, n, 640 | 1280) is built around the kernel
    assert "[16,16384,640]" not in text and "[16,16384,1280]" not in text


@pytest.mark.parametrize("heads, kv_heads, dq, dv, pool", [
    (64, 4, 192, 128, jnp.bfloat16), (64, 4, 192, 128, jnp.float8_e4m3fn),
], ids=["mimo", "mimo-fp8"])
def test_global_paged_decode_attention_compiles(chip, heads, kv_heads, dq,
                                                dv, pool):
    """One global layer's reader of MiMo-V2.5 at its cell's shapes: 16
    slots of 2,048 pages of 16 rows over a pool of 32,769 pages, key rows
    of 768 lanes beside value rows of 512; ONE softmax a head, the same
    kernel as the paired reader's. The fp8 pool is
    ``tools/swa_check_control.py``'s control; Trinity's shapes (1,024 /
    1,024 lanes; its model still gathers: PERF.md, PR 49) are what
    ``tools/paged_decode_bakeoff.py`` measures."""
    from llm_in_practise_tpu.ops import swa_attention as swa

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def reader(q, k, v, table, lengths):
        return swa.paged_decode_attention(
            q, k, v, table, lengths, scale=dq ** -0.5, kv_heads=kv_heads,
            v_dim=dv, interpret=False)

    text = _compile(reader, arg((16, 1, heads, dq)),
                    arg((32769, 16, kv_heads * dq), pool),
                    arg((32769, 16, kv_heads * dv), pool),
                    arg((16, 2048), jnp.int32), arg((16,), jnp.int32))
    # by its name, and by the (slots, query heads, n) result the cells'
    # readers find the global decode attention by
    assert swa.GLOBAL_PAGED_KERNEL in text
    assert f"f32[16,{heads},{dv}]" in text
    assert swa.GLOBAL_KERNEL not in text


def test_latent_paged_decode_attention_compiles(chip):
    """One layer of DeepSeek-V3's decode program at its cell's shapes: 16
    slots of 1,024 pages of 16 rows over a pool of 16,385 pages whose
    640-lane row (512 + 64 up to whole lanes) is key AND value, 128 heads.
    The row's write into its page (the pool donated), then ONE pool
    operand to the same kernel as the other readers', the absorb and
    ``W^V`` around it; no view is built and the pool is not laid out
    again."""
    import re

    from llm_in_practise_tpu.models import layers
    from llm_in_practise_tpu.ops import mla_attention as mla
    from llm_in_practise_tpu.ops import swa_attention as swa

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def step(pool, q_nope, q_rope, row, w_kvb, table, start, valid):
        pool = layers.page_row_write(pool, table, start, valid, row)
        out = mla.paged_decode_attention(
            q_nope, q_rope, pool, w_kvb, rank=512, scale=0.1,
            interpret=False, **swa.paged_rows(table, start, valid, 16))
        return out, pool

    text = jax.jit(step, donate_argnums=(0,)).lower(
        arg((16385, 16, 640)), arg((16, 1, 128, 128)),
        arg((16, 1, 128, 64)), arg((16, 576)), arg((512, 128, 256)),
        arg((16, 1024), jnp.int32), arg((16,), jnp.int32),
        arg((16,), jnp.int32)).compile().as_text()
    # by its name, and by the (slots, heads, n) result the cell's reader
    # finds the decode attention by
    assert mla.PAGED_KERNEL in text and "f32[16,128,512]" in text
    assert "[16,16384,640]" not in text and "[16,16384,576]" not in text
    assert not re.findall(
        r"= bf16\[16385,16,640\]\S* (?:copy|transpose)\(", text)


def test_lfm2_moe_decode_step_compiles(chip, monkeypatch):
    """The decode trunk of the LFM2-24B-A2B cut at its cell's shapes (32
    slots of 512 pages of 16 rows; 10 layers at the published widths, 64
    experts of 2,048 x 1,536 a routed layer, STACKED by run): both attention
    layers read their pages where they lie (64-wide heads, 512-lane rows),
    each routed layer's three grouped matmuls take the run's whole stack
    (a 2,048 x 1,536 expert matrix is over ``TILE_ELEMENTS``: split tiles),
    and no layer's experts are copied out of the stack."""
    import json
    import re

    from llm_in_practise_tpu.models import layers
    from llm_in_practise_tpu.models import lfm2_moe as lm
    from llm_in_practise_tpu.ops import grouped_experts as ge
    from llm_in_practise_tpu.ops import swa_attention as swa

    monkeypatch.setattr(ge, "interpret_default", lambda: False)
    monkeypatch.setattr(swa, "interpret_default", lambda: False)
    assert ge._tile(2048, 1536) == (2048, 768)
    assert ge._tile(1536, 2048) == (1536, 1024)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "lfm2-24b-a2b-bf16-serve.json")) as f:
        cfg = lm.Lfm2MoeConfig.from_hf_config(json.load(f))
    model = lm.Lfm2Moe(cfg)
    slots, per_slot, page = 32, 512, 16
    pages = slots * per_slot + 1

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def decode(params, ids, index, valid, table, pools, tails):
        pools, tails = iter(pools), iter(tails)
        cache = []
        for kind, stats in zip(cfg.layer_types, model.step_stats(slots)):
            entry = {"index": index, layers.VALID_KEY: valid, **stats}
            if kind == lm.CONV:
                entry["conv"] = next(tails)
            else:
                k, v = next(pools)
                entry.update(k=k, v=v, **{layers.PAGES_KEY: table})
            cache.append(entry)
        return model.apply({"params": params}, ids, cache=cache)

    params = _shapes(jax.eval_shape(lambda: lm.random_params(cfg, 0)), chip)
    text = _compile(
        decode, params, arg((slots, 1), jnp.int32), arg((slots,), jnp.int32),
        arg((slots,), jnp.int32), arg((slots, per_slot), jnp.int32),
        [(arg((pages, page, 512)), arg((pages, page, 512)))] * 2,
        [arg((slots, 2, 2048))] * 8)
    # 2 paged readers + 3 grouped matmuls x (2 attention layers + 2 traced
    # bodies of three conv layers)
    assert text.count("tpu_custom_call") == 14
    assert swa.GLOBAL_PAGED_KERNEL in text
    assert not re.search(r" copy\([^\n]*\[(?:64|192),(?:2048|1536),", text)
