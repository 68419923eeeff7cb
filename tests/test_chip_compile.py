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
def test_flash_attention_compiles(chip, grad):
    q = jax.ShapeDtypeStruct((1, 2048, 32, 128), jnp.bfloat16, sharding=chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, interpret=False)

    def bwd(q, k, v):
        return jax.grad(
            lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    text = _compile(bwd if grad else fwd, q, q, q)
    # forward + dK/dV + dQ kernels in the backward program
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
