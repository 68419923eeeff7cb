"""W8A16 int8 path: codec, fused Pallas matmul, serving integration.

Contract mirrors the 4-bit kernels' tests (``test_nf4_matmul.py``,
``test_int4_matmul.py``): the kernel (interpret mode on CPU — same logic
as TPU) must match the dequant+matmul reference in forward and backward
across tile-aligned and fallback shapes; the codec must be near-lossless
at 8 bits; the leaf type must ride every serving surface the other
formats do — fused apply, QuantizedModel scan sideband, packed IO, TP
sharding (reference W8A16 scheme:
``Quantization/LLM-Compressor/AWQ/quantize_qwen3_4b_awq.py:17-26``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_in_practise_tpu.ops.int8_matmul import int8_matmul
from llm_in_practise_tpu.quant import int8
from llm_in_practise_tpu.quant.int8 import Int8Tensor


def _mk(k, n, seed=0):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(0, 0.02, (k, n)), jnp.float32)
    return w, int8.quantize(w)


def test_codec_near_lossless():
    w, t = _mk(256, 512)
    back = int8.decode(t, jnp.float32)
    # per-channel symmetric int8: max error is half an LSB = scale/2
    err = jnp.abs(back - w)
    assert float(jnp.max(err / jnp.maximum(t.scale[None, :], 1e-12))) <= 0.51
    assert t.q.dtype == jnp.int8
    assert t.nbytes < w.nbytes / 3.9  # 1 byte/param + (N,) scale


def test_codec_rejects_non_2d():
    with pytest.raises(ValueError):
        int8.quantize(jnp.ones((8,)))


@pytest.mark.parametrize("m,k,n", [(16, 256, 512), (5, 128, 128), (1, 384, 640)])
def test_forward_matches_dequant(m, k, n):
    _, t = _mk(k, n)
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (m, k)), jnp.float32)
    ref = x @ int8.decode(t, jnp.float32)
    out = int8_matmul(x, t)
    scale = float(jnp.abs(ref).max())
    assert float(jnp.abs(out - ref).max()) < 0.02 * max(scale, 1.0)


def test_fallback_shapes_match():
    # K=96 has no 128-multiple divisor: _plan is None, dense fallback
    # (which, like the 4-bit kernels', dequantizes in bf16)
    _, t = _mk(96, 160)
    x = jnp.asarray(np.random.default_rng(3).normal(0, 1, (4, 96)), jnp.float32)
    ref = x @ int8.decode(t, jnp.float32)
    out = int8_matmul(x, t)
    scale = float(jnp.abs(ref).max())
    assert float(jnp.abs(out - ref).max()) < 0.02 * max(scale, 1.0)


def test_batched_leading_dims():
    _, t = _mk(128, 256)
    x = jnp.asarray(np.random.default_rng(2).normal(0, 1, (2, 3, 128)),
                    jnp.float32)
    out = int8_matmul(x, t)
    assert out.shape == (2, 3, 256)
    ref = x @ int8.decode(t, jnp.float32)
    assert float(jnp.abs(out - ref).max()) < 0.05


def test_backward_matches_dequant():
    _, t = _mk(256, 512)
    x = jnp.asarray(np.random.default_rng(4).normal(0, 1, (8, 256)),
                    jnp.float32)
    dy = jnp.asarray(np.random.default_rng(5).normal(0, 1, (8, 512)),
                     jnp.float32)

    def f_kernel(x):
        return jnp.vdot(int8_matmul(x, t), dy)

    def f_ref(x):
        return jnp.vdot(x @ int8.decode(t, jnp.float32), dy)

    gk = jax.grad(f_kernel)(x)
    gr = jax.grad(f_ref)(x)
    scale = float(jnp.abs(gr).max())
    assert float(jnp.abs(gk - gr).max()) < 0.02 * max(scale, 1.0)


def test_scale_commutes_with_contraction():
    """The kernel's defining identity: x @ (q·s) == (x @ q)·s exactly in
    f32 — dequant_matmul is the same math the kernel streams."""
    w, t = _mk(128, 128)
    x = jnp.asarray(np.random.default_rng(6).normal(0, 1, (4, 128)),
                    jnp.float32)
    a = x @ int8.decode(t, jnp.float32)
    b = int8.dequant_matmul(x, t)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)


def test_fused_apply_serves_int8_tree(rng):
    """fused_quant_apply over a GPT with Int8 kernel leaves ≈ the bf16
    model (8-bit noise only), on both the kernel and XLA paths."""
    from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
    from llm_in_practise_tpu.peft.fused import fused_quant_apply

    cfg = GPTConfig(vocab_size=128, seq_len=32, n_layer=2, n_head=4,
                    embed_dim=128, dropout=0.0, tie_weights=True,
                    norm_first=True)
    model = GPT(cfg)
    params = model.init(rng, jnp.ones((1, 8), jnp.int32))["params"]
    qtree = int8.quantize_tree(
        params, predicate=lambda p, leaf: leaf.ndim == 2 and "embed" not in p)
    x = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, 16)),
                    jnp.int32)
    ref = model.apply({"params": params}, x, deterministic=True)
    for kernels in (True, False):
        out = fused_quant_apply(model, qtree, x, compute_dtype=jnp.float32,
                                use_kernels=kernels)
        # int8 per-channel quantization noise stays small through 2 layers
        rel = (jnp.abs(out - ref).max()
               / jnp.maximum(jnp.abs(ref).max(), 1e-6))
        assert float(rel) < 0.05, (kernels, float(rel))


def test_packed_io_roundtrip(tmp_path):
    from llm_in_practise_tpu.quant import io as quant_io

    w, t = _mk(128, 256)
    tree = {"block_0": {"mlp": {"fc_in": {"kernel": t}}},
            "norm": {"scale": jnp.ones((128,), jnp.float32)}}
    quant_io.save_packed(str(tmp_path), tree)
    loaded, meta = quant_io.load_packed(str(tmp_path))
    got = loaded["block_0"]["mlp"]["fc_in"]["kernel"]
    assert isinstance(got, Int8Tensor)
    assert got.shape == t.shape
    np.testing.assert_array_equal(np.asarray(got.q), np.asarray(t.q))
    np.testing.assert_allclose(np.asarray(got.scale), np.asarray(t.scale))


def test_int8_tp_serving_matches_single_device(devices):
    from llm_in_practise_tpu.core import mesh as mesh_lib
    from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
    from llm_in_practise_tpu.peft.fused import fused_quant_apply
    from llm_in_practise_tpu.quant.sharding import (
        quant_tree_shardings, shard_quant_tree,
    )
    from llm_in_practise_tpu.utils.tree import flatten_with_paths
    from jax.sharding import PartitionSpec as P

    cfg = GPTConfig(vocab_size=256, seq_len=32, n_layer=2, n_head=4,
                    embed_dim=128, dropout=0.0, tie_weights=True,
                    norm_first=True)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    qtree = int8.quantize_tree(
        params, predicate=lambda p, leaf: leaf.ndim == 2 and leaf.size >= 4096
        and "embed" not in p)
    x = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 32)),
                    jnp.int32)

    def fwd(q, x):
        return fused_quant_apply(model, q, x, use_kernels=False,
                                 compute_dtype=jnp.float32)

    ref = jax.jit(fwd)(qtree, x)
    mesh = mesh_lib.build_mesh(
        mesh_lib.MeshSpec(data=4, model=2), devices=devices)
    sh = quant_tree_shardings(qtree, mesh)
    flat = flatten_with_paths(sh, is_leaf=lambda v: isinstance(v, Int8Tensor))
    # column-parallel in-projection: q N-sharded, scale follows out axis
    q_proj = flat["block_0/attn/q_proj/kernel"]
    assert q_proj.q.spec == P(None, "model")
    assert q_proj.scale.spec == P("model")
    with mesh:
        out = jax.jit(fwd)(shard_quant_tree(qtree, mesh), x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_quantized_scan_serving_int8(rng):
    """Int8 under the decode scan: stacked q/scale ride the sideband and
    the stacked tree's greedy tokens (``infer/generate.py``, the path
    that decodes a stacked model) equal the unrolled tree's exactly."""
    from llm_in_practise_tpu.infer.generate import generate
    from llm_in_practise_tpu.models.qwen3 import (
        Qwen3, qwen3_config, stack_layer_params,
    )
    from llm_in_practise_tpu.serve.quantized import QuantizedModel

    cfg_u = qwen3_config(vocab_size=128, compute_dtype="float32")
    pu = Qwen3(cfg_u).init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"]
    qu = int8.quantize_tree(
        pu, predicate=lambda p, leaf: leaf.ndim == 2 and "embed" not in p
        and "norm" not in p)
    qs = stack_layer_params(qu, cfg_u.n_layer)

    def run(model, params):
        out = generate(
            QuantizedModel(model, compute_dtype=jnp.float32,
                           use_kernels=False),
            params, jnp.asarray([list(range(1, 9))], jnp.int32),
            max_new_tokens=8, greedy=True, cache_len=64,
            cache_dtype=jnp.float32)
        return np.asarray(out).tolist()

    a = run(Qwen3(cfg_u), qu)
    b = run(Qwen3(cfg_u.replace(scan_layers=True)), qs)
    assert a == b and len(a[0]) == 16


def test_kernel_matmul_on_tpu():
    """TPU-gated smoke of the Pallas int8 kernel (ADVICE r4: the kernel
    is probe-only infrastructure — production dispatch routes Int8Tensor
    to the XLA dequant matmul, measured faster — so a TPU-lowering
    regression would otherwise go unnoticed until the next tile probe).
    Skips off-TPU; the CPU interpret-mode path is covered below."""
    import pytest

    if jax.default_backend() != "tpu":
        pytest.skip("real-TPU lowering smoke; interpret mode covered elsewhere")
    from llm_in_practise_tpu.ops.int8_matmul import int8_matmul

    rng = np.random.default_rng(11)
    w = jnp.asarray(rng.normal(0, 0.02, (512, 256)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (16, 512)), jnp.bfloat16)
    t = int8.quantize(w)
    got = int8_matmul(x, t, jnp.bfloat16)
    want = int8.dequant_matmul(x, t)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_quantize_3d_stacked_kernel():
    """Stacked (n_layer, in, out) kernels quantize with per-(layer, out)
    scales and decode back — what quantize_base_lowmem(fmt="int8") hits
    on scan-layout trees (its predicate admits ndim 3)."""
    rng = np.random.default_rng(7)
    w = jnp.asarray(rng.normal(0, 0.02, (3, 64, 32)), jnp.float32)
    t = int8.quantize(w)
    assert t.q.shape == (3, 64, 32) and t.scale.shape == (3, 32)
    back = int8.decode(t, jnp.float32)
    err = jnp.abs(back - w)
    assert float(jnp.max(err / jnp.maximum(t.scale[:, None, :], 1e-12))) <= 0.51
    # per-layer slices equal independently-quantized layers
    t0 = int8.quantize(w[1])
    np.testing.assert_array_equal(np.asarray(t.q[1]), np.asarray(t0.q))
    # the matmul helper falls back to decode for 3-D (sliced before use
    # in the scan; direct calls must still be correct)
    x = jnp.asarray(rng.normal(0, 1, (4, 64)), jnp.float32)
    got = int8.dequant_matmul(x, t)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(x @ int8.decode(t, jnp.float32)),
        rtol=1e-5, atol=1e-5)
