"""Scan-layers cached decode: stacked KV cache == unrolled.

``scan_layers=True`` is the training layout, and its cached forward is
what post-training sampling (``infer/generate.py``) decodes through:
``init_cache`` returns a stacked ``[{k: (L, B, T, H, D), v: ...,
index}]`` cache and decode scans one block over the depth axis.

These tests pin exact equality between the two layouts at the model
level: raw prefill/decode, vector (per-slot) indices, and greedy
generation over packed weights. The serving engine serves the unrolled
layout only (PR 31): it refuses a stacked model at construction, and
its readers refuse the stacked rows an older replica may have left in
a shared KV pool.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_in_practise_tpu.models.qwen3 import (
    Qwen3, qwen3_config, stack_layer_params,
)
from llm_in_practise_tpu.serve.engine import InferenceEngine, SamplingParams


@pytest.fixture(scope="module")
def models():
    cfg_u = qwen3_config(vocab_size=128, compute_dtype="float32")
    cfg_s = cfg_u.replace(scan_layers=True)
    pu = Qwen3(cfg_u).init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"]
    ps = stack_layer_params(pu, cfg_u.n_layer)
    return Qwen3(cfg_u), pu, Qwen3(cfg_s), ps


def test_cache_layouts(models):
    mu, _, ms, _ = models
    cu = mu.init_cache(2, 32)
    cs = ms.init_cache(2, 32)
    assert len(cu) == mu.cfg.n_layer and cu[0]["k"].ndim == 4
    assert len(cs) == 1 and cs[0]["k"].ndim == 5
    assert cs[0]["k"].shape[:3] == (ms.cfg.n_layer, 2, 32)
    assert mu.cache_slot_axis == 0 and ms.cache_slot_axis == 1


def test_prefill_and_decode_equal(models):
    mu, pu, ms, ps = models
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, 128, (2, 6)), jnp.int32)
    cu = mu.init_cache(2, 32, dtype=jnp.float32)
    cs = ms.init_cache(2, 32, dtype=jnp.float32)
    lu, cu = mu.apply({"params": pu}, prompt, cache=cu)
    ls, cs = ms.apply({"params": ps}, prompt, cache=cs)
    np.testing.assert_allclose(lu, ls, atol=1e-4)
    tok_u = jnp.argmax(lu[:, -1], -1)[:, None].astype(jnp.int32)
    tok_s = jnp.argmax(ls[:, -1], -1)[:, None].astype(jnp.int32)
    for _ in range(4):
        lu, cu = mu.apply({"params": pu}, tok_u, cache=cu)
        ls, cs = ms.apply({"params": ps}, tok_s, cache=cs)
        np.testing.assert_allclose(lu, ls, atol=1e-4)
        tok_u = jnp.argmax(lu[:, -1], -1)[:, None].astype(jnp.int32)
        tok_s = jnp.argmax(ls[:, -1], -1)[:, None].astype(jnp.int32)
        assert (tok_u == tok_s).all()
    assert int(cs[0]["index"]) == 6 + 4


def test_vector_index_per_slot_depth(models):
    """Continuous-batching shape: each slot at its own depth."""
    _, _, ms, ps = models
    cs = ms.init_cache(2, 32, dtype=jnp.float32)
    cs[0]["index"] = jnp.asarray([3, 7], jnp.int32)
    tok = jnp.asarray([[5], [9]], jnp.int32)
    logits, cs2 = ms.apply({"params": ps}, tok, cache=cs)
    assert logits.shape == (2, 1, 128)
    assert (np.asarray(cs2[0]["index"]) == [4, 8]).all()
    # the write landed at each slot's own depth
    assert float(jnp.abs(cs2[0]["k"][:, 0, 3]).sum()) > 0
    assert float(jnp.abs(cs2[0]["k"][:, 1, 7]).sum()) > 0
    assert float(jnp.abs(cs2[0]["k"][:, 1, 3]).sum()) == 0


def test_quantized_scan_serving_equals_unrolled(models):
    """NF4 decode under scan: stacked quant components ride the scan as
    sideband inputs (layers.scan_sideband) and the fused interceptor
    serves each layer's slice — greedy tokens of the stacked tree equal
    the unrolled tree's. XLA dequant path here (Pallas kernels need the
    TPU)."""
    from llm_in_practise_tpu.infer.generate import generate
    from llm_in_practise_tpu.peft.qlora import quantize_base
    from llm_in_practise_tpu.serve.quantized import QuantizedModel

    mu, pu, ms, _ = models
    qu = quantize_base(pu)
    qs = stack_layer_params(qu, mu.cfg.n_layer)
    rng = np.random.default_rng(1)
    prompts = jnp.asarray(rng.integers(0, 128, (3, 23)), jnp.int32)

    def run(model, params):
        return np.asarray(generate(
            QuantizedModel(model, compute_dtype=jnp.float32,
                           use_kernels=False),
            params, prompts, max_new_tokens=12, greedy=True,
            cache_len=64, cache_dtype=jnp.float32))

    a, b = run(mu, qu), run(ms, qs)
    assert a.shape == (3, 35)
    np.testing.assert_array_equal(a, b)


def test_engine_refuses_stacked_model(models):
    """One decision, one place: a model whose KV buffers put the layer
    on axis 0 is refused when the engine is built — bare, wrapped
    (``QuantizedModel`` passes ``cache_slot_axis`` through), as the
    draft, and whatever the KV layout — and the message names the
    layout the engine does serve."""
    from llm_in_practise_tpu.serve.quantized import QuantizedModel

    mu, pu, ms, ps = models
    with pytest.raises(ValueError, match="unrolled layout"):
        InferenceEngine(ms, ps, max_slots=2, cache_len=64)
    with pytest.raises(ValueError, match="unrolled layout"):
        InferenceEngine(ms, ps, max_slots=2, cache_len=64,
                        kv_layout="paged")
    with pytest.raises(ValueError, match="unrolled layout"):
        InferenceEngine(QuantizedModel(ms, compute_dtype=jnp.float32,
                                       use_kernels=False),
                        ps, max_slots=2, cache_len=64)
    with pytest.raises(ValueError, match="draft_model.*unrolled layout"):
        InferenceEngine(mu, pu, max_slots=2, cache_len=64,
                        speculative_k=2, draft_model=ms, draft_params=ps)


@pytest.mark.parametrize("kv_layout", ["contiguous", "paged"])
def test_stacked_rows_in_a_shared_pool_are_refused(models, kv_layout):
    """``slot_axis`` stays on the kv-pool wire: rows an older (stacked)
    replica wrote are transposed relative to this engine's writes, so
    the reader must pass them by — same tokens, no pool hit — where
    the same rows tagged 0 are reused."""
    from llm_in_practise_tpu.serve.kv_pool import (
        HostKVPool, TieredKV, decode_entry, encode_entry,
    )

    mu, pu, _, _ = models
    pool = HostKVPool(max_tokens=1 << 16)
    prompt = list(range(40))

    def serve_one():
        tiers = TieredKV(host_pool=pool, async_offload=False)
        eng = InferenceEngine(mu, pu, max_slots=2, cache_len=128,
                              prefix_cache=True, kv_layout=kv_layout,
                              kv_pool=tiers)
        eng.start()
        req = eng.submit(prompt, SamplingParams(greedy=True, max_tokens=4))
        out = req.result()
        eng.stop()
        return out, req.cache_outcome

    first, outcome = serve_one()            # seeds the pool, tagged 0
    hosts = list(pool._entries.values())
    assert outcome == "cold" and hosts
    assert all(h.slot_axis == 0 for h in hosts)
    assert serve_one() == (first, "hit")    # a restarted engine reuses it
    for h in hosts:
        h.slot_axis = 1
    # the tag survives the wire
    assert decode_entry(encode_entry(hosts[0])).slot_axis == 1
    assert serve_one() == (first, "cold")


def test_quantized_scan_no_cache_forward(models):
    """Cache-less quantized forward under scan (the TRAINING scan path,
    whose sideband now carries the packed weights): logits equal the
    unrolled quantized forward."""
    from llm_in_practise_tpu.peft.qlora import quantize_base
    from llm_in_practise_tpu.serve.quantized import QuantizedModel

    mu, pu, ms, _ = models
    qu = quantize_base(pu)
    qs = stack_layer_params(qu, mu.cfg.n_layer)
    x = jnp.ones((1, 4), jnp.int32)
    a = QuantizedModel(mu, compute_dtype=jnp.float32,
                       use_kernels=False).apply({"params": qu}, x)
    b = QuantizedModel(ms, compute_dtype=jnp.float32,
                       use_kernels=False).apply({"params": qs}, x)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-4)
