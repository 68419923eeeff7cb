"""Mixed-format quantization (int8 MLP + NF4 attention) — the 14B
single-chip serving split.

Round-4 arithmetic: a 14B all-int8 tree leaves no KV room on a 16 GiB
chip and all-NF4 decode misses the 100 ms TPOT gate; the mixed preset
pays int8's bytes only where they buy decode rate (the MLP's 81% of
layer bytes). These tests pin the split and its serving exactness; the
on-TPU latency evidence is the round-5 14B serve ladder artifact.
"""

import jax
import jax.numpy as jnp
import numpy as np

from llm_in_practise_tpu.peft.fused import _is_quant
from llm_in_practise_tpu.peft.qlora import (
    mixed_serve_fmt, quantize_base_lowmem,
)
from llm_in_practise_tpu.quant.int8 import Int8Tensor
from llm_in_practise_tpu.quant.nf4 import NF4Tensor
from llm_in_practise_tpu.utils.tree import flatten_with_paths


def test_mixed_preset_split():
    assert mixed_serve_fmt("block_0/mlp/gate/kernel") == "int8"
    assert mixed_serve_fmt("block_0/attn/q_proj/kernel") == "nf4"
    assert mixed_serve_fmt("blocks/block/mlp/down/kernel") == "int8"


def test_quantize_base_lowmem_mixed_leaf_types():
    from llm_in_practise_tpu.models.qwen3 import Qwen3, qwen3_config

    cfg = qwen3_config(vocab_size=128)
    params = Qwen3(cfg).init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"]
    q = quantize_base_lowmem(params, min_size=1, fmt="mixed")
    leaves = flatten_with_paths(q, is_leaf=_is_quant)
    kinds = {p: type(v) for p, v in leaves.items() if _is_quant(v)}
    assert kinds, "nothing quantized"
    for p, k in kinds.items():
        if "/mlp/" in p:
            assert k is Int8Tensor, p
        else:
            assert k is NF4Tensor, p
    # attention kernels really were quantized (not silently skipped)
    assert any("/attn/" in p for p in kinds)


def test_callable_fmt():
    """fmt may be any path->format callable (probe tooling uses this to
    try alternative splits without new presets)."""
    from llm_in_practise_tpu.models.qwen3 import Qwen3, qwen3_config

    cfg = qwen3_config(vocab_size=128)
    params = Qwen3(cfg).init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"]
    q = quantize_base_lowmem(
        params, min_size=1,
        fmt=lambda p: "int8" if p.endswith("o_proj/kernel") else "nf4")
    leaves = flatten_with_paths(q, is_leaf=_is_quant)
    for p, v in leaves.items():
        if not _is_quant(v):
            continue
        want = Int8Tensor if p.endswith("o_proj/kernel") else NF4Tensor
        assert type(v) is want, p


def test_mixed_tree_serves_greedy_close_to_bf16():
    """A mixed tree runs through the fused serving interceptor (per-leaf
    dispatch: Int8 -> XLA dequant matmul, NF4 -> kernel path) and greedy
    decode matches the unquantized model on a short horizon."""
    from llm_in_practise_tpu.models.qwen3 import Qwen3, qwen3_config
    from llm_in_practise_tpu.serve.engine import (
        InferenceEngine, SamplingParams,
    )
    from llm_in_practise_tpu.serve.quantized import QuantizedModel

    cfg = qwen3_config(vocab_size=128, compute_dtype="float32")
    params = Qwen3(cfg).init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"]
    qtree = quantize_base_lowmem(params, min_size=1, fmt="mixed",
                                 cast_rest_above=None)

    def run(p, model):
        eng = InferenceEngine(
            QuantizedModel(model, compute_dtype=jnp.float32,
                           use_kernels=False)
            if p is qtree else model,
            p, max_slots=2, cache_len=64, cache_dtype=jnp.float32)
        return eng.generate(list(range(1, 9)),
                            SamplingParams(greedy=True, max_tokens=8))

    ref = run(params, Qwen3(cfg))
    got = run(qtree, Qwen3(cfg))
    # 8-bit MLP + 4-bit attention at tiny init scale: trajectories may
    # drift after a few tokens; require agreement on the first 4
    assert got[:4] == ref[:4]


def test_mixed_stacked_scan_matches_unrolled():
    """Mixed quantization commutes with the scan layout: the
    quantize-then-stack tree decodes (``infer/generate.py``) the
    unrolled tree's greedy tokens exactly."""
    from llm_in_practise_tpu.infer.generate import generate
    from llm_in_practise_tpu.models.qwen3 import (
        Qwen3, qwen3_config, stack_layer_params,
    )
    from llm_in_practise_tpu.serve.quantized import QuantizedModel

    cfg = qwen3_config(vocab_size=128, compute_dtype="float32")
    params = Qwen3(cfg).init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"]
    qu = quantize_base_lowmem(params, min_size=1, fmt="mixed",
                              cast_rest_above=None)
    qs = stack_layer_params(qu, cfg.n_layer)

    def run(model, p):
        out = generate(
            QuantizedModel(model, compute_dtype=jnp.float32,
                           use_kernels=False),
            p, jnp.asarray([list(range(1, 9))], jnp.int32),
            max_new_tokens=8, greedy=True, cache_len=64,
            cache_dtype=jnp.float32)
        return np.asarray(out).tolist()

    a = run(Qwen3(cfg), qu)
    b = run(Qwen3(cfg.replace(scan_layers=True)), qs)
    assert a == b and len(a[0]) == 16
