"""TP-sharded serving of packed quantized trees (NF4 / Int4 / AWQ).

The reference serves its GPTQ/AWQ exports under vLLM tensor parallelism
(``Fine-Tuning/README.md:345-349``, TP=2). Here the packed component
arrays carry NamedShardings derived from the dense rule table
(quant/sharding.py) and the XLA dequant path partitions under the mesh —
these tests assert (a) the intended placements and (b) output equality
with the single-device forward.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from llm_in_practise_tpu.core import mesh as mesh_lib
from tests import envcaps
from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
from llm_in_practise_tpu.peft.fused import fused_quant_apply
from llm_in_practise_tpu.peft.qlora import quantize_base
from llm_in_practise_tpu.quant.int4 import rtn_quantize
from llm_in_practise_tpu.quant.nf4 import NF4Tensor
from llm_in_practise_tpu.quant.sharding import (
    quant_tree_shardings,
    shard_quant_tree,
)
from llm_in_practise_tpu.utils.tree import flatten_with_paths


def _model_and_params():
    cfg = GPTConfig(vocab_size=256, seq_len=32, n_layer=2, n_head=4,
                    embed_dim=128, dropout=0.0, tie_weights=True,
                    norm_first=True)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    return model, params


def _tp_mesh(devices):
    return mesh_lib.build_mesh(
        mesh_lib.MeshSpec(data=4, model=2), devices=devices)


def _x():
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.integers(0, 256, (2, 32)), jnp.int32)


def test_nf4_component_shardings_follow_rule_table(devices):
    _, params = _model_and_params()
    qtree = quantize_base(params, min_size=4096)
    mesh = _tp_mesh(devices)
    sh = quant_tree_shardings(qtree, mesh)
    flat = flatten_with_paths(
        sh, is_leaf=lambda v: isinstance(v, NF4Tensor))
    # column-parallel in-projection: N-sharded packed, replicated absmax
    q_proj = flat["block_0/attn/q_proj/kernel"]
    assert q_proj.packed.spec == P(None, "model")
    assert q_proj.absmax_q.spec == P()
    # row-parallel out-projection: K-sharded packed AND absmax sidecars
    fc_out = flat["block_0/mlp/fc_out/kernel"]
    assert fc_out.packed.spec == P("model", None)
    assert fc_out.absmax_q.spec == P("model")
    assert fc_out.absmax_scale.spec == P("model")


@pytest.mark.skipif(not envcaps.shard_map_has_check_vma(),
                    reason=envcaps.SHARD_MAP_CHECK_VMA_REASON)
def test_nf4_tp_serving_matches_single_device(devices):
    model, params = _model_and_params()
    qtree = quantize_base(params, min_size=4096)
    x = _x()

    def fwd(q, x):
        return fused_quant_apply(model, q, x, use_kernels=False,
                                 compute_dtype=jnp.float32)

    ref = jax.jit(fwd)(qtree, x)

    mesh = _tp_mesh(devices)
    with mesh:
        q_sharded = shard_quant_tree(qtree, mesh)
        out = jax.jit(fwd)(q_sharded, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_int4_tp_serving_matches_single_device(devices):
    model, params = _model_and_params()

    def maybe_q(path, leaf):
        ps = "/".join(str(getattr(k, "key", k)) for k in path)
        if (getattr(leaf, "ndim", 0) == 2 and leaf.size >= 4096
                and "embed" not in ps):
            return rtn_quantize(leaf, group_size=64)
        return leaf

    qtree = jax.tree_util.tree_map_with_path(maybe_q, params)
    x = _x()

    def fwd(q, x):
        return fused_quant_apply(model, q, x, use_kernels=False,
                                 compute_dtype=jnp.float32)

    ref = jax.jit(fwd)(qtree, x)
    mesh = _tp_mesh(devices)
    with mesh:
        out = jax.jit(fwd)(shard_quant_tree(qtree, mesh), x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_quantized_model_auto_disables_kernels_on_tp_mesh(devices):
    from llm_in_practise_tpu.serve.quantized import QuantizedModel

    model, _ = _model_and_params()
    assert QuantizedModel(model).use_kernels
    assert not QuantizedModel(model, mesh=_tp_mesh(devices)).use_kernels
    data_only = mesh_lib.build_mesh(
        mesh_lib.MeshSpec(data=8), devices=devices)
    assert QuantizedModel(model, mesh=data_only).use_kernels
