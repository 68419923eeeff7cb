"""SDAR-MoE (``model_type`` ``sdar_moe``): a Qwen3-MoE backbone decoded by
block diffusion.

The block is dense Qwen3's (models/qwen3.py: GQA, per-head QK-RMSNorm,
rotate-half RoPE, RMSNorm, no biases — ``Qwen3Attention`` and ``RMSNorm``
are reused by import) with the MLP of EVERY layer replaced by routed
experts: softmax over all experts in float32, top-k, renormalised over
the k (``norm_topk_prob``), no shared expert, through the dropless
grouped layer of ``ops/grouped_experts.py``. The output head is untied.

What makes it a block-diffusion model, and what the serving engine reads
off the model (``serve/block_step.py``), never off a flag:

- ``block_length`` B: attention is block-causal (query ``i`` sees key
  ``j`` iff ``j // B <= i // B``) and the logits at position ``i``
  predict token ``i`` ITSELF (no shift);
- ``mask_token_id``: the input id of a position not yet revealed;
- the reveal schedule's defaults (``denoising_steps``, ``remasking``,
  ``confidence_threshold``).

Weights are whatever dtype the params tree holds (bf16 on the serving
path: :func:`random_params`); activations are ``compute_dtype``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from llm_in_practise_tpu.models.qwen3 import (
    Qwen3Attention,
    Qwen3Config,
    RMSNorm,
    init_cache as _qwen3_init_cache,
)
from llm_in_practise_tpu.ops import rope as rope_ops
from llm_in_practise_tpu.ops.grouped_experts import grouped_expert_ffn, route

Cache = dict[str, Any]
REMASKING = ("low_confidence_static", "low_confidence_dynamic")


@dataclasses.dataclass(frozen=True)
class SDARMoEConfig:
    vocab_size: int
    hidden_size: int
    moe_intermediate_size: int
    n_layer: int
    n_head: int
    n_kv_head: int
    head_dim: int
    n_experts: int
    n_experts_per_tok: int
    norm_topk_prob: bool = True
    rope_theta: float = 1_000_000.0
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 4096
    attn_impl: str = "auto"
    compute_dtype: str = "bfloat16"
    # block diffusion (the published config.json gives none of these:
    # the family's released inference defaults)
    block_length: int = 4
    mask_token_id: int = 151669
    denoising_steps: int = 4
    remasking: str = "low_confidence_static"
    confidence_threshold: float = 0.9

    def __post_init__(self):
        if self.block_length < 1:
            raise ValueError(f"block_length must be >= 1, got "
                             f"{self.block_length}")
        if not 1 <= self.denoising_steps <= self.block_length:
            raise ValueError(
                f"denoising_steps must be in [1, block_length="
                f"{self.block_length}], got {self.denoising_steps}")
        if self.remasking not in REMASKING:
            raise ValueError(f"remasking must be one of {REMASKING}, got "
                             f"{self.remasking!r}")

    def replace(self, **kw) -> "SDARMoEConfig":
        return dataclasses.replace(self, **kw)

    @classmethod
    def from_hf_config(cls, hf: dict, **overrides) -> "SDARMoEConfig":
        """Build from the model's ``config.json`` keys. Layer patterns
        this file does not implement are refused, not ignored."""
        if int(hf.get("decoder_sparse_step", 1)) != 1 or hf.get(
                "mlp_only_layers"):
            raise ValueError(
                "sdar_moe: only decoder_sparse_step=1 with no "
                "mlp_only_layers (every layer sparse) is implemented")
        if hf.get("use_sliding_window") or hf.get("rope_scaling"):
            raise ValueError("sdar_moe: sliding windows and RoPE scaling "
                             "are not implemented")
        # the block-diffusion keys are not in the published config.json;
        # a configuration file that states them overrides the defaults
        extra = {k: hf[k] for k in ("block_length", "mask_token_id",
                                    "denoising_steps", "remasking",
                                    "confidence_threshold") if k in hf}
        cfg = cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            n_layer=hf["num_hidden_layers"],
            n_head=hf["num_attention_heads"],
            n_kv_head=hf.get("num_key_value_heads",
                             hf["num_attention_heads"]),
            head_dim=hf.get("head_dim",
                            hf["hidden_size"] // hf["num_attention_heads"]),
            n_experts=hf["num_experts"],
            n_experts_per_tok=hf["num_experts_per_tok"],
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
            rope_theta=float(hf.get("rope_theta", 1_000_000.0)),
            rms_norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
            max_seq_len=int(hf.get("max_position_embeddings", 4096)),
            **extra,
        )
        return cfg.replace(**overrides)

    @property
    def attn_cfg(self) -> Qwen3Config:
        """What ``Qwen3Attention`` reads, with the block-causal mask."""
        return Qwen3Config(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            intermediate_size=self.moe_intermediate_size,
            n_layer=self.n_layer, n_head=self.n_head,
            n_kv_head=self.n_kv_head, head_dim=self.head_dim,
            rope_theta=self.rope_theta, rms_norm_eps=self.rms_norm_eps,
            max_seq_len=self.max_seq_len, attn_impl=self.attn_impl,
            compute_dtype=self.compute_dtype,
            attn_block=self.block_length)


def sdar_moe_config(vocab_size: int = 512, **kw) -> SDARMoEConfig:
    """Tiny-default constructor for tests."""
    defaults = dict(
        vocab_size=vocab_size, hidden_size=64, moe_intermediate_size=32,
        n_layer=2, n_head=4, n_kv_head=2, head_dim=16, n_experts=8,
        n_experts_per_tok=2, max_seq_len=256, mask_token_id=vocab_size - 1)
    defaults.update(kw)
    return SDARMoEConfig(**defaults)


class SDARMoELayer(nn.Module):
    """The routed expert layer of one block. Sows the chosen expert ids
    (``routing`` collection, (tokens, k) per call) for the engine's load
    counters; a caller that does not make the collection mutable pays
    nothing."""

    cfg: SDARMoEConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        init = nn.initializers.normal(0.02)
        e, h, w = cfg.n_experts, cfg.hidden_size, cfg.moe_intermediate_size
        router = self.param("router", init, (h, e))
        w_gate = self.param("w_gate", init, (e, h, w))
        w_up = self.param("w_up", init, (e, h, w))
        w_down = self.param("w_down", init, (e, w, h))
        compute = jnp.dtype(cfg.compute_dtype)
        flat = x.reshape(-1, h)
        ids, weights = route(flat, router, cfg.n_experts_per_tok,
                             norm_topk=cfg.norm_topk_prob)
        self.sow("routing", "experts", ids)
        y = grouped_expert_ffn(flat.astype(compute), ids, weights,
                               w_gate.astype(compute), w_up.astype(compute),
                               w_down.astype(compute))
        return y.reshape(x.shape).astype(x.dtype)


class SDARMoEBlock(nn.Module):
    cfg: SDARMoEConfig

    @nn.compact
    def __call__(self, x, rope_tables, *, cache=None, positions=None):
        cfg = self.cfg
        a, cache = Qwen3Attention(cfg.attn_cfg, name="attn")(
            RMSNorm(cfg.rms_norm_eps, name="ln1")(x), rope_tables,
            cache=cache, positions=positions)
        x = x + a
        x = x + SDARMoELayer(cfg, name="moe")(
            RMSNorm(cfg.rms_norm_eps, name="ln2")(x))
        return x, cache


class SDARMoE(nn.Module):
    """``model(idx) -> logits`` (position ``i`` predicts token ``i``);
    with ``cache`` (the engines' per-layer ``{k, v, index}`` list) returns
    ``(logits, cache)``."""

    cfg: SDARMoEConfig

    @nn.compact
    def __call__(self, idx: jax.Array, *, deterministic: bool = True,
                 cache: list[Cache] | None = None,
                 positions: jax.Array | None = None,
                 return_hidden: bool = False, head_only: bool = False):
        # ``return_hidden`` / ``head_only``: the forward in two halves
        # (see models/qwen3.py)
        cfg = self.cfg
        compute = jnp.dtype(cfg.compute_dtype)

        def head(x):
            w = self.param("lm_head", nn.initializers.normal(0.02),
                           (cfg.hidden_size, cfg.vocab_size))
            return jnp.dot(x.astype(compute), w.astype(compute),
                           preferred_element_type=jnp.float32)

        if head_only:
            return head(idx)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         embedding_init=nn.initializers.normal(0.02),
                         name="tok_embed")
        x = embed(idx).astype(compute)
        rope_tables = rope_ops.precompute_cos_sin(
            cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
        new_caches = [] if cache is not None else None
        for i in range(cfg.n_layer):
            x, layer_cache = SDARMoEBlock(cfg, name=f"block_{i}")(
                x, rope_tables,
                cache=cache[i] if cache is not None else None,
                positions=positions)
            if new_caches is not None:
                new_caches.append(layer_cache)
        x = RMSNorm(cfg.rms_norm_eps, name="ln_f")(x)
        if return_hidden:
            return (x, new_caches) if cache is not None else x
        logits = head(x)
        if cache is not None:
            return logits, new_caches
        return logits

    # -- convenience API shared by every in-tree model family -----------------
    @property
    def config(self) -> SDARMoEConfig:
        return self.cfg

    @property
    def block_length(self) -> int:
        return self.cfg.block_length

    @property
    def mask_token_id(self) -> int:
        return self.cfg.mask_token_id

    def init_params(self, rng, example_len: int = 8):
        return self.init(rng, jnp.ones((1, example_len), jnp.int32))["params"]

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        return _qwen3_init_cache(self.cfg.attn_cfg, batch, max_len, dtype)

    @property
    def cache_slot_axis(self) -> int:
        return 0


def random_params(cfg: SDARMoEConfig, seed: int, dtype=jnp.bfloat16,
                  std: float = 0.02) -> dict:
    """Seeded N(0, ``std``) weights made ON THE DEVICE in ``dtype``, one
    leaf at a time (a float32 tree of the 6-layer serving cut would not
    fit beside its bf16 copy); norm scales are ones. Every layer and
    every expert is a distinct draw."""
    shapes = jax.eval_shape(
        lambda: SDARMoE(cfg).init(jax.random.PRNGKey(0),
                                  jnp.ones((1, 8), jnp.int32))["params"])
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    root = jax.random.PRNGKey(int(seed) % (2 ** 31))
    draw = jax.jit(
        lambda key, shape: (std * jax.random.normal(key, shape,
                                                    jnp.float32)).astype(dtype),
        static_argnums=1)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        if jax.tree_util.keystr(path).endswith("['scale']"):
            out.append(jnp.ones(leaf.shape, dtype))
        else:
            out.append(draw(jax.random.fold_in(root, i), tuple(leaf.shape)))
    return jax.tree_util.tree_unflatten(treedef, out)
