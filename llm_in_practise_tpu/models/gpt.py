"""Decoder-only GPT family: one module covering MiniGPT and GPTLike.

Capability parity (behavior, not code) with the reference's from-scratch GPTs:

- MiniGPT v2 — post-LN encoder blocks, learned position-embedding parameter,
  N(0, 0.02) init, final LN + head (reference ``llm-demo/minigpt2/model.py:40-74``).
- GPTLike (learned PE) — pre-LN blocks, learned ``nn.Embedding`` positions,
  weight tying (reference ``GPTLike_wikitext2_learned_pe.py:118-205``).
- GPTLike (fixed PE) — sinusoidal position table registered as a constant
  (reference ``GPTLike_wikitext2_fixed_pe.py:178-230``).

The variants are expressed as :class:`GPTConfig` presets, not separate model
code; factories below give each reference model its named constructor.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from llm_in_practise_tpu.models import layers
from llm_in_practise_tpu.ops.rope import sinusoidal_embeddings


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int
    seq_len: int = 256
    n_layer: int = 4
    n_head: int = 4
    embed_dim: int = 128
    mlp_ratio: float = 4.0
    dropout: float = 0.1
    pos_embedding: str = "learned"  # "learned" | "sinusoidal" | "rope"
    norm_first: bool = True
    tie_weights: bool = False
    activation: str = "gelu"
    rope_theta: float = 10000.0
    attn_impl: str = "auto"
    compute_dtype: str = "float32"
    remat: bool = False  # gradient checkpointing: recompute blocks in bwd

    def replace(self, **kw) -> "GPTConfig":
        return dataclasses.replace(self, **kw)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "GPTConfig":
        valid = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in valid})


class GPT(nn.Module):
    """Decoder-only LM. ``__call__(idx) -> logits`` (+ updated KV cache)."""

    config: GPTConfig

    @nn.compact
    def __call__(
        self,
        idx: jax.Array,
        *,
        deterministic: bool = True,
        cache: list[layers.Cache] | None = None,
        positions: jax.Array | None = None,
        return_hidden: bool = False,
        # ``idx`` IS final-norm hidden states: apply the output head
        # alone (the other half of ``return_hidden``; see models/qwen3.py)
        head_only: bool = False,
    ):
        cfg = self.config
        compute_dtype = jnp.dtype(cfg.compute_dtype)

        embed = nn.Embed(
            cfg.vocab_size, cfg.embed_dim,
            embedding_init=layers.dense_init, name="tok_embed",
        )

        def head(x):
            if cfg.tie_weights:
                return embed.attend(x)
            return nn.Dense(
                cfg.vocab_size, kernel_init=layers.dense_init, name="lm_head"
            )(x)

        if head_only:
            return head(idx)
        b, l = idx.shape
        x = embed(idx)

        if positions is None:
            start = cache[0]["index"] if cache is not None else 0
            positions = layers.cache_positions(start, b, l)
        if cfg.pos_embedding == "learned":
            pos_table = self.param(
                "pos_embed", layers.dense_init, (cfg.seq_len, cfg.embed_dim)
            )
            x = x + pos_table[positions]
        elif cfg.pos_embedding == "sinusoidal":
            x = x + sinusoidal_embeddings(cfg.seq_len, cfg.embed_dim)[positions]
        # "rope" applies inside attention.

        x = nn.Dropout(cfg.dropout)(x, deterministic=deterministic)
        x = x.astype(compute_dtype)

        new_cache = [] if cache is not None else None
        block_pos = positions if cfg.pos_embedding == "rope" else None
        for i in range(cfg.n_layer):
            layer_cache = cache[i] if cache is not None else None
            block = layers.TransformerBlock(
                cfg.embed_dim, cfg.n_head, cfg.mlp_ratio, cfg.dropout,
                norm_first=cfg.norm_first, activation=cfg.activation,
                use_rope=cfg.pos_embedding == "rope",
                rope_theta=cfg.rope_theta, max_seq_len=cfg.seq_len,
                attn_impl=cfg.attn_impl, dtype=compute_dtype,
                name=f"block_{i}",
            )
            if cfg.remat and cache is None:
                # gradient checkpointing (reference
                # gradient_checkpointing_enable parity)
                x = layers.remat_apply(
                    block, x, deterministic=deterministic,
                    cache=None, positions=block_pos)
            else:
                x, layer_cache = block(
                    x, deterministic=deterministic, cache=layer_cache,
                    positions=block_pos)
            if new_cache is not None:
                new_cache.append(layer_cache)

        x = nn.LayerNorm(name="ln_f")(x.astype(jnp.float32))
        if return_hidden:
            # trunk output for downstream heads (classification fine-tunes —
            # the HF_Basics sequence-classification demos); the LM head's
            # params are simply never created in this configuration
            return (x, new_cache) if cache is not None else x
        logits = head(x)
        if cache is not None:
            return logits, new_cache
        return logits

    def init_cache(self, batch: int, max_len: int | None = None, dtype=jnp.bfloat16):
        cfg = self.config
        return layers.init_cache(
            batch, max_len or cfg.seq_len, cfg.n_head,
            cfg.embed_dim // cfg.n_head, cfg.n_layer, dtype,
        )


# --- Named presets mirroring the reference's model zoo -----------------------

def minigpt_config(vocab_size: int, **overrides) -> GPTConfig:
    """MiniGPT v2 preset (reference ``minigpt2/model.py:5-14`` Config)."""
    base = dict(
        seq_len=256, n_layer=4, n_head=4, embed_dim=128, dropout=0.1,
        pos_embedding="learned", norm_first=False, tie_weights=False,
    )
    base.update(overrides)
    return GPTConfig(vocab_size=vocab_size, **base)


def minigpt_v1_config(vocab_size: int, **overrides) -> GPTConfig:
    """MiniGPT v1 preset: char-level toy, seq 16, d_model 64
    (reference ``llm-demo/minigpt/model.py:5-31``)."""
    base = dict(
        seq_len=16, n_layer=2, n_head=2, embed_dim=64, dropout=0.1,
        pos_embedding="learned", norm_first=False,
    )
    base.update(overrides)
    return GPTConfig(vocab_size=vocab_size, **base)


def gptlike_config(vocab_size: int, pos_embedding: str = "learned", **overrides) -> GPTConfig:
    """GPTLike preset (reference ``GPTLike_wikitext2_learned_pe.py`` defaults:
    6 layers, 8 heads, d_model 512, block 256, pre-LN, weight tying)."""
    base = dict(
        seq_len=256, n_layer=6, n_head=8, embed_dim=512, dropout=0.1,
        pos_embedding=pos_embedding, norm_first=True, tie_weights=True,
    )
    base.update(overrides)
    return GPTConfig(vocab_size=vocab_size, **base)
