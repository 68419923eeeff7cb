"""DeepSeekLike: RoPE + MLA (low-rank KV) + sparse MoE, TPU-first.

Capability parity with the reference's flagship from-scratch models
(``LLM_Distributed_Trainning/PyTorch/transformer_basics/``):

- ``DeepSeekLike_wikitext2.py:122-294`` — RoPE, MLA, dense MoE with per-k
  one-hot masks, shared experts, softmax-renormalized top-k gates.
- ``DeepSeekLike_spare_MoE_wikitext2.py:131-333`` — cos/sin RoPE, MLA with
  per-head latent compression, **sparse dispatch** via data-dependent
  ``index_select`` / ``index_add_`` gather/scatter.

The TPU redesign keeps the math and changes the mechanics:

- **MLA** is a shared (not per-head) low-rank factorization: ``kv_down``
  projects to a ``kv_rank`` latent, ``k_up``/``v_up`` decompress to heads;
  queries go through ``q_down``/``q_up``. The decode cache stores the
  *latent* — ``kv_rank`` floats/token instead of ``2·n_head·head_dim`` —
  which is the actual point of MLA; decompression is a batched matmul that
  rides the MXU.
- **MoE routing is static-shape**: the reference's ``index_add_`` scatter has
  data-dependent sizes and cannot jit. Here tokens are dispatched into a
  fixed ``(n_experts, capacity)`` buffer with first-choice priority via
  cumsum positions and one-hot einsums — the standard XLA MoE formulation.
  Dropped tokens (over capacity) fall through to the shared experts /
  residual path. Gates are softmax-over-top-k renormalized, and the
  switch-style load-balance aux loss plus router z-loss are sown into the
  ``losses`` collection.
- Stacked expert weights live at ``experts/fc_in|fc_out`` so the sharding
  rule table partitions them over the ``expert`` mesh axis (expert
  parallelism — described-but-absent in the reference,
  ``DeepSpeed/README.md:17-18``).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from llm_in_practise_tpu.models import layers
from llm_in_practise_tpu.ops import rope as rope_ops
from llm_in_practise_tpu.ops.attention import dot_product_attention

Cache = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class DeepSeekConfig:
    vocab_size: int
    seq_len: int = 256
    n_layer: int = 4
    n_head: int = 8
    embed_dim: int = 256
    # MLA ranks (reference uses latent = head_dim // 4 per head;
    # here a shared latent across heads, same compression ratio by default).
    q_rank: int | None = None      # None → embed_dim // 2
    kv_rank: int | None = None     # None → embed_dim // 4
    # MoE
    n_experts: int = 8
    n_shared_experts: int = 1
    top_k: int = 2
    capacity_factor: float = 1.25
    expert_hidden: int | None = None  # None → embed_dim * mlp_ratio / top_k
    first_dense_layers: int = 1       # leading dense-MLP blocks (DeepSeek style)
    aux_loss_coef: float = 0.01
    z_loss_coef: float = 0.001
    mlp_ratio: float = 4.0
    dropout: float = 0.0
    rope_theta: float = 10000.0
    activation: str = "gelu"
    attn_impl: str = "auto"
    compute_dtype: str = "float32"
    remat: bool = False  # gradient checkpointing: recompute blocks in bwd
    cache_mode: str = "latent"  # "latent" (MLA cache) | "full" (k/v cache)

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.n_head

    @property
    def q_rank_(self) -> int:
        return self.q_rank or self.embed_dim // 2

    @property
    def kv_rank_(self) -> int:
        return self.kv_rank or self.embed_dim // 4

    @property
    def expert_hidden_(self) -> int:
        if self.expert_hidden:
            return self.expert_hidden
        return max(8, int(self.embed_dim * self.mlp_ratio) // max(1, self.top_k))

    def replace(self, **kw) -> "DeepSeekConfig":
        return dataclasses.replace(self, **kw)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "DeepSeekConfig":
        valid = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in valid})


class MLA(nn.Module):
    """Multi-head Latent Attention: shared low-rank Q and KV factorizations.

    Parity: reference ``CausalMLA`` (``DeepSeekLike_spare_MoE_wikitext2.py:
    180-233``) compresses Q/K/V per head to ``head_dim//4`` and decompresses
    before RoPE + standard causal attention. Same compress→decompress→RoPE
    data flow here, with the latent shared across heads so the decode cache
    shrinks from ``2·H·hd`` to ``kv_rank`` per token.
    """

    config: DeepSeekConfig

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        *,
        deterministic: bool = True,
        cache: Cache | None = None,
        positions: jax.Array | None = None,
    ) -> tuple[jax.Array, Cache | None]:
        cfg = self.config
        b, l, _ = x.shape
        h, hd = cfg.n_head, cfg.head_dim
        dense = lambda feat, name: nn.Dense(
            feat, kernel_init=layers.dense_init, use_bias=False, name=name
        )

        # Low-rank query: D -> q_rank -> H*hd
        q_latent = dense(cfg.q_rank_, "q_down")(x)
        q = dense(h * hd, "q_up")(q_latent).reshape(b, l, h, hd)
        # Shared low-rank KV latent: D -> kv_rank
        kv_latent = dense(cfg.kv_rank_, "kv_down")(x)

        if positions is None:
            start = cache["index"] if cache is not None else 0
            positions = layers.cache_positions(start, b, l)

        k_up = dense(h * hd, "k_up")
        v_up = dense(h * hd, "v_up")
        # RoPE tables must cover the cache length, which may exceed seq_len
        # (init_cache(max_len=...)); otherwise position gathers past the table
        # would clamp silently and corrupt phases.
        table_len = cfg.seq_len
        if cache is not None:
            table_len = max(
                table_len,
                (cache["kv"] if "kv" in cache else cache["k"]).shape[1],
            )
        cos, sin = rope_ops.precompute_cos_sin(hd, table_len, cfg.rope_theta)

        q = rope_ops.apply_rotary_emb(q, cos, sin, positions=positions)

        q_offset = None
        if cache is None:
            k = k_up(kv_latent).reshape(b, l, h, hd)
            v = v_up(kv_latent).reshape(b, l, h, hd)
            k = rope_ops.apply_rotary_emb(k, cos, sin, positions=positions)
        elif cfg.cache_mode == "latent":
            # Cache the compressed latent; decompress the whole valid prefix
            # each step (batched matmul — MXU work, not HBM). RoPE phases are
            # reconstructed from absolute positions.
            lat_cache = layers.cache_update(
                cache["kv"], kv_latent, cache["index"]
            )
            q_offset = cache["index"]
            cache = {"kv": lat_cache, "index": cache["index"] + l}
            max_len = lat_cache.shape[1]
            lat = lat_cache.astype(x.dtype)
            k = k_up(lat).reshape(b, max_len, h, hd)
            v = v_up(lat).reshape(b, max_len, h, hd)
            all_pos = jnp.broadcast_to(jnp.arange(max_len)[None, :], (b, max_len))
            k = rope_ops.apply_rotary_emb(k, cos, sin, positions=all_pos)
        else:  # "full": decompressed k/v cache (standard layout)
            k = k_up(kv_latent).reshape(b, l, h, hd)
            v = v_up(kv_latent).reshape(b, l, h, hd)
            k = rope_ops.apply_rotary_emb(k, cos, sin, positions=positions)
            q_offset = cache["index"]
            k_cache = layers.cache_update(cache["k"], k, cache["index"])
            v_cache = layers.cache_update(cache["v"], v, cache["index"])
            cache = {"k": k_cache, "v": v_cache, "index": cache["index"] + l}
            k, v = k_cache.astype(q.dtype), v_cache.astype(q.dtype)

        dropout_rng = None
        if not deterministic and cfg.dropout > 0.0:
            dropout_rng = self.make_rng("dropout")
        out = dot_product_attention(
            q, k, v,
            causal=True,
            q_offset=q_offset,
            dropout_rate=0.0 if deterministic else cfg.dropout,
            dropout_rng=dropout_rng,
            impl=cfg.attn_impl,
        )
        out = out.reshape(b, l, h * hd)
        out = dense(cfg.embed_dim, "out_proj")(out)
        out = nn.Dropout(cfg.dropout)(out, deterministic=deterministic)
        return out, cache


class _StackedKernel(nn.Module):
    """A (n_experts, d_in, d_out) weight named ``<name>/kernel`` so the
    sharding rule table can target ``experts/fc_in/kernel`` etc."""

    shape: tuple[int, ...]

    @nn.compact
    def __call__(self) -> jax.Array:
        return self.param("kernel", layers.dense_init, self.shape)


class StackedExperts(nn.Module):
    """All expert MLPs as stacked tensors, applied with einsum over the
    (expert, capacity, dim) dispatch buffer."""

    n_experts: int
    d_model: int
    d_hidden: int
    activation: str = "gelu"

    @nn.compact
    def __call__(self, expert_inputs: jax.Array) -> jax.Array:
        # expert_inputs: (E, C, D)
        w_in = _StackedKernel((self.n_experts, self.d_model, self.d_hidden), name="fc_in")()
        w_out = _StackedKernel((self.n_experts, self.d_hidden, self.d_model), name="fc_out")()
        h = jnp.einsum("ecd,edh->ech", expert_inputs, w_in.astype(expert_inputs.dtype))
        h = layers._activation(self.activation)(h)
        return jnp.einsum("ech,ehd->ecd", h, w_out.astype(h.dtype))


class MoEFeedForward(nn.Module):
    """Top-k routed experts + always-on shared experts, static shapes.

    Parity: reference ``MoEFeedForward``
    (``DeepSeekLike_spare_MoE_wikitext2.py:253-333``) — top-k softmax gates
    renormalized over the selected experts, shared experts added
    unconditionally. The scatter/gather dispatch becomes one-hot einsums with
    a fixed per-expert capacity.
    """

    config: DeepSeekConfig

    @nn.compact
    def __call__(self, x: jax.Array, *, deterministic: bool = True) -> jax.Array:
        cfg = self.config
        b, l, d = x.shape
        n_tok = b * l
        e, k = cfg.n_experts, cfg.top_k
        tokens = x.reshape(n_tok, d)

        router_logits = nn.Dense(
            e, use_bias=False, kernel_init=layers.dense_init, name="router"
        )(tokens.astype(jnp.float32))
        probs = jax.nn.softmax(router_logits, axis=-1)                  # (N, E)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)                 # (N, k)
        gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

        # Aux losses (sown; no-ops unless the "losses" collection is mutable).
        # Switch-style load balance: E * Σ_e fraction_e * mean_prob_e.
        sel_onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)   # (N, k, E)
        fraction = sel_onehot.sum(1).mean(0)                            # (E,)
        balance = e * jnp.sum(fraction * probs.mean(0)) * k
        z_loss = jnp.mean(jax.nn.logsumexp(router_logits, axis=-1) ** 2)
        self.sow("losses", "moe_aux",
                 cfg.aux_loss_coef * balance + cfg.z_loss_coef * z_loss)

        experts = StackedExperts(
            e, d, cfg.expert_hidden_, cfg.activation, name="experts"
        )
        if deterministic:
            # Drop-free dense routing for eval/decode: every expert runs over
            # all tokens and the (N, E) gate matrix combines. O(N·E) memory —
            # no capacity buffer — and exact (nothing dropped), so cached
            # decode reproduces the full forward regardless of batch shape.
            gates_dense = (sel_onehot * gate_vals[..., None]).sum(1)    # (N, E)
            expert_inputs = jnp.broadcast_to(tokens[None], (e, n_tok, d))
            expert_out = experts(expert_inputs)                         # (E, N, D)
            routed = jnp.einsum(
                "ne,end->nd", gates_dense.astype(x.dtype), expert_out
            )
        else:
            # Training: capacity-based dispatch with first-choice priority —
            # flatten (k, N) slot-major so every token's 1st choice outranks
            # all 2nd choices; overflow tokens are dropped (gate mass lost),
            # the standard static-shape TPU MoE trade. Dispatch/combine are
            # scatter/gather on (expert, slot) coordinates: each (kN,)
            # choice owns a unique capacity slot, so no (N, k, E, C)
            # one-hot tensor is ever materialized (that buffer dominated
            # both HBM and time at real batch sizes).
            capacity = max(1, int(cfg.capacity_factor * n_tok * k / e))
            flat = sel_onehot.transpose(1, 0, 2).reshape(k * n_tok, e)  # (kN, E)
            # rank of each choice within its expert, priority-ordered
            slot_f = (jnp.cumsum(flat, axis=0) * flat).sum(-1) - 1.0    # (kN,)
            keep = (slot_f >= 0) & (slot_f < capacity)                  # (kN,)
            slot = jnp.where(keep, slot_f, 0).astype(jnp.int32)
            eid = expert_idx.transpose(1, 0).reshape(-1)                # (kN,)
            tok_idx = jnp.tile(jnp.arange(n_tok), k)                    # (kN,)
            contrib = tokens[tok_idx] * keep[:, None].astype(x.dtype)
            # every kept (eid, slot) pair is unique → add == set
            expert_inputs = jnp.zeros((e, capacity, d), x.dtype).at[
                eid, slot].add(contrib)
            expert_out = experts(expert_inputs)                         # (E, C, D)
            gathered = expert_out[eid, slot]                            # (kN, D)
            w = (gate_vals.transpose(1, 0).reshape(-1)
                 * keep.astype(jnp.float32))                            # (kN,)
            routed = (gathered.reshape(k, n_tok, d)
                      * w.reshape(k, n_tok, 1).astype(x.dtype)).sum(0)

        out = routed.reshape(b, l, d)
        for i in range(cfg.n_shared_experts):
            out = out + layers.MLP(
                d, cfg.expert_hidden_, cfg.dropout, cfg.activation,
                name=f"shared_expert_{i}",
            )(x, deterministic=deterministic)
        return nn.Dropout(cfg.dropout)(out, deterministic=deterministic)


class DeepSeekBlock(nn.Module):
    config: DeepSeekConfig
    use_moe: bool = True

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        *,
        deterministic: bool = True,
        cache: Cache | None = None,
        positions: jax.Array | None = None,
    ) -> tuple[jax.Array, Cache | None]:
        cfg = self.config
        a, cache = MLA(cfg, name="attn")(
            nn.LayerNorm(name="ln1")(x),
            deterministic=deterministic, cache=cache, positions=positions,
        )
        x = x + a
        h = nn.LayerNorm(name="ln2")(x)
        if self.use_moe:
            x = x + MoEFeedForward(cfg, name="moe")(h, deterministic=deterministic)
        else:
            x = x + layers.MLP(
                cfg.embed_dim, int(cfg.embed_dim * cfg.mlp_ratio),
                cfg.dropout, cfg.activation, name="mlp",
            )(h, deterministic=deterministic)
        return x, cache


class DeepSeekLike(nn.Module):
    """Decoder-only MLA+MoE LM (reference ``DeepSeekLike:354``)."""

    config: DeepSeekConfig

    @nn.compact
    def __call__(
        self,
        idx: jax.Array,
        *,
        deterministic: bool = True,
        cache: list[Cache] | None = None,
        positions: jax.Array | None = None,
        # the forward in two halves (see models/qwen3.py): final-norm
        # hidden states out, or ``idx`` IS such states and the output
        # head alone runs
        return_hidden: bool = False,
        head_only: bool = False,
    ):
        cfg = self.config
        compute_dtype = jnp.dtype(cfg.compute_dtype)

        def head(x):
            return nn.Dense(
                cfg.vocab_size, use_bias=False,
                kernel_init=layers.dense_init, name="lm_head",
            )(x)

        if head_only:
            return head(idx)
        x = nn.Embed(
            cfg.vocab_size, cfg.embed_dim,
            embedding_init=layers.dense_init, name="tok_embed",
        )(idx)
        x = nn.Dropout(cfg.dropout)(x, deterministic=deterministic)
        x = x.astype(compute_dtype)

        new_cache = [] if cache is not None else None
        for i in range(cfg.n_layer):
            layer_cache = cache[i] if cache is not None else None
            block = DeepSeekBlock(
                cfg, use_moe=i >= cfg.first_dense_layers, name=f"block_{i}"
            )
            if cfg.remat and cache is None:
                # gradient checkpointing; the sown MoE aux losses thread
                # through the lifted remat unchanged (tested)
                x = layers.remat_apply(
                    block, x, deterministic=deterministic,
                    cache=None, positions=positions)
            else:
                x, layer_cache = block(
                    x, deterministic=deterministic, cache=layer_cache,
                    positions=positions)
            if new_cache is not None:
                new_cache.append(layer_cache)

        x = nn.LayerNorm(name="ln_f")(x.astype(jnp.float32))
        if return_hidden:
            return (x, new_cache) if cache is not None else x
        logits = head(x)
        if cache is not None:
            return logits, new_cache
        return logits

    def init_cache(self, batch: int, max_len: int | None = None, dtype=jnp.bfloat16):
        cfg = self.config
        max_len = max_len or cfg.seq_len
        if cfg.cache_mode == "latent":
            return [
                {
                    "kv": jnp.zeros((batch, max_len, cfg.kv_rank_), dtype),
                    "index": jnp.zeros((), jnp.int32),
                }
                for _ in range(cfg.n_layer)
            ]
        return layers.init_cache(
            batch, max_len, cfg.n_head, cfg.head_dim, cfg.n_layer, dtype
        )


def moe_loss_fn(params, apply_fn, batch, rng):
    """Train-step loss fn adding the sown MoE aux losses to cross-entropy.

    Use as ``make_train_step(loss_fn=moe_loss_fn)`` — parity with the
    reference's single CE objective plus the load-balance term sparse MoE
    needs (absent in the reference, which load-balances implicitly via its
    softmax gates; required here by capacity routing).
    """
    from llm_in_practise_tpu.train.losses import cross_entropy

    x, y = batch
    logits, mut = apply_fn(
        {"params": params}, x,
        deterministic=False, rngs={"dropout": rng}, mutable=["losses"],
    )
    loss, n_valid = cross_entropy(logits, y)
    aux = sum(
        jnp.sum(jnp.asarray(v).astype(jnp.float32))
        for v in jax.tree_util.tree_leaves(mut.get("losses", {}))
    )
    return loss + aux, {"n_valid": n_valid, "moe_aux": aux, "ce_loss": loss}


def deepseeklike_config(vocab_size: int, **overrides) -> DeepSeekConfig:
    """Preset mirroring reference ``DeepSeekLike_spare_MoE_wikitext2.py``
    defaults (d_model 256, 4 layers, 8 heads, block 256, 8 experts top-2,
    1 shared)."""
    base = dict(
        seq_len=256, n_layer=4, n_head=8, embed_dim=256,
        n_experts=8, top_k=2, n_shared_experts=1, dropout=0.1,
    )
    base.update(overrides)
    return DeepSeekConfig(vocab_size=vocab_size, **base)
