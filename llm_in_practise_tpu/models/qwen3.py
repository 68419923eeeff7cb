"""Qwen3 architecture in flax — the HF-interop model family.

Capability parity with the reference's fine-tuning targets (Qwen3-8B/14B and
DeepSeek-R1-0528-Qwen3-8B, loaded via ``AutoModelForCausalLM`` in
``Fine-Tuning/qwen3-8b-lora.py:114-120`` and
``Fine-Tuning/qwen3-14b-qlora-dist-deepspeed.py:95-107``), built TPU-first:

- GQA attention with per-head **QK-RMSNorm** (the Qwen3 signature), RoPE with
  theta 1e6, SwiGLU MLP, RMSNorm everywhere, no biases.
- KV cache stores only ``n_kv_head`` heads; the group-broadcast to ``n_head``
  happens inside the jitted step where XLA fuses it into the attention einsum.
- Everything static-shape; the same module serves training (no cache) and
  KV-cached decode.

Weights come from HF safetensors checkpoints via
:mod:`llm_in_practise_tpu.models.hf_loader`.
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
class Qwen3Config:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    n_layer: int
    n_head: int
    n_kv_head: int
    head_dim: int
    rope_theta: float = 1_000_000.0
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 4096
    tie_word_embeddings: bool = False
    attn_impl: str = "auto"
    compute_dtype: str = "bfloat16"
    remat: bool = False  # gradient checkpointing: recompute blocks in bwd
    # Compile one block and lax.scan it over the depth axis: XLA program
    # size (and compile time) becomes O(1) in n_layer instead of O(n) —
    # at 28+ layers the unrolled HLO takes tens of minutes to compile.
    # Params are stored STACKED (leading n_layer axis, under "blocks");
    # use stack_layer_params / unstack_layer_params to convert to/from
    # the unrolled per-block layout (HF interop). Cached decode works in
    # BOTH layouts: under scan the KV cache is stacked too (leading
    # n_layer axis, slot axis 1 — see ``init_cache``) and each scan step
    # carries its layer's KV slice as a scanned input/output.
    scan_layers: bool = False
    # lax.scan unroll factor for the scan-layers paths: >1 puts N block
    # copies in the loop body (program size O(unroll), iterations
    # n_layer/unroll) — amortizes per-iteration loop mechanics at a
    # bounded compile-time cost. n_layer must be divisible by it.
    scan_unroll: int = 1
    # Block length of the attention mask: 1 is causal; block-diffusion
    # models (models/sdar_moe.py) attend bidirectionally inside blocks
    # of this many absolute positions (ops/attention.py::causal_mask).
    attn_block: int = 1

    def replace(self, **kw) -> "Qwen3Config":
        return dataclasses.replace(self, **kw)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "Qwen3Config":
        return cls(**d)

    @classmethod
    def from_hf_config(cls, hf: dict, **overrides) -> "Qwen3Config":
        """Build from a HF ``config.json`` dict (transformers Qwen3Config)."""
        cfg = cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            n_layer=hf["num_hidden_layers"],
            n_head=hf["num_attention_heads"],
            n_kv_head=hf.get("num_key_value_heads", hf["num_attention_heads"]),
            head_dim=hf.get(
                "head_dim", hf["hidden_size"] // hf["num_attention_heads"]
            ),
            rope_theta=float(hf.get("rope_theta", 1_000_000.0)),
            rms_norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
            max_seq_len=int(hf.get("max_position_embeddings", 4096)),
            tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
        )
        return cfg.replace(**overrides)


def qwen3_config(vocab_size: int = 1024, **kw) -> Qwen3Config:
    """Tiny-default constructor for tests and examples."""
    defaults = dict(
        vocab_size=vocab_size, hidden_size=128, intermediate_size=256,
        n_layer=2, n_head=4, n_kv_head=2, head_dim=32, max_seq_len=256,
    )
    defaults.update(kw)
    return Qwen3Config(**defaults)


class RMSNorm(nn.Module):
    """RMSNorm with f32 accumulation (HF Qwen3RMSNorm semantics)."""

    eps: float = 1e-6

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        dtype = x.dtype
        x = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        x = x * jax.lax.rsqrt(var + self.eps)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return (x * scale).astype(dtype)


def init_cache(
    cfg: Qwen3Config, batch: int, max_len: int, dtype=jnp.bfloat16
) -> list[Cache]:
    """Static-shape per-layer KV cache holding only the KV-head groups.

    Unrolled layout: one ``{k, v, index}`` dict per layer, slot (batch)
    axis 0. Scan layout (``cfg.scan_layers``): ONE dict whose k/v carry a
    leading ``n_layer`` axis (slot axis 1) and a single shared ``index``
    — every layer advances in lockstep, so per-layer indices are
    redundant. It is wrapped in a one-element list so engine code that
    iterates per-layer dicts traverses both layouts identically."""
    if cfg.scan_layers:
        return [
            {
                "k": jnp.zeros((cfg.n_layer, batch, max_len,
                                cfg.n_kv_head, cfg.head_dim), dtype),
                "v": jnp.zeros((cfg.n_layer, batch, max_len,
                                cfg.n_kv_head, cfg.head_dim), dtype),
                "index": jnp.zeros((), jnp.int32),
            }
        ]
    return [
        {
            "k": jnp.zeros((batch, max_len, cfg.n_kv_head, cfg.head_dim), dtype),
            "v": jnp.zeros((batch, max_len, cfg.n_kv_head, cfg.head_dim), dtype),
            "index": jnp.zeros((), jnp.int32),
        }
        for _ in range(cfg.n_layer)
    ]


class Qwen3Attention(nn.Module):
    """GQA + QK-RMSNorm + RoPE causal attention."""

    cfg: Qwen3Config

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        rope_tables: tuple[jax.Array, jax.Array],
        *,
        cache: Cache | None = None,
        positions: jax.Array | None = None,
    ) -> tuple[jax.Array, Cache | None]:
        cfg = self.cfg
        b, l, _ = x.shape
        # dtype pins the compute path: flax Dense with dtype=None promotes
        # bf16 activations against f32 params and the layer silently runs
        # f32 (params stay f32 masters either way)
        compute = jnp.dtype(cfg.compute_dtype)
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=compute, name=name)
        q = dense(cfg.n_head * cfg.head_dim, "q_proj")(x)
        k = dense(cfg.n_kv_head * cfg.head_dim, "k_proj")(x)
        v = dense(cfg.n_kv_head * cfg.head_dim, "v_proj")(x)
        q = q.reshape(b, l, cfg.n_head, cfg.head_dim)
        k = k.reshape(b, l, cfg.n_kv_head, cfg.head_dim)
        v = v.reshape(b, l, cfg.n_kv_head, cfg.head_dim)

        # Qwen3 signature: per-head RMSNorm on q and k before RoPE.
        q = RMSNorm(cfg.rms_norm_eps, name="q_norm")(q)
        k = RMSNorm(cfg.rms_norm_eps, name="k_norm")(k)

        cos, sin = rope_tables
        if positions is None and cache is not None:
            positions = layers.cache_positions(cache["index"], b, l)
        # HF rotate_half lane layout — required for checkpoint fidelity.
        # Rotation math rides the f32 tables; result returns to the
        # compute dtype so attention keeps its bf16 MXU path.
        q = rope_ops.apply_rotary_emb(
            q, cos, sin, positions=positions, interleaved=False
        ).astype(compute)
        k = rope_ops.apply_rotary_emb(
            k, cos, sin, positions=positions, interleaved=False
        ).astype(compute)

        q_offset = None
        if cache is not None:
            q_offset = cache["index"]
            k_cache = layers.cache_update(cache["k"], k, cache["index"])
            v_cache = layers.cache_update(cache["v"], v, cache["index"])
            cache = {"k": k_cache, "v": v_cache, "index": cache["index"] + l}
            k, v = k_cache.astype(q.dtype), v_cache.astype(q.dtype)

        # GQA: k/v go in with their n_kv_head heads — the dense path
        # contracts against them grouped (no broadcast ever exists in
        # HBM; a materialized jnp.repeat here measured ~256 MB/layer/step
        # at 8B decode, docs/perf.md Finding 14), and the flash kernel
        # finds a query head's K/V head in its index maps.
        out = dot_product_attention(
            q, k, v,
            causal=True, q_offset=q_offset,
            impl=cfg.attn_impl, block=cfg.attn_block,
        )
        out = out.reshape(b, l, cfg.n_head * cfg.head_dim)
        return dense(cfg.hidden_size, "out_proj")(out), cache


class Qwen3MLP(nn.Module):
    """SwiGLU: down(silu(gate(x)) * up(x))."""

    cfg: Qwen3Config

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        compute = jnp.dtype(cfg.compute_dtype)  # see Qwen3Attention
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=compute, name=name)
        gate = dense(cfg.intermediate_size, "gate_proj")(x)
        up = dense(cfg.intermediate_size, "up_proj")(x)
        return dense(cfg.hidden_size, "down_proj")(nn.silu(gate) * up)


class Qwen3Block(nn.Module):
    cfg: Qwen3Config

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        rope_tables: tuple[jax.Array, jax.Array],
        *,
        cache: Cache | None = None,
        positions: jax.Array | None = None,
    ) -> tuple[jax.Array, Cache | None]:
        cfg = self.cfg
        a, cache = Qwen3Attention(cfg, name="attn")(
            RMSNorm(cfg.rms_norm_eps, name="ln1")(x), rope_tables,
            cache=cache, positions=positions,
        )
        x = x + a
        x = x + Qwen3MLP(cfg, name="mlp")(RMSNorm(cfg.rms_norm_eps, name="ln2")(x))
        return x, cache


class _ScanBody(nn.Module):
    """One scan step: positional-only signature for ``nn.scan`` (carry = the
    hidden stream; rope tables and positions ride as broadcast inputs).
    ``sideband`` (scanned, may be None) is this layer's slice of
    caller-provided side inputs — stacked packed quantized weights and/or
    stacked LoRA factors — published via :func:`..layers.scan_sideband`
    so method interceptors (peft/fused.py) can serve the current layer's
    tensors; gradients flow through it (it is ordinary scanned ``xs``),
    which is what makes full-depth QLoRA training under scan work."""

    cfg: Qwen3Config

    @nn.compact
    def __call__(self, x, sideband, rope_tables, positions):
        block_cls = (
            nn.remat(Qwen3Block, prevent_cse=False)
            if self.cfg.remat else Qwen3Block
        )
        with layers.scan_sideband(sideband):
            x, _ = block_cls(self.cfg, name="block")(
                x, rope_tables, cache=None, positions=positions)
        return x, None


class _ScanDecodeBody(nn.Module):
    """One cached-decode scan step: the layer's KV slice rides as a
    scanned input and the refreshed slice as the scanned output, so the
    decode program compiles ONE block regardless of depth (the serving
    analog of the training-path ``_ScanBody``). The write ``index`` is
    shared by every layer (lockstep) and is broadcast, not scanned; the
    per-layer index the block returns is dropped — the caller advances
    the shared one once. ``sideband`` (scanned, may be empty) is this
    layer's slice of caller-provided side inputs — e.g. packed quantized
    weights — published via :func:`..layers.scan_sideband` for method
    interceptors (peft/fused.py) during the body's trace."""

    cfg: Qwen3Config

    @nn.compact
    def __call__(self, x, kv, index, sideband, rope_tables, positions):
        layer_cache = {"k": kv["k"], "v": kv["v"], "index": index}
        with layers.scan_sideband(sideband):
            x, new = Qwen3Block(self.cfg, name="block")(
                x, rope_tables, cache=layer_cache, positions=positions)
        return x, {"k": new["k"], "v": new["v"]}


def stack_layer_params(params: dict, n_layer: int) -> dict:
    """Unrolled ``block_i`` subtrees -> the scan layout (stacked leaves
    with a leading ``n_layer`` axis under ``blocks/block``)."""
    rest = {k: v for k, v in params.items()
            if not k.startswith("block_")}
    blocks = [params[f"block_{i}"] for i in range(n_layer)]
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls, axis=0), *blocks)
    return {**rest, "blocks": {"block": stacked}}


def stack_layer_params_jitted(params: dict, n_layer: int,
                              out_shardings=None) -> dict:
    """:func:`stack_layer_params` as one jitted call with the input
    DONATED — peak memory is the unrolled tree plus one stacked leaf,
    not two full trees. ``out_shardings`` (a pytree of shardings
    matching the STACKED layout) pins the result's placement — without
    it the compiler chooses, typically replicating. The shared
    conversion used by the bench, the serve example, and the HF
    loader."""
    kw = {} if out_shardings is None else {"out_shardings": out_shardings}
    return jax.jit(
        lambda t: stack_layer_params(t, n_layer), donate_argnums=0, **kw
    )(params)


def stack_layer_params_lowmem(params: dict, n_layer: int) -> dict:
    """:func:`stack_layer_params` leaf-group by leaf-group: one jitted
    donated stack per component, so peak memory is the unrolled tree
    plus ONE stacked leaf — not tree + stacked tree, which is what the
    whole-tree jit (:func:`stack_layer_params_jitted`) holds at its
    peak and what OOMs when the packed tree alone is half of HBM (an
    int8 8B is 6.9 GiB, a 14B NF4 base 7.4 GiB: 2x either + KV cache
    exceeds a 16 GiB chip)."""
    rest = {k: v for k, v in params.items()
            if not k.startswith("block_")}
    blocks = [params[f"block_{i}"] for i in range(n_layer)]
    stack1 = jax.jit(lambda *ls: jnp.stack(ls, axis=0),
                     donate_argnums=tuple(range(n_layer)))
    stacked = jax.tree.map(lambda *ls: stack1(*ls), *blocks)
    return {**rest, "blocks": {"block": stacked}}


def unstack_layer_params(params: dict, n_layer: int) -> dict:
    """Scan layout -> unrolled ``block_i`` subtrees (serving / HF export)."""
    rest = {k: v for k, v in params.items() if k != "blocks"}
    stacked = params["blocks"]["block"]
    for i in range(n_layer):
        rest[f"block_{i}"] = jax.tree.map(lambda x: x[i], stacked)
    return rest


class Qwen3(nn.Module):
    """Qwen3 causal LM. ``model(idx) -> logits``; optional KV cache pytree."""

    cfg: Qwen3Config

    @nn.compact
    def __call__(
        self,
        idx: jax.Array,
        *,
        deterministic: bool = True,  # accepted for train-step compatibility
        cache: list[Cache] | None = None,
        positions: jax.Array | None = None,
        return_hidden: bool = False,  # final-norm hidden states (embedder use)
        # ``idx`` IS final-norm hidden states (..., hidden): apply the
        # output head alone. With ``return_hidden`` it splits the forward
        # in two, so a caller can pick positions before the head
        # (``layers.last_position_logits``: a prefill's one row of logits)
        head_only: bool = False,
        # Per-layer side inputs for the scan paths (leading n_layer axis;
        # e.g. stacked packed quantized weights, stacked LoRA factors) —
        # scanned alongside each layer's slice and published to
        # interceptors via the layers.scan_sideband channel. Training
        # scan and cached-decode scan both thread it; requires
        # scan_layers=True.
        scan_sideband: Any = None,
    ):
        cfg = self.cfg
        compute_dtype = jnp.dtype(cfg.compute_dtype)
        if scan_sideband is not None and not cfg.scan_layers:
            raise ValueError(
                "scan_sideband is only consumed by the scan-layers paths "
                "(set scan_layers=True)")
        embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size,
            embedding_init=nn.initializers.normal(0.02), name="tok_embed",
        )

        def head(x):
            if cfg.tie_word_embeddings:
                return embed.attend(x.astype(jnp.float32))
            return nn.Dense(
                cfg.vocab_size, use_bias=False, name="lm_head"
            )(x.astype(jnp.float32))

        if head_only:
            return head(idx)
        x = embed(idx).astype(compute_dtype)
        # One table pair per forward; constant-folded under jit.
        rope_tables = rope_ops.precompute_cos_sin(
            cfg.head_dim, cfg.max_seq_len, cfg.rope_theta
        )
        new_caches: list[Cache] | None = [] if cache is not None else None
        if cfg.scan_layers:
            if cache is not None:
                stacked = cache[0]
                if positions is None:
                    positions = layers.cache_positions(
                        stacked["index"], idx.shape[0], idx.shape[1])
                scan = nn.scan(
                    _ScanDecodeBody,
                    variable_axes={"params": 0},
                    split_rngs={"params": True, "dropout": True},
                    in_axes=(0, nn.broadcast, 0, nn.broadcast,
                             nn.broadcast),
                    out_axes=0,
                    length=cfg.n_layer,
                    unroll=cfg.scan_unroll,
                )
                x, kv = scan(cfg, name="blocks")(
                    x, {"k": stacked["k"], "v": stacked["v"]},
                    stacked["index"], scan_sideband, rope_tables,
                    positions)
                new_caches = [{"k": kv["k"], "v": kv["v"],
                               "index": stacked["index"] + idx.shape[1]}]
            else:
                scan = nn.scan(
                    _ScanBody,
                    variable_axes={"params": 0},
                    split_rngs={"params": True, "dropout": True},
                    in_axes=(0, nn.broadcast, nn.broadcast),
                    length=cfg.n_layer,
                    unroll=cfg.scan_unroll,
                )
                x, _ = scan(cfg, name="blocks")(
                    x, scan_sideband, rope_tables, positions)
        else:
            for i in range(cfg.n_layer):
                layer_cache = cache[i] if cache is not None else None
                block = Qwen3Block(cfg, name=f"block_{i}")
                if cfg.remat and cache is None:
                    # gradient checkpointing (the reference fine-tunes all
                    # call gradient_checkpointing_enable —
                    # qwen3-8b-lora.py:128-144)
                    x = layers.remat_apply(
                        block, x, rope_tables, cache=None,
                        positions=positions)
                else:
                    x, layer_cache = block(
                        x, rope_tables, cache=layer_cache,
                        positions=positions
                    )
                if new_caches is not None:
                    new_caches.append(layer_cache)
        x = RMSNorm(cfg.rms_norm_eps, name="ln_f")(x)
        if return_hidden:
            # with a cache the refreshed cache must come back too, or the
            # caller's KV writes are dead code and get eliminated
            return (x, new_caches) if cache is not None else x
        logits = head(x)
        if cache is not None:
            return logits, new_caches
        return logits

    # -- convenience API shared by every in-tree model family -----------------
    @property
    def config(self) -> Qwen3Config:
        return self.cfg

    def init_params(self, rng, example_len: int = 8):
        return self.init(rng, jnp.ones((1, example_len), jnp.int32))["params"]

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        return init_cache(self.cfg, batch, max_len, dtype)

    @property
    def cache_slot_axis(self) -> int:
        """Which axis of the KV buffers indexes the slot (batch): 0 in
        the unrolled layout, 1 under the stacked scan layout (axis 0 is
        the layer). Serving code reads this to stay layout-agnostic."""
        return 1 if self.cfg.scan_layers else 0
