"""DeepSeek-V3 (``model_type`` ``deepseek_v3``,
huggingface.co/deepseek-ai/DeepSeek-V3): multi-head latent attention (MLA)
over a latent cache, and sigmoid, group-limited routed experts beside a
shared expert. ``models/deepseek.py`` is the teaching model of the
reference course and is NOT this architecture (RoPE on the decompressed
key, no decoupled rope key, capacity-dropping softmax experts).

Per layer, ``u = RMSNorm(x)``, ``h = x + Attn(u)``, ``y = h +
FFN(RMSNorm(h))``.

**Attn.** ``c_q = RMSNorm(W_qa u)``; ``q = W_qb c_q`` gives each head
``[q_nope | q_rope]``; ``[c_kv | k_rope] = W_kva u``, ``c_kv`` RMS-normed,
``k_rope`` ONE rope key a token shared by all heads; RoPE on ``q_rope``
and ``k_rope``. The cache row of a token in a layer is ``[c_kv | k_rope]``
(``kv_lora_rank + qk_rope_head_dim`` = 576 values) and nothing else:
``init_cache`` returns per layer ``{"ckv": (B, L, 576), "index"}``.
``W_kvb`` decompresses a latent to each head's ``[k_nope | v]``;
``score = (q_nope . k_nope + q_rope . k_rope) * s``, ``s = 192^-1/2 m^2``,
``m = 0.1 mscale_all_dim ln(factor) + 1`` (YaRN, ``ops/rope.py``). The
phase is the STATIC query length the program is traced with: a query
length of 1 against a cache runs the absorbed form on the latent rows
(``ops/mla_attention.py::decode_attention``), anything longer the naive
form over key blocks (``prefill_attention``).

**The cache contract.** A layer's ``cache`` is ``{"ckv", "index"}``. As
the engines' contiguous cache and as a paged program's gathered view,
``ckv`` is ``(B, W, 576)`` rows from position 0: the layer writes its new
rows at ``index`` (``layers.cache_update``) and the program scatters them
back to the pool. The serving engine's DECODE programs (one token a row)
give a model that declares ``reads_pages`` no view: ``ckv`` is then the
pool as ``serve/paged_kv.py`` stores it by pages, ``(pages, page rows, 576
up to whole lanes)``, beside each row's block table under
``layers.PAGES_KEY`` and ``layers.VALID_KEY`` (1: the row decodes; 0: idle
or mid-prefill). THE LAYER writes the row into its page
(``layers.page_row_write``; a row that is not valid writes into the trash
page) and attends the pages where they lie, to each row's true length
(``mla_attention.paged_decode_attention``); the pool comes back under
``ckv`` and the engine writes nothing after it. The model's ``__call__``
makes the rows' work list once (``swa_attention.paged_rows``) for all its
layers.

**RoPE lanes: interleaved.** Rope dimension pair ``(2i, 2i+1)`` rotates
by frequency ``f_i``. The published modeling code de-interleaves ``q_pe``
/ ``k_pe`` (``view(.., d/2, 2).transpose``) and then rotates halves,
which rotates exactly these pairs and leaves the result in another lane
order; ``q . k`` does not see a lane order that both share. So a
checkpoint's ``q_b_proj`` / ``kv_a_proj_with_mqa`` rope columns load AS
THEY ARE (the identity is the permutation a loader applies; a half-split
implementation would have to de-interleave them), and only the cache
row's last 64 lanes differ in order from the published cache. The
reference (``benchmark/reference/deepseek_v3.py``) takes them
interleaved too.

**FFN.** Layers below ``first_k_dense_replace``: SwiGLU of
``intermediate_size``. The others: ``ops/grouped_experts.py::route`` with
``scoring="sigmoid"`` (selection bias ``e_score_correction_bias``, groups,
``routed_scaling_factor``), the routed experts HELD HERE through
``grouped_expert_ffn(held=(expert_offset, experts_held))``, plus the
shared expert. With ``experts_held`` < ``n_routed_experts`` this is one
chip's share of an expert-parallel layer: the router scores every expert,
the assignments to absent experts are dropped, and what those experts
would add is left out (no code stands in for their chips or the
exchange).

**Left out: multi-token prediction.** ``num_nextn_predict_layers`` is
read and no module is built for it: the published model serves without
its MTP layer unless it drafts for itself, and the engine's speculative
round takes an independent draft model only. A checkpoint's layer 61 has
nowhere to go yet.

**Step statistics.** A routed layer given a cache dict that holds
``LOAD_KEY`` / ``ROUTE_KEY`` (the serving engine's paged programs add
them to the transient view; they are no part of the cache) adds this
pass's ``[1, held assignments, held experts touched, busiest held
expert's load]`` to the first and writes the experts each row's LAST
position chose to the second.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from llm_in_practise_tpu.models import layers
from llm_in_practise_tpu.models.qwen3 import RMSNorm
from llm_in_practise_tpu.ops import mla_attention
from llm_in_practise_tpu.ops import rope as rope_ops
from llm_in_practise_tpu.ops import swa_attention as swa
from llm_in_practise_tpu.ops.grouped_experts import (
    grouped_expert_ffn,
    held_counts,
    route,
)

Cache = dict[str, Any]
LOAD_KEY, ROUTE_KEY = layers.LOAD_KEY, layers.ROUTE_KEY
VALID_KEY, PAGES_KEY = layers.VALID_KEY, layers.PAGES_KEY


@dataclasses.dataclass(frozen=True)
class DeepSeekV3Config:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    n_layer: int
    n_head: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts: int
    n_experts_per_tok: int
    n_shared_experts: int = 1
    first_k_dense_replace: int = 3
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    # the experts this chip holds: ids expert_offset .. + experts_held - 1
    # (None: all of them)
    experts_held: int | None = None
    expert_offset: int = 0
    rope_theta: float = 10_000.0
    # YaRN (None: plain RoPE): factor, original_max_position_embeddings,
    # beta_fast, beta_slow, mscale, mscale_all_dim
    yarn: tuple[float, int, float, float, float, float] | None = None
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 4096
    compute_dtype: str = "bfloat16"
    # read from the config and NOT built (module docstring): a
    # checkpoint's multi-token-prediction layers have nowhere to go yet
    n_nextn_predict_layers: int = 0

    def __post_init__(self):
        held = self.held
        if not (0 <= held[0] and held[0] + held[1] <= self.n_routed_experts
                and held[1] >= 1):
            raise ValueError(f"held experts {held} outside the router's "
                             f"{self.n_routed_experts}")
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_routed_experts must divide into n_group")
        if not 1 <= self.topk_group <= self.n_group:
            raise ValueError("topk_group must be in [1, n_group]")

    def replace(self, **kw) -> "DeepSeekV3Config":
        return dataclasses.replace(self, **kw)

    @property
    def held(self) -> tuple[int, int]:
        """(first held expert id, how many)."""
        return (self.expert_offset, self.n_routed_experts
                if self.experts_held is None else self.experts_held)

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def attention_scale(self) -> float:
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        if self.yarn is None:
            return qk ** -0.5
        return rope_ops.yarn_attention_scale(qk, self.yarn[0], self.yarn[5])

    def is_routed(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace

    @classmethod
    def from_hf_config(cls, hf: dict, **overrides) -> "DeepSeekV3Config":
        """Build from the model's ``config.json`` keys, plus
        ``experts_held`` / ``expert_offset`` where given. What this file
        does not implement is refused, not ignored."""
        def refuse(key, got, want):
            raise ValueError(f"deepseek_v3: {key}={got!r} is not "
                             f"implemented (only {want})")

        if hf.get("scoring_func", "sigmoid") != "sigmoid":
            refuse("scoring_func", hf["scoring_func"], "'sigmoid'")
        if hf.get("topk_method", "noaux_tc") != "noaux_tc":
            refuse("topk_method", hf["topk_method"], "'noaux_tc'")
        if int(hf.get("moe_layer_freq", 1)) != 1:
            refuse("moe_layer_freq", hf["moe_layer_freq"], "1")
        if hf.get("hidden_act", "silu") != "silu":
            refuse("hidden_act", hf["hidden_act"], "'silu'")
        if hf.get("attention_bias") or hf.get("tie_word_embeddings"):
            refuse("attention_bias / tie_word_embeddings", True, "false")
        if hf.get("num_key_value_heads",
                  hf["num_attention_heads"]) != hf["num_attention_heads"]:
            refuse("num_key_value_heads", hf["num_key_value_heads"],
                   "num_attention_heads: MLA has no grouped heads")
        if hf.get("q_lora_rank") is None:
            refuse("q_lora_rank", None, "a low-rank query projection")
        scaling, yarn = hf.get("rope_scaling"), None
        if scaling is not None:
            kind = scaling.get("type", scaling.get("rope_type"))
            if kind != "yarn":
                refuse("rope_scaling.type", kind, "'yarn' or none")
            yarn = (float(scaling["factor"]),
                    int(scaling["original_max_position_embeddings"]),
                    float(scaling.get("beta_fast", 32)),
                    float(scaling.get("beta_slow", 1)),
                    float(scaling.get("mscale", 1)),
                    float(scaling.get("mscale_all_dim", 0)))
        cfg = cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            n_layer=hf["num_hidden_layers"],
            n_head=hf["num_attention_heads"],
            q_lora_rank=hf["q_lora_rank"],
            kv_lora_rank=hf["kv_lora_rank"],
            qk_nope_head_dim=hf["qk_nope_head_dim"],
            qk_rope_head_dim=hf["qk_rope_head_dim"],
            v_head_dim=hf["v_head_dim"],
            n_routed_experts=hf["n_routed_experts"],
            n_experts_per_tok=hf["num_experts_per_tok"],
            n_shared_experts=int(hf.get("n_shared_experts", 1)),
            first_k_dense_replace=int(hf.get("first_k_dense_replace", 0)),
            n_group=int(hf.get("n_group", 1)),
            topk_group=int(hf.get("topk_group", 1)),
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
            experts_held=hf.get("experts_held"),
            expert_offset=int(hf.get("expert_offset", 0)),
            rope_theta=float(hf.get("rope_theta", 10_000.0)),
            yarn=yarn,
            rms_norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
            max_seq_len=int(hf.get("max_position_embeddings", 4096)),
            n_nextn_predict_layers=int(
                hf.get("num_nextn_predict_layers", 0)),
        )
        return cfg.replace(**overrides)


def deepseek_v3_config(vocab_size: int = 512, **kw) -> DeepSeekV3Config:
    """Tiny-default constructor for tests: 1 dense + 2 routed layers, 32
    routed experts in 4 groups, YaRN over 32 original positions."""
    defaults = dict(
        vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, n_layer=3, n_head=4, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=32, n_experts_per_tok=4,
        first_k_dense_replace=1, n_group=4, topk_group=2,
        yarn=(4.0, 32, 32.0, 1.0, 1.0, 1.0), max_seq_len=256)
    defaults.update(kw)
    return DeepSeekV3Config(**defaults)


def rope_tables(cfg: DeepSeekV3Config):
    if cfg.yarn is None:
        return rope_ops.precompute_cos_sin(
            cfg.qk_rope_head_dim, cfg.max_seq_len, cfg.rope_theta)
    factor, original, fast, slow, mscale, all_dim = cfg.yarn
    return rope_ops.precompute_yarn_cos_sin(
        cfg.qk_rope_head_dim, cfg.max_seq_len, cfg.rope_theta,
        factor=factor, original_max_len=original, beta_fast=fast,
        beta_slow=slow, mscale=mscale, mscale_all_dim=all_dim)


def _dense(cfg, feats, name):
    return nn.Dense(feats, use_bias=False, dtype=jnp.dtype(cfg.compute_dtype),
                    kernel_init=nn.initializers.normal(0.02), name=name)


class MLAttention(nn.Module):
    cfg: DeepSeekV3Config

    @nn.compact
    def __call__(self, x, tables, *, cache=None, positions=None,
                 pages=None):
        cfg = self.cfg
        b, l, _ = x.shape
        h, dn, dr = cfg.n_head, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        rank, dv = cfg.kv_lora_rank, cfg.v_head_dim
        compute = jnp.dtype(cfg.compute_dtype)
        c_q = RMSNorm(cfg.rms_norm_eps, name="q_a_norm")(
            _dense(cfg, cfg.q_lora_rank, "q_a_proj")(x))
        q = _dense(cfg, h * (dn + dr), "q_b_proj")(c_q).reshape(
            b, l, h, dn + dr)
        kv = _dense(cfg, rank + dr, "kv_a_proj")(x)
        c_kv = RMSNorm(cfg.rms_norm_eps, name="kv_a_norm")(kv[..., :rank])
        w_kvb = self.param("kv_b_proj", nn.initializers.normal(0.02),
                           (rank, h, dn + dv)).astype(compute)
        cos, sin = tables
        if positions is None and cache is not None:
            positions = layers.cache_positions(cache["index"], b, l)
        rot = lambda t: rope_ops.apply_rotary_emb(  # noqa: E731
            t, cos, sin, positions=positions, interleaved=True
        ).astype(compute)
        q_nope, q_rope = q[..., :dn], rot(q[..., dn:])
        k_rope = rot(kv[..., None, rank:])[:, :, 0]
        row = jnp.concatenate([c_kv.astype(compute), k_rope], axis=-1)
        if cache is not None and PAGES_KEY in cache:
            # the pool's PAGES (a decode program, l == 1): the new row
            # goes into its page, and the query walks the row's pages to
            # its true length (``pages``: the model's one
            # ``swa.paged_rows`` a program); a row that is not live writes
            # into the trash page and reads nothing
            start = cache["index"]
            pool = layers.page_row_write(
                cache["ckv"], pages["table"], start, cache[VALID_KEY],
                row[:, 0])
            out = mla_attention.paged_decode_attention(
                q_nope, q_rope, pool, w_kvb, rank=rank,
                scale=cfg.attention_scale, **pages)
            cache = dict(cache, ckv=pool, index=start + l)
        else:
            start = 0
            latent = row
            if cache is not None:
                start = cache["index"]
                stored = layers.cache_update(cache["ckv"], row, start)
                cache = dict(cache, ckv=stored, index=start + l)
                latent = stored.astype(compute)
            attend = (mla_attention.decode_attention
                      if l == 1 and cache is not None
                      else mla_attention.prefill_attention)
            out = attend(q_nope, q_rope, latent, start, w_kvb, rank=rank,
                         scale=cfg.attention_scale)
        return _dense(cfg, cfg.hidden_size, "o_proj")(
            out.reshape(b, l, h * dv)), cache


class SwiGLU(nn.Module):
    cfg: DeepSeekV3Config
    width: int

    @nn.compact
    def __call__(self, x):
        gate = _dense(self.cfg, self.width, "gate_proj")(x)
        up = _dense(self.cfg, self.width, "up_proj")(x)
        return _dense(self.cfg, self.cfg.hidden_size, "down_proj")(
            nn.silu(gate) * up)


class RoutedExperts(nn.Module):
    """The routed experts held here plus the shared expert. Returns
    ``(y, ids (N, k), held counts (held,))``."""

    cfg: DeepSeekV3Config

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        init = nn.initializers.normal(0.02)
        hid, wid = cfg.hidden_size, cfg.moe_intermediate_size
        first, held = cfg.held
        router = self.param("router", init, (hid, cfg.n_routed_experts))
        bias = self.param("e_score_correction_bias",
                          nn.initializers.normal(0.01),
                          (cfg.n_routed_experts,))
        w_gate = self.param("w_gate", init, (held, hid, wid))
        w_up = self.param("w_up", init, (held, hid, wid))
        w_down = self.param("w_down", init, (held, wid, hid))
        compute = jnp.dtype(cfg.compute_dtype)
        flat = x.reshape(-1, hid)
        ids, weights = route(
            flat, router, cfg.n_experts_per_tok,
            norm_topk=cfg.norm_topk_prob, scoring="sigmoid", bias=bias,
            n_group=cfg.n_group, topk_group=cfg.topk_group,
            scale=cfg.routed_scaling_factor)
        self.sow("routing", "experts", ids)
        y = grouped_expert_ffn(
            flat.astype(compute), ids, weights, w_gate.astype(compute),
            w_up.astype(compute), w_down.astype(compute),
            held=(first, held), n_experts=cfg.n_routed_experts)
        y = y.reshape(x.shape).astype(x.dtype)
        y = y + SwiGLU(cfg, wid * cfg.n_shared_experts, name="shared")(x)
        return y, ids, held_counts(ids, (first, held))


class DeepSeekV3Block(nn.Module):
    cfg: DeepSeekV3Config
    routed: bool

    @nn.compact
    def __call__(self, x, tables, *, cache=None, positions=None,
                 pages=None):
        cfg = self.cfg
        a, cache = MLAttention(cfg, name="attn")(
            RMSNorm(cfg.rms_norm_eps, name="ln1")(x), tables, cache=cache,
            positions=positions, pages=pages)
        x = x + a
        v = RMSNorm(cfg.rms_norm_eps, name="ln2")(x)
        if not self.routed:
            return x + SwiGLU(cfg, cfg.intermediate_size, name="mlp")(v), cache
        y, ids, counts = RoutedExperts(cfg, name="moe")(v)
        if cache is not None and LOAD_KEY in cache:
            k = ids.shape[-1]
            cache = dict(cache, **{
                LOAD_KEY: cache[LOAD_KEY] + jnp.stack([
                    jnp.int32(1), jnp.sum(counts), jnp.sum(counts > 0),
                    jnp.max(counts)]),
                ROUTE_KEY: ids.reshape(x.shape[0], -1, k)[:, -1]})
        return x + y, cache


class DeepSeekV3(nn.Module):
    """``model(idx) -> logits``; with ``cache`` (the engines' per-layer
    ``{ckv, index}`` list) returns ``(logits, cache)``."""

    cfg: DeepSeekV3Config

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
        tables = rope_tables(cfg)
        new_caches = [] if cache is not None else None
        # a decode program whose layers read their pages in place: ONE
        # walk of the rows' pages (they all read the same rows)
        pages = next((swa.paged_rows(c[PAGES_KEY], c["index"], c[VALID_KEY],
                                     c["ckv"].shape[1])
                      for c in cache or () if PAGES_KEY in c), None)
        for i in range(cfg.n_layer):
            x, layer_cache = DeepSeekV3Block(
                cfg, cfg.is_routed(i), name=f"block_{i}")(
                x, tables, cache=cache[i] if cache is not None else None,
                positions=positions, pages=pages)
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
    def config(self) -> DeepSeekV3Config:
        return self.cfg

    def init_params(self, rng, example_len: int = 8):
        return self.init(rng, jnp.ones((1, example_len), jnp.int32))["params"]

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        """Per layer ``{"ckv": (batch, max_len, kv_lora_rank +
        qk_rope_head_dim), "index"}``: the latent and the shared rope
        key of every position, and nothing per head."""
        return [{"ckv": jnp.zeros((batch, max_len, self.cfg.latent_dim),
                                  dtype),
                 "index": jnp.zeros((), jnp.int32)}
                for _ in range(self.cfg.n_layer)]

    @property
    def cache_slot_axis(self) -> int:
        return 0

    #: a decode program hands every layer the latent pool's pages as they
    #: are stored and each row's block table (``layers.PAGES_KEY``), not a
    #: gathered view
    reads_pages = True

    def step_stats(self, rows: int) -> list[dict]:
        """Zeroed per-layer statistics entries for a serving program's
        transient cache view of ``rows`` rows (module docstring): empty
        for a dense layer."""
        k = self.cfg.n_experts_per_tok
        return [{LOAD_KEY: jnp.zeros((4,), jnp.int32),
                 ROUTE_KEY: jnp.zeros((rows, k), jnp.int32)}
                if self.cfg.is_routed(i) else {}
                for i in range(self.cfg.n_layer)]


def random_params(cfg: DeepSeekV3Config, seed: int, dtype=jnp.bfloat16,
                  std: float = 0.02) -> dict:
    """Seeded weights made ON THE DEVICE in ``dtype``, one leaf at a
    time and a stacked expert leaf one expert at a time (a float32 tree
    of the serving cut would not fit beside its bf16 copy): N(0, ``std``),
    ``e_score_correction_bias`` N(0, 0.01) so that selection and weights
    really differ, norm scales 1. Every layer and every expert is a
    distinct draw."""
    shapes = jax.eval_shape(
        lambda: DeepSeekV3(cfg).init(jax.random.PRNGKey(0),
                                     jnp.ones((1, 8), jnp.int32))["params"])
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    root = jax.random.PRNGKey(int(seed) % (2 ** 31))

    draw = jax.jit(
        lambda key, shape, sigma: (sigma * jax.random.normal(
            key, shape, jnp.float32)).astype(dtype), static_argnums=1)

    out = []
    for i, (path, leaf) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        key = jax.random.fold_in(root, i)
        if name.endswith("['scale']"):
            out.append(jnp.ones(leaf.shape, dtype))
        elif name.endswith("['e_score_correction_bias']"):
            out.append(draw(key, tuple(leaf.shape), 0.01))
        elif name.endswith(("['w_gate']", "['w_up']", "['w_down']")):
            out.append(jnp.stack([
                draw(jax.random.fold_in(key, e), tuple(leaf.shape[1:]), std)
                for e in range(leaf.shape[0])]))
        else:
            out.append(draw(key, tuple(leaf.shape), std))
    return jax.tree_util.tree_unflatten(treedef, out)
