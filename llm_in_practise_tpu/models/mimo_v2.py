"""MiMo-V2 (``model_type`` ``mimo_v2``, huggingface.co/XiaomiMiMo/MiMo-V2.5;
family MiMo-V2-Flash): the text path. Window layers whose attention sees
128 positions beside global layers that see everything (5 : 1), a learned
sink in the window softmax, keys of 192 over values of 128, and
sigmoid-routed experts with no shared expert.

Per layer (``x`` the residual stream, RMSNorm eps 1e-5, no biases):
``h = norm(x)``; ``q = h Wq`` -> (heads, 192); ``k = h Wk`` -> (Hk, 192);
``v = value_scale * h Wv`` -> (Hk, 128), ``Hk`` = 4 in a global layer
(``hybrid_layer_pattern`` 0), 8 in a window layer (1). Rotary on the first
``int(192 * partial_rotary_factor)`` = 64 dimensions of ``q`` and ``k``,
half-split pairing, base ``rope_theta`` (global) or ``swa_rope_theta``
(window); the other dimensions pass through. ``s_ij = q_i . k_j / sqrt(192)``
for ``j <= i`` and, in a window layer, ``i - j < 128``. Global: ``p =
softmax(s)``. Window: ``p_ij = exp(s_ij) / (exp(b_h) + sum_j' exp(s_ij'))``,
``b_h`` the head's sink. ``o = sum_j p_ij v_j`` -> ``Wo``; residual. Then
``ops/swa_attention.py`` holds the three forms (a chunk through the Pallas
kernel, one query over a view, one query over the ring).

Feed-forward: a layer whose ``moe_layer_freq`` entry is 0 is a SwiGLU of
``intermediate_size``; the others route: ``sigmoid`` of float32 router
logits, the ``k`` largest of ``sigmoid + e_score_correction_bias`` chosen
(one group: no group stage), weights = the chosen ``sigmoid`` normalised to
sum 1; experts SwiGLU of ``moe_intermediate_size``; NO shared expert. With
``experts_held`` < ``n_routed_experts`` a layer is one chip's share of an
expert-parallel layer, as ``models/deepseek_v3.py`` has it.

**The cache has two kinds of layer.** ``init_cache(batch, max_len)`` gives
a global layer ``{"k": (B, max_len, 4 * 192), "v": (B, max_len, 4 *
128)}``: a row is ONE vector, whole lane tiles wide (768, 512), which
``serve/paged_kv.py`` stores by pages and gathers a page at a time; 192
alone is 1.5 tiles, and a ``(4, 192)`` row is stored token-minor and
re-laid out twice a program, or padded (docs/paged-kv.md, "Keys of 192":
the chip chose). A window layer gets a RING, ``{"k": (B, R, 8, 192), "v":
(B, R, 8, 128)}`` with ``R = min(max_len, window)``: its row axis does not
follow ``max_len``, which is how ``serve/paged_kv.py`` tells a layer that
grows with the context from one whose state is bounded. A window layer given
``cache["valid"]`` (B,) takes only the first ``valid`` of the call's
positions for real (a chunk's padding, a decode row that is not live,
write nothing into the ring). A decode program of the serving engine hands a
global layer no view at all (the class declares ``reads_pages``): the pool's
two buffers as they are stored by pages and each row's block table under
``layers.PAGES_KEY``; the layer writes its new row into its page
(``layers.page_row_write``) and attends the pages where they lie, to each
row's true length (``swa.paged_decode_attention``), both global layers along
ONE list of the rows' blocks. A chunk row (``L > 1``) keeps its gathered
one-row view.

**Left out**: the multi-token-prediction layers and the vision / audio
encoders (the configuration holds no key of theirs). **Refused by name**
(`from_hf_config`): a sink on global layers, a shared expert, expert
groups, scaled rotary, another scoring function or top-k method.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from llm_in_practise_tpu.models import layers
from llm_in_practise_tpu.models.qwen3 import RMSNorm
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
class MiMoV2Config:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    n_layer: int
    n_head: int
    n_kv_head: int              # global layers
    swa_n_kv_head: int          # window layers
    head_dim: int
    v_head_dim: int
    window: int
    # per layer: 1 = window attention, 0 = global; 1 = routed, 0 = dense
    hybrid_layer_pattern: tuple[int, ...]
    moe_layer_freq: tuple[int, ...]
    n_routed_experts: int
    n_experts_per_tok: int
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # the experts this chip holds: ids expert_offset .. + experts_held - 1
    # (None: all of them)
    experts_held: int | None = None
    expert_offset: int = 0
    rope_theta: float = 10_000_000.0
    swa_rope_theta: float = 10_000.0
    partial_rotary_factor: float = 0.334
    attention_value_scale: float = 0.707
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 4096
    compute_dtype: str = "bfloat16"

    def __post_init__(self):
        if not (len(self.hybrid_layer_pattern) == len(self.moe_layer_freq)
                == self.n_layer):
            raise ValueError("hybrid_layer_pattern and moe_layer_freq must "
                             "have one entry a layer")
        held = self.held
        if not (0 <= held[0] and held[0] + held[1] <= self.n_routed_experts
                and held[1] >= 1):
            raise ValueError(f"held experts {held} outside the router's "
                             f"{self.n_routed_experts}")
        if self.n_head % self.n_kv_head or self.n_head % self.swa_n_kv_head:
            raise ValueError("query heads must divide into K/V heads")

    def replace(self, **kw) -> "MiMoV2Config":
        return dataclasses.replace(self, **kw)

    @property
    def held(self) -> tuple[int, int]:
        """(first held expert id, how many)."""
        return (self.expert_offset, self.n_routed_experts
                if self.experts_held is None else self.experts_held)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def is_window(self, layer: int) -> bool:
        return bool(self.hybrid_layer_pattern[layer])

    def is_routed(self, layer: int) -> bool:
        return bool(self.moe_layer_freq[layer])

    def kv_heads(self, layer: int) -> int:
        return self.swa_n_kv_head if self.is_window(layer) else self.n_kv_head

    @classmethod
    def from_hf_config(cls, hf: dict, **overrides) -> "MiMoV2Config":
        """Build from the model's ``config.json`` keys, plus
        ``experts_held`` / ``expert_offset`` where given. What this file
        does not implement is refused by name, not ignored."""
        def refuse(key, got, want):
            raise ValueError(f"mimo_v2: {key}={got!r} is not implemented "
                             f"(only {want})")

        if hf.get("add_full_attention_sink_bias"):
            refuse("add_full_attention_sink_bias", True, "false")
        if not hf.get("add_swa_attention_sink_bias", True):
            refuse("add_swa_attention_sink_bias", False, "true")
        if hf.get("n_shared_experts"):
            refuse("n_shared_experts", hf["n_shared_experts"], "null")
        if int(hf.get("n_group", 1)) != 1 or int(hf.get("topk_group", 1)) != 1:
            refuse("n_group / topk_group",
                   (hf.get("n_group"), hf.get("topk_group")), "1")
        scaling = hf.get("rope_scaling")
        if scaling is not None:
            kind = scaling.get("rope_type", scaling.get("type"))
            if kind != "default":
                refuse("rope_scaling.rope_type", kind, "'default'")
        if hf.get("scoring_func", "sigmoid") != "sigmoid":
            refuse("scoring_func", hf["scoring_func"], "'sigmoid'")
        if hf.get("topk_method", "noaux_tc") != "noaux_tc":
            refuse("topk_method", hf["topk_method"], "'noaux_tc'")
        if hf.get("hidden_act", "silu") != "silu":
            refuse("hidden_act", hf["hidden_act"], "'silu'")
        if hf.get("attention_bias") or hf.get("tie_word_embeddings"):
            refuse("attention_bias / tie_word_embeddings", True, "false")
        if hf.get("hybrid_block_size") is not None:
            refuse("hybrid_block_size", hf["hybrid_block_size"], "null")
        heads = hf["num_attention_heads"]
        for key, want in (("swa_num_attention_heads", heads),
                          ("swa_head_dim", hf["head_dim"]),
                          ("swa_v_head_dim", hf["v_head_dim"])):
            if hf.get(key, want) != want:
                refuse(key, hf[key], f"{want}, the global layers'")
        window = hf.get("sliding_window", hf.get("sliding_window_size"))
        if hf.get("sliding_window_size", window) != window:
            refuse("sliding_window_size", hf["sliding_window_size"],
                   "sliding_window")
        # read and not applied (the module's assumptions):
        # attention_chunk_size, attention_projection_layout
        scale = hf.get("routed_scaling_factor")
        cfg = cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            n_layer=hf["num_hidden_layers"],
            n_head=heads,
            n_kv_head=hf["num_key_value_heads"],
            swa_n_kv_head=hf["swa_num_key_value_heads"],
            head_dim=hf["head_dim"],
            v_head_dim=hf["v_head_dim"],
            window=int(window),
            hybrid_layer_pattern=tuple(hf["hybrid_layer_pattern"]),
            moe_layer_freq=tuple(hf["moe_layer_freq"]),
            n_routed_experts=hf["n_routed_experts"],
            n_experts_per_tok=hf["num_experts_per_tok"],
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
            routed_scaling_factor=1.0 if scale is None else float(scale),
            experts_held=hf.get("experts_held"),
            expert_offset=int(hf.get("expert_offset", 0)),
            rope_theta=float(hf.get("rope_theta", 10_000_000.0)),
            swa_rope_theta=float(hf.get("swa_rope_theta", 10_000.0)),
            partial_rotary_factor=float(
                hf.get("partial_rotary_factor", 1.0)),
            attention_value_scale=float(
                hf.get("attention_value_scale", 1.0)),
            rms_norm_eps=float(hf.get("layernorm_epsilon",
                                      hf.get("rms_norm_eps", 1e-5))),
            max_seq_len=int(hf.get("max_position_embeddings", 4096)),
        )
        return cfg.replace(**overrides)


def mimo_v2_config(vocab_size: int = 512, **kw) -> MiMoV2Config:
    """Tiny-default constructor for tests that keeps what is distinctive:
    keys wider than values (24 / 16), two K/V head counts (2 / 4 under 8
    query heads), rotary on a part of the key (8 of 24), a window (8) far
    shorter than the rows, 1 dense + 3 routed layers of 16 experts."""
    defaults = dict(
        vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, n_layer=4, n_head=8, n_kv_head=2,
        swa_n_kv_head=4, head_dim=24, v_head_dim=16, window=8,
        hybrid_layer_pattern=(0, 1, 1, 0), moe_layer_freq=(0, 1, 1, 1),
        n_routed_experts=16, n_experts_per_tok=4, max_seq_len=256)
    defaults.update(kw)
    return MiMoV2Config(**defaults)


def _dense(cfg, feats, name):
    return nn.Dense(feats, use_bias=False, dtype=jnp.dtype(cfg.compute_dtype),
                    kernel_init=nn.initializers.normal(0.02), name=name)


class HybridAttention(nn.Module):
    """One layer's attention, global or window by ``window``."""

    cfg: MiMoV2Config
    window: bool

    @nn.compact
    def __call__(self, x, *, cache=None, positions=None, pages=None):
        cfg = self.cfg
        b, l, _ = x.shape
        h, dq, dv = cfg.n_head, cfg.head_dim, cfg.v_head_dim
        hk = cfg.swa_n_kv_head if self.window else cfg.n_kv_head
        compute = jnp.dtype(cfg.compute_dtype)
        q = _dense(cfg, h * dq, "q_proj")(x).reshape(b, l, h, dq)
        k = _dense(cfg, hk * dq, "k_proj")(x).reshape(b, l, hk, dq)
        v = (_dense(cfg, hk * dv, "v_proj")(x)
             * cfg.attention_value_scale).astype(compute).reshape(
                 b, l, hk, dv)
        sink = None
        if self.window:
            sink = self.param("attention_sink_bias",
                              nn.initializers.zeros, (h,), jnp.float32)
        start = jnp.zeros((b,), jnp.int32)
        if cache is not None:
            start = jnp.broadcast_to(
                jnp.asarray(cache["index"], jnp.int32), (b,))
        if positions is None:
            positions = start[:, None] + jnp.arange(l)[None, :]
        rot = cfg.rotary_dim
        cos, sin = rope_ops.precompute_cos_sin(
            rot, cfg.max_seq_len,
            cfg.swa_rope_theta if self.window else cfg.rope_theta)

        def rotate(t):
            turned = rope_ops.apply_rotary_emb(
                t[..., :rot], cos, sin, positions=positions,
                interleaved=False)
            return jnp.concatenate([turned, t[..., rot:]], axis=-1).astype(
                compute)

        q, k = rotate(q), rotate(k)
        scale = dq ** -0.5
        if cache is None:
            out = swa.prefill_attention(
                q, k, v, start, scale=scale, sink=sink,
                window=cfg.window if self.window else None)
        elif self.window:
            valid = cache.get(VALID_KEY)
            valid = (jnp.full((b,), l, jnp.int32) if valid is None
                     else jnp.minimum(valid.astype(jnp.int32), l))
            ring_k = swa.ring_write(cache["k"], k, start, valid)
            ring_v = swa.ring_write(cache["v"], v, start, valid)
            if l == 1:
                out = swa.ring_decode_attention(
                    q, ring_k, ring_v, start, scale=scale,
                    window=cfg.window, sink=sink)
            else:
                out = swa.prefill_attention(
                    q, k, v, start, scale=scale, window=cfg.window,
                    sink=sink, cached=(cache["k"], cache["v"]))
            cache = dict(cache, k=ring_k, v=ring_v,
                         index=cache["index"] + l)
        elif PAGES_KEY in cache:
            # a global layer over the pool's PAGES (a decode program, l ==
            # 1): the new row goes into its page, and the query walks the
            # row's pages to its true length (``pages``: the model's one
            # ``swa.paged_rows`` a program); a row that is not live writes
            # into the trash page and reads nothing
            pool_k, pool_v = (
                layers.page_row_write(cache[key], pages["table"], start,
                                      cache[VALID_KEY], new.reshape(b, -1))
                for key, new in (("k", k), ("v", v)))
            out = swa.paged_decode_attention(
                q, pool_k, pool_v, scale=scale, kv_heads=hk, v_dim=dv,
                **pages)
            cache = dict(cache, k=pool_k, v=pool_v, index=cache["index"] + l)
        else:
            # a global layer's rows are FLAT (init_cache): one vector of
            # Hk * 192 / Hk * 128, whole lane tiles
            k_all = layers.cache_update(
                cache["k"], k.reshape(b, l, hk * dq), cache["index"])
            v_all = layers.cache_update(
                cache["v"], v.reshape(b, l, hk * dv), cache["index"])
            if l == 1:
                out = swa.decode_attention(q, k_all.astype(compute),
                                           v_all.astype(compute), start,
                                           scale=scale)
            else:
                rows = k_all.shape[1]
                out = swa.prefill_attention(
                    q, k_all.astype(compute).reshape(b, rows, hk, dq),
                    v_all.astype(compute).reshape(b, rows, hk, dv), start,
                    scale=scale)
            cache = dict(cache, k=k_all, v=v_all, index=cache["index"] + l)
        return _dense(cfg, cfg.hidden_size, "o_proj")(
            out.reshape(b, l, h * dv)), cache


class SwiGLU(nn.Module):
    cfg: MiMoV2Config
    width: int

    @nn.compact
    def __call__(self, x):
        gate = _dense(self.cfg, self.width, "gate_proj")(x)
        up = _dense(self.cfg, self.width, "up_proj")(x)
        return _dense(self.cfg, self.cfg.hidden_size, "down_proj")(
            nn.silu(gate) * up)


class RoutedExperts(nn.Module):
    """The routed experts held here (no shared expert). Returns ``(y,
    ids (N, k), held counts (held,))``."""

    cfg: MiMoV2Config

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
        # one group: route() runs no group stage
        ids, weights = route(
            flat, router, cfg.n_experts_per_tok,
            norm_topk=cfg.norm_topk_prob, scoring="sigmoid", bias=bias,
            scale=cfg.routed_scaling_factor)
        self.sow("routing", "experts", ids)
        y = grouped_expert_ffn(
            flat.astype(compute), ids, weights, w_gate.astype(compute),
            w_up.astype(compute), w_down.astype(compute),
            held=(first, held), n_experts=cfg.n_routed_experts)
        return (y.reshape(x.shape).astype(x.dtype), ids,
                held_counts(ids, (first, held)))


class MiMoV2Block(nn.Module):
    cfg: MiMoV2Config
    window: bool
    routed: bool

    @nn.compact
    def __call__(self, x, *, cache=None, positions=None, pages=None):
        cfg = self.cfg
        a, cache = HybridAttention(cfg, self.window, name="attn")(
            RMSNorm(cfg.rms_norm_eps, name="ln1")(x), cache=cache,
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


class MiMoV2(nn.Module):
    """``model(idx) -> logits``; with ``cache`` (the engines' per-layer
    list) returns ``(logits, cache)``."""

    cfg: MiMoV2Config

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
        new_caches = [] if cache is not None else None
        # a decode program whose global layers read their pages in place:
        # ONE walk of the rows' pages (they all read the same rows)
        pages = next((swa.paged_rows(c[PAGES_KEY], c["index"], c[VALID_KEY],
                                     c["v"].shape[1])
                      for c in cache or () if PAGES_KEY in c), None)
        for i in range(cfg.n_layer):
            x, layer_cache = MiMoV2Block(
                cfg, cfg.is_window(i), cfg.is_routed(i), name=f"block_{i}")(
                x, cache=cache[i] if cache is not None else None,
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
    def config(self) -> MiMoV2Config:
        return self.cfg

    def init_params(self, rng, example_len: int = 8):
        return self.init(rng, jnp.ones((1, example_len), jnp.int32))["params"]

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        """Per layer ``{"k", "v", "index"}``: a global layer's rows follow
        ``max_len`` and are FLAT, ``Hk * 192`` and ``Hk * 128`` wide; a
        window layer's are a ring of ``min(max_len, window)`` rows of
        ``(Hk, ·)`` (module docstring)."""
        cfg = self.cfg
        out = []
        for i in range(cfg.n_layer):
            hk = cfg.kv_heads(i)
            if cfg.is_window(i):
                shapes = ((batch, min(max_len, cfg.window), hk, d)
                          for d in (cfg.head_dim, cfg.v_head_dim))
            else:
                shapes = ((batch, max_len, hk * d)
                          for d in (cfg.head_dim, cfg.v_head_dim))
            k, v = (jnp.zeros(shape, dtype) for shape in shapes)
            out.append({"k": k, "v": v, "index": jnp.zeros((), jnp.int32)})
        return out

    @property
    def cache_slot_axis(self) -> int:
        return 0

    #: a decode program hands the global layers the pool's pages as they
    #: are stored and each row's block table (``layers.PAGES_KEY``), not a
    #: gathered view
    reads_pages = True

    def step_stats(self, rows: int) -> list[dict]:
        """Zeroed per-layer statistics entries for a serving program's
        transient cache view of ``rows`` rows (``serve/step_stats.py``):
        empty for a dense layer."""
        k = self.cfg.n_experts_per_tok
        return [{LOAD_KEY: jnp.zeros((4,), jnp.int32),
                 ROUTE_KEY: jnp.zeros((rows, k), jnp.int32)}
                if self.cfg.is_routed(i) else {}
                for i in range(self.cfg.n_layer)]


def random_params(cfg: MiMoV2Config, seed: int, dtype=jnp.bfloat16,
                  std: float = 0.02) -> dict:
    """Seeded weights made ON THE DEVICE in ``dtype``, one leaf at a time
    and a stacked expert leaf one expert at a time: N(0, ``std``),
    ``e_score_correction_bias`` N(0, 0.01), sinks N(0, 1) in float32 (a
    sink of 0 among scores near 0 is one key more; a learned one is not),
    norm scales 1. Every layer and every expert is a distinct draw."""
    shapes = jax.eval_shape(
        lambda: MiMoV2(cfg).init(jax.random.PRNGKey(0),
                                 jnp.ones((1, 8), jnp.int32))["params"])
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    root = jax.random.PRNGKey(int(seed) % (2 ** 31))

    draw = jax.jit(
        lambda key, shape, sigma, kind: (sigma * jax.random.normal(
            key, shape, jnp.float32)).astype(kind), static_argnums=(1, 3))

    out = []
    for i, (path, leaf) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        key = jax.random.fold_in(root, i)
        if name.endswith("['scale']"):
            out.append(jnp.ones(leaf.shape, dtype))
        elif name.endswith("['attention_sink_bias']"):
            out.append(draw(key, tuple(leaf.shape), 1.0, jnp.float32))
        elif name.endswith("['e_score_correction_bias']"):
            out.append(draw(key, tuple(leaf.shape), 0.01, dtype))
        elif name.endswith(("['w_gate']", "['w_up']", "['w_down']")):
            out.append(jnp.stack([
                draw(jax.random.fold_in(key, e), tuple(leaf.shape[1:]), std,
                     dtype)
                for e in range(leaf.shape[0])]))
        else:
            out.append(draw(key, tuple(leaf.shape), std, dtype))
    return jax.tree_util.tree_unflatten(treedef, out)
