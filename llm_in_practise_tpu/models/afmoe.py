"""Arcee Trinity (``model_type`` ``afmoe``,
huggingface.co/arcee-ai/Trinity-Large-Preview; family Trinity Large
400B-A13B): window layers that see 4,096 positions beside every fourth
layer that sees everything and carries NO position, a sigmoid gate on the
attention output, QK-norm, four norms a layer, and sigmoid-routed experts
beside a shared one.

Per layer (``x`` the residual stream, RMSNorm eps 1e-5 with a learned
scale, no biases): ``x0 = embed(ids) * sqrt(hidden)`` (``mup_enabled``).
``h = norm_in(x)``; ``q = h Wq`` -> (48, 128); ``k = h Wk``, ``v = h Wv``
-> (8, 128); ``g = h Wg`` -> (48 * 128). ``q``, ``k`` <- RMSNorm over the
128 (one learned scale each, shared by the heads), BEFORE rotary.
``layer_types[n] == "sliding_attention"``: rotary on all 128 dimensions,
half-split pairing, base ``rope_theta``; ``s_ij = q_i . k_j / sqrt(128)``
for ``0 <= i - j < sliding_window``. ``"full_attention"``: NO rotary;
``s_ij`` for ``j <= i``. ``p = softmax_j(s)`` (no sink); ``o = sum_j p_ij
v_j``; ``a = (o * sigmoid(g)) Wo``; ``x <- x + norm_post_attn(a)``.
``h' = norm_pre_mlp(x)``; the first ``num_dense_layers`` layers are a
SwiGLU of ``intermediate_size``; the others route: ``sigmoid`` of float32
router logits, the ``k`` largest of ``sigmoid + expert_bias`` chosen (one
group), weights = the chosen ``sigmoid`` / (their sum + 1e-20) *
``route_scale``; experts SwiGLU of ``moe_intermediate_size``, plus the
shared expert's SwiGLU (``moe_intermediate_size * num_shared_experts``) of
the same ``h'``. ``x <- x + norm_post_mlp(m)``. Final norm, untied head.
With ``experts_held`` < ``n_routed_experts`` a layer is one chip's share
of an expert-parallel layer, as ``models/deepseek_v3.py`` has it: the
shared expert is computed by every share.

``ops/swa_attention.py`` holds the attention's three forms, as for
``models/mimo_v2.py`` (keys and values both 128 wide here, no sink): a
chunk through the Pallas kernel, a window layer's over ``[its ring ‖ its
own keys]`` in one call because the ring (4,096 rows) is longer than a
chunk; one query over a view of flat rows; one query over the ring.

**The cache has two kinds of layer** (``serve/paged_kv.py`` reads them off
the template): a global layer ``{"k", "v": (B, max_len, 8 * 128)}``, a row
ONE vector of whole lane tiles, stored by pages and attended flat
(docs/paged-kv.md); a window layer a RING ``{"k", "v": (B, R, 8, 128)}``,
``R = min(max_len, window)``, whose row axis does not follow ``max_len``.
A window layer given ``cache["valid"]`` (B,) takes only the first
``valid`` of the call's positions for real.

**Assumed** (the configuration gives ``sliding_window``, ``layer_types``,
``mup_enabled``, the router's keys; the rest follows the family's public
implementation): the gate is a linear of the layer's normed input, applied
to the concatenated heads before ``Wo``; QK-norm before rotary; rotary on
window layers only; the sandwich norm's depth scaling is an initialisation
and no part of the forward pass; muP is the embedding's factor alone;
``expert_bias`` enters the selection only. **Read and not applied**:
``load_balance_coeff`` (training), ``use_grouped_mm`` (an implementation
switch), ``global_attn_every_n_layers`` (implied by ``layer_types``).
**Refused by name** (`from_hf_config`): scaled rotary, another scoring
function or activation, expert groups, tied embeddings, an unknown layer
type.
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
VALID_KEY = layers.VALID_KEY
SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    n_layer: int
    n_head: int
    n_kv_head: int
    head_dim: int
    window: int
    # per layer: True = window attention (rotary), False = global (none)
    window_layers: tuple[bool, ...]
    # the first ``n_dense_layers`` layers are dense, the others route
    n_dense_layers: int
    n_routed_experts: int
    n_experts_per_tok: int
    n_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 1.0
    mup_enabled: bool = True
    # the experts this chip holds: ids expert_offset .. + experts_held - 1
    # (None: all of them)
    experts_held: int | None = None
    expert_offset: int = 0
    rope_theta: float = 10_000.0
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 4096
    compute_dtype: str = "bfloat16"

    def __post_init__(self):
        if len(self.window_layers) != self.n_layer:
            raise ValueError("layer_types must have one entry a layer")
        held = self.held
        if not (0 <= held[0] and held[0] + held[1] <= self.n_routed_experts
                and held[1] >= 1):
            raise ValueError(f"held experts {held} outside the router's "
                             f"{self.n_routed_experts}")
        if self.n_head % self.n_kv_head:
            raise ValueError("query heads must divide into K/V heads")

    def replace(self, **kw) -> "AfmoeConfig":
        return dataclasses.replace(self, **kw)

    @property
    def held(self) -> tuple[int, int]:
        """(first held expert id, how many)."""
        return (self.expert_offset, self.n_routed_experts
                if self.experts_held is None else self.experts_held)

    def is_window(self, layer: int) -> bool:
        return bool(self.window_layers[layer])

    def is_routed(self, layer: int) -> bool:
        return layer >= self.n_dense_layers

    @property
    def embed_scale(self) -> float:
        return float(self.hidden_size) ** 0.5 if self.mup_enabled else 1.0

    @classmethod
    def from_hf_config(cls, hf: dict, **overrides) -> "AfmoeConfig":
        """Build from the model's ``config.json`` keys, plus
        ``experts_held`` / ``expert_offset`` where given. What this file
        does not implement is refused by name, not ignored."""
        def refuse(key, got, want):
            raise ValueError(f"afmoe: {key}={got!r} is not implemented "
                             f"(only {want})")

        if hf.get("rope_scaling") is not None:
            refuse("rope_scaling", hf["rope_scaling"], "null")
        if hf.get("score_func", "sigmoid") != "sigmoid":
            refuse("score_func", hf["score_func"], "'sigmoid'")
        for key in ("n_group", "topk_group", "num_expert_groups",
                    "num_limited_groups"):
            if int(hf.get(key, 1)) != 1:
                refuse(key, hf[key], "1")
        if hf.get("hidden_act", "silu") != "silu":
            refuse("hidden_act", hf["hidden_act"], "'silu'")
        if hf.get("tie_word_embeddings"):
            refuse("tie_word_embeddings", True, "false")
        for kind in hf["layer_types"]:
            if kind not in (SLIDING, FULL):
                refuse("layer_types", kind, f"{SLIDING!r} / {FULL!r}")
        # read and not applied (the module's assumptions):
        # load_balance_coeff, use_grouped_mm, global_attn_every_n_layers
        cfg = cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            n_layer=hf["num_hidden_layers"],
            n_head=hf["num_attention_heads"],
            n_kv_head=hf["num_key_value_heads"],
            head_dim=hf["head_dim"],
            window=int(hf["sliding_window"]),
            window_layers=tuple(k == SLIDING for k in hf["layer_types"]),
            n_dense_layers=int(hf["num_dense_layers"]),
            n_routed_experts=hf["num_experts"],
            n_experts_per_tok=hf["num_experts_per_tok"],
            n_shared_experts=int(hf.get("num_shared_experts", 0)),
            route_norm=bool(hf.get("route_norm", True)),
            route_scale=float(hf.get("route_scale", 1.0)),
            mup_enabled=bool(hf.get("mup_enabled", False)),
            experts_held=hf.get("experts_held"),
            expert_offset=int(hf.get("expert_offset", 0)),
            rope_theta=float(hf.get("rope_theta", 10_000.0)),
            rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
            max_seq_len=int(hf.get("max_position_embeddings", 4096)),
        )
        return cfg.replace(**overrides)


def afmoe_config(vocab_size: int = 512, **kw) -> AfmoeConfig:
    """Tiny-default constructor for tests that keeps what is distinctive:
    three window layers (rotary, a ring of 8) to one global layer (no
    position), 6 query heads on 2 K/V heads, 1 dense + 3 routed layers of
    16 experts beside a shared one."""
    defaults = dict(
        vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, n_layer=4, n_head=6, n_kv_head=2,
        head_dim=16, window=8, window_layers=(True, True, True, False),
        n_dense_layers=1, n_routed_experts=16, n_experts_per_tok=4,
        route_scale=2.448, max_seq_len=256)
    defaults.update(kw)
    return AfmoeConfig(**defaults)


def _dense(cfg, feats, name):
    return nn.Dense(feats, use_bias=False, dtype=jnp.dtype(cfg.compute_dtype),
                    kernel_init=nn.initializers.normal(0.02), name=name)


class GatedAttention(nn.Module):
    """One layer's attention, window (rotary) or global (none) by
    ``window``, its output gated before ``Wo``."""

    cfg: AfmoeConfig
    window: bool

    @nn.compact
    def __call__(self, x, *, cache=None, positions=None):
        cfg = self.cfg
        b, l, _ = x.shape
        h, hk, d = cfg.n_head, cfg.n_kv_head, cfg.head_dim
        compute = jnp.dtype(cfg.compute_dtype)
        q = _dense(cfg, h * d, "q_proj")(x).reshape(b, l, h, d)
        k = _dense(cfg, hk * d, "k_proj")(x).reshape(b, l, hk, d)
        v = _dense(cfg, hk * d, "v_proj")(x).reshape(b, l, hk, d)
        gate = _dense(cfg, h * d, "gate_proj")(x)
        q = RMSNorm(cfg.rms_norm_eps, name="q_norm")(q)
        k = RMSNorm(cfg.rms_norm_eps, name="k_norm")(k)
        start = jnp.zeros((b,), jnp.int32)
        if cache is not None:
            start = jnp.broadcast_to(
                jnp.asarray(cache["index"], jnp.int32), (b,))
        if self.window:
            # a global layer carries no position at all
            if positions is None:
                positions = start[:, None] + jnp.arange(l)[None, :]
            cos, sin = rope_ops.precompute_cos_sin(
                d, cfg.max_seq_len, cfg.rope_theta)
            q, k = (rope_ops.apply_rotary_emb(
                t, cos, sin, positions=positions,
                interleaved=False).astype(compute) for t in (q, k))
        scale = d ** -0.5
        if cache is None:
            out = swa.prefill_attention(
                q, k, v, start, scale=scale,
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
                    window=cfg.window)
            else:
                out = swa.prefill_attention(
                    q, k, v, start, scale=scale, window=cfg.window,
                    cached=(cache["k"], cache["v"]))
            cache = dict(cache, k=ring_k, v=ring_v,
                         index=cache["index"] + l)
        else:
            # a global layer's rows are FLAT (init_cache): one vector of
            # Hk * 128, whole lane tiles
            k_all = layers.cache_update(
                cache["k"], k.reshape(b, l, hk * d), cache["index"])
            v_all = layers.cache_update(
                cache["v"], v.reshape(b, l, hk * d), cache["index"])
            if l == 1:
                out = swa.decode_attention(q, k_all.astype(compute),
                                           v_all.astype(compute), start,
                                           scale=scale)
            else:
                rows = k_all.shape[1]
                out = swa.prefill_attention(
                    q, k_all.astype(compute).reshape(b, rows, hk, d),
                    v_all.astype(compute).reshape(b, rows, hk, d), start,
                    scale=scale)
            cache = dict(cache, k=k_all, v=v_all, index=cache["index"] + l)
        gated = out.reshape(b, l, h * d) * nn.sigmoid(gate)
        return _dense(cfg, cfg.hidden_size, "o_proj")(
            gated.astype(compute)), cache


class SwiGLU(nn.Module):
    cfg: AfmoeConfig
    width: int

    @nn.compact
    def __call__(self, x):
        gate = _dense(self.cfg, self.width, "gate_proj")(x)
        up = _dense(self.cfg, self.width, "up_proj")(x)
        return _dense(self.cfg, self.cfg.hidden_size, "down_proj")(
            nn.silu(gate) * up)


class RoutedExperts(nn.Module):
    """The routed experts held here plus the shared expert. Returns ``(y,
    ids (N, k), held counts (held,))``."""

    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        init = nn.initializers.normal(0.02)
        hid, wid = cfg.hidden_size, cfg.moe_intermediate_size
        first, held = cfg.held
        router = self.param("router", init, (hid, cfg.n_routed_experts))
        bias = self.param("expert_bias", nn.initializers.normal(0.01),
                          (cfg.n_routed_experts,))
        w_gate = self.param("w_gate", init, (held, hid, wid))
        w_up = self.param("w_up", init, (held, hid, wid))
        w_down = self.param("w_down", init, (held, wid, hid))
        compute = jnp.dtype(cfg.compute_dtype)
        flat = x.reshape(-1, hid)
        # one group: route() runs no group stage
        ids, weights = route(
            flat, router, cfg.n_experts_per_tok, norm_topk=cfg.route_norm,
            scoring="sigmoid", bias=bias, scale=cfg.route_scale)
        self.sow("routing", "experts", ids)
        y = grouped_expert_ffn(
            flat.astype(compute), ids, weights, w_gate.astype(compute),
            w_up.astype(compute), w_down.astype(compute),
            held=(first, held), n_experts=cfg.n_routed_experts)
        y = y.reshape(x.shape).astype(x.dtype)
        if cfg.n_shared_experts:
            y = y + SwiGLU(cfg, wid * cfg.n_shared_experts, name="shared")(x)
        return y, ids, held_counts(ids, (first, held))


class AfmoeBlock(nn.Module):
    cfg: AfmoeConfig
    window: bool
    routed: bool

    @nn.compact
    def __call__(self, x, *, cache=None, positions=None, valid=None):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, name=name)  # noqa: E731
        a, cache = GatedAttention(cfg, self.window, name="attn")(
            norm("norm_in")(x), cache=cache, positions=positions)
        x = x + norm("norm_post_attn")(a)
        v = norm("norm_pre_mlp")(x)
        if not self.routed:
            m = SwiGLU(cfg, cfg.intermediate_size, name="mlp")(v)
            return x + norm("norm_post_mlp")(m), cache
        m, ids, counts = RoutedExperts(cfg, name="moe")(v)
        if cache is not None and LOAD_KEY in cache:
            b, l = x.shape[:2]
            # the experts of each row's last REAL position (a padded
            # chunk's last row is padding: its prompt's last token, whose
            # logits the program returns, lies at ``valid - 1``)
            at = (jnp.full((b,), l - 1) if valid is None
                  else jnp.clip(valid.astype(jnp.int32), 1, l) - 1)
            chosen = ids.reshape(b, l, ids.shape[-1])
            cache = dict(cache, **{
                LOAD_KEY: cache[LOAD_KEY] + jnp.stack([
                    jnp.int32(1), jnp.sum(counts), jnp.sum(counts > 0),
                    jnp.max(counts)]),
                ROUTE_KEY: jnp.take_along_axis(
                    chosen, at[:, None, None], axis=1)[:, 0]})
        return x + norm("norm_post_mlp")(m), cache


class Afmoe(nn.Module):
    """``model(idx) -> logits``; with ``cache`` (the engines' per-layer
    list) returns ``(logits, cache)``."""

    cfg: AfmoeConfig

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
        x = (embed(idx) * cfg.embed_scale).astype(compute)
        new_caches = [] if cache is not None else None
        # how many of the call's positions are real, a row: the engine
        # tells the layers that own their writes (the rings); the routed
        # layers report their experts at the last real one
        valid = next((c[VALID_KEY] for c in cache or () if VALID_KEY in c),
                     None)
        for i in range(cfg.n_layer):
            x, layer_cache = AfmoeBlock(
                cfg, cfg.is_window(i), cfg.is_routed(i), name=f"block_{i}")(
                x, cache=cache[i] if cache is not None else None,
                positions=positions, valid=valid)
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
    def config(self) -> AfmoeConfig:
        return self.cfg

    def init_params(self, rng, example_len: int = 8):
        return self.init(rng, jnp.ones((1, example_len), jnp.int32))["params"]

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        """Per layer ``{"k", "v", "index"}``: a global layer's rows follow
        ``max_len`` and are FLAT, ``Hk * 128`` wide; a window layer's are
        a ring of ``min(max_len, window)`` rows of ``(Hk, 128)`` (module
        docstring)."""
        cfg = self.cfg
        out = []
        for i in range(cfg.n_layer):
            shape = ((batch, min(max_len, cfg.window), cfg.n_kv_head,
                      cfg.head_dim) if cfg.is_window(i)
                     else (batch, max_len, cfg.n_kv_head * cfg.head_dim))
            out.append({"k": jnp.zeros(shape, dtype),
                        "v": jnp.zeros(shape, dtype),
                        "index": jnp.zeros((), jnp.int32)})
        return out

    @property
    def cache_slot_axis(self) -> int:
        return 0

    def step_stats(self, rows: int) -> list[dict]:
        """Zeroed per-layer statistics entries for a serving program's
        transient cache view of ``rows`` rows (``serve/step_stats.py``):
        empty for a dense layer."""
        k = self.cfg.n_experts_per_tok
        return [{LOAD_KEY: jnp.zeros((4,), jnp.int32),
                 ROUTE_KEY: jnp.zeros((rows, k), jnp.int32)}
                if self.cfg.is_routed(i) else {}
                for i in range(self.cfg.n_layer)]


def random_params(cfg: AfmoeConfig, seed: int, dtype=jnp.bfloat16,
                  std: float = 0.02) -> dict:
    """Seeded weights made ON THE DEVICE in ``dtype``, one leaf at a time
    and a stacked expert leaf one expert at a time: N(0, ``std``),
    ``expert_bias`` N(0, 0.01), norm scales 1. Every layer and every
    expert is a distinct draw."""
    shapes = jax.eval_shape(
        lambda: Afmoe(cfg).init(jax.random.PRNGKey(0),
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
        elif name.endswith("['expert_bias']"):
            out.append(draw(key, tuple(leaf.shape), 0.01, dtype))
        elif name.endswith(("['w_gate']", "['w_up']", "['w_down']")):
            out.append(jnp.stack([
                draw(jax.random.fold_in(key, e), tuple(leaf.shape[1:]), std,
                     dtype)
                for e in range(leaf.shape[0])]))
        else:
            out.append(draw(key, tuple(leaf.shape), std, dtype))
    return jax.tree_util.tree_unflatten(treedef, out)
