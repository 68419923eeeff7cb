"""LFM2-MoE (``model_type`` ``lfm2_moe``,
huggingface.co/LiquidAI/LFM2-24B-A2B): gated SHORT-CONVOLUTION mixers in
three layers of four, grouped-query attention with QK-norm and rotary in
the fourth, and behind either a dense SwiGLU (the leading layers) or routed
experts chosen by a sigmoid router with a selection bias.

**Layer** (pre-norm, two residuals; RMSNorm with a learned scale, eps
``norm_eps``, no biases anywhere): ``h = x + mixer(norm_op(x))``; ``y = h +
ffn(norm_ffn(h))``. ``x0 = E[ids]``; after the last layer one RMSNorm (the
family's ``embedding_norm``), ``logits = x E^T`` (tied).

**Short-convolution mixer** (``layer_types[n] == "conv"``): ``[B ‖ C ‖ u] =
n W_in`` (hidden -> 3 hidden, split in that order); ``g_t = B_t * u_t``;
``c_t = sum_{j<3} w_j * g_{t-2+j}`` (depthwise, causal, ``conv_L_cache`` = 3
taps a channel, ``g`` before the sequence zero, no bias, no activation);
``out = (C_t * c_t) W_out``. What the layer keeps between tokens is ``(g_{t-2},
g_{t-1})``: two rows, REPLACED at every position.

**Attention mixer** (``"full_attention"``): ``q, k, v = n W_q, n W_k, n
W_v``; RMSNorm over each head's ``head_dim`` values of ``q`` and of ``k``
(one learned scale each) BEFORE rotary; half-split rotary over the whole
head at ``rope_theta``; causal softmax of ``q . k / sqrt(head_dim)``,
``n_head / n_kv_head`` query heads a K/V head; ``W_o``.

**Feed-forward**: the first ``n_dense_layers`` layers ``(silu(n W_1) * n
W_3) W_2`` of ``intermediate_size``; the others route: ``s = sigmoid(n
W_g)`` in float32, the ``k`` experts with the largest ``s + expert_bias``
(the bias SELECTS only), weights ``s_e / (sum of the chosen s + 1e-6)`` times
``routed_scaling_factor``; experts SwiGLU of ``moe_intermediate_size``, all
of them held here, no shared expert (``ops/grouped_experts.py``).

**The cache has two kinds of layer** (``serve/paged_kv.py`` reads them off
the template): an attention layer ``{"k", "v": (B, max_len, n_kv_head *
head_dim)}``, a row ONE vector of whole lane tiles, stored by pages; a decode
program of the serving engine hands it no view (the class declares
``reads_pages``): the pool's buffers as they are stored and each row's block
table under ``layers.PAGES_KEY``; the layer writes its new row into its page
and the query walks the pages where they lie, to the row's true length
(``swa.paged_decode_attention``, every attention layer along ONE list of the
rows' blocks). A chunk row (``L > 1``) keeps the gathered view and the
kernel of ``swa.prefill_attention``. A conv layer ``{"conv": (B, 2,
hidden)}`` in the COMPUTE dtype whatever the cache's: its shape does not
change with ``max_len`` at all, so ``paged_kv.cache_kinds`` calls it
``recurrent`` and it is held by slot. State discipline: a row whose
``valid`` is 0 keeps its tail bit for bit; padding does not advance it; a
live call that starts at position 0 starts from zeros whatever the slot's
last tenant left.

**Maximal runs of layers of one kind are STACKED** (:meth:`Lfm2MoeConfig.runs`
names them: ``run_<first layer>``, every leaf with a leading layer axis) and
a run of conv layers goes through ONE traced body (``lax.scan``): the
published order of kinds is kept. The stacked experts of a run are handed to
the grouped kernel WHOLE, ``(layers * experts, ·, ·)``, with the layer's
offset added to the expert ids: a scan that sliced a layer's experts out of
the stack would copy them for the kernel, every step.

**Assumed** (the configuration has no key for them): the head is tied; the
projection's split order ``B, C, u``; the tail is kept in the compute dtype;
the router's matmul and sigmoid are float32. **Read and not applied**:
``model_type``. **Refused by name** (``from_hf_config``): a convolution bias,
scaled rotary, untied embeddings, a head width other than hidden / heads, an
unknown layer type.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from llm_in_practise_tpu.models import layers
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
CONV, FULL = "conv", "full_attention"
# the name the mixer's gate - convolve - gate carries (docs/observability.md)
SHORT_CONV_SCOPE = "short_conv"
ROUTE_NORM_EPS = 1e-6       # the published renormalisation's


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    n_layer: int
    n_head: int
    n_kv_head: int
    layer_types: tuple[str, ...]
    n_dense_layers: int
    n_experts: int
    n_experts_per_tok: int
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    conv_L_cache: int = 3
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 4096
    compute_dtype: str = "bfloat16"

    def __post_init__(self):
        if len(self.layer_types) != self.n_layer:
            raise ValueError("layer_types must have one entry a layer")
        if self.n_head % self.n_kv_head or self.hidden_size % self.n_head:
            raise ValueError("query heads must divide the hidden size and "
                             "into K/V heads")

    def replace(self, **kw) -> "Lfm2MoeConfig":
        return dataclasses.replace(self, **kw)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.n_head

    @property
    def held(self) -> tuple[int, int]:
        """(first held expert id, how many): all of them."""
        return (0, self.n_experts)

    def is_routed(self, layer: int) -> bool:
        return layer >= self.n_dense_layers

    @property
    def runs(self) -> tuple[tuple[str, bool, int, int], ...]:
        """Maximal runs of consecutive layers of one kind: ``(layer type,
        routed, first layer, how many)``."""
        out: list[tuple[str, bool, int, int]] = []
        for n, kind in enumerate(self.layer_types):
            routed = self.is_routed(n)
            if out and out[-1][:2] == (kind, routed):
                out[-1] = (kind, routed, out[-1][2], out[-1][3] + 1)
            else:
                out.append((kind, routed, n, 1))
        return tuple(out)

    @classmethod
    def from_hf_config(cls, hf: dict, **overrides) -> "Lfm2MoeConfig":
        """Build from the model's ``config.json`` keys. What this file
        does not implement is refused by name, not ignored."""
        def refuse(key, got, want):
            raise ValueError(f"lfm2_moe: {key}={got!r} is not implemented "
                             f"(only {want})")

        if hf.get("conv_bias"):
            refuse("conv_bias", hf["conv_bias"], "false")
        if not hf.get("tie_word_embeddings", True):
            refuse("tie_word_embeddings", False, "true")
        rope = hf.get("rope_parameters") or {}
        if rope.get("rope_type", "default") != "default" or hf.get(
                "rope_scaling") is not None:
            refuse("rope_type", rope.get("rope_type", hf.get("rope_scaling")),
                   "'default'")
        for kind in hf["layer_types"]:
            if kind not in (CONV, FULL):
                refuse("layer_types", kind, f"{CONV!r} / {FULL!r}")
        heads = hf["num_attention_heads"]
        if hf.get("head_dim") not in (None, hf["hidden_size"] // heads):
            refuse("head_dim", hf["head_dim"], "hidden_size / heads")
        cfg = cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            n_layer=hf["num_hidden_layers"],
            n_head=heads,
            n_kv_head=hf["num_key_value_heads"],
            layer_types=tuple(hf["layer_types"]),
            n_dense_layers=int(hf["num_dense_layers"]),
            n_experts=hf["num_experts"],
            n_experts_per_tok=hf["num_experts_per_tok"],
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            use_expert_bias=bool(hf.get("use_expert_bias", True)),
            conv_L_cache=int(hf.get("conv_L_cache", 3)),
            rope_theta=float(rope.get("rope_theta",
                                      hf.get("rope_theta", 1_000_000.0))),
            norm_eps=float(hf.get("norm_eps", 1e-5)),
            max_seq_len=int(hf.get("max_position_embeddings", 4096)),
        )
        return cfg.replace(**overrides)


def lfm2_moe_config(vocab_size: int = 512, **kw) -> Lfm2MoeConfig:
    """Tiny-default constructor for tests, of the model's SHAPE: six layers
    ``conv conv attn conv conv conv``, two dense, 8 experts top-2, 4 query
    heads of 16 on 2 K/V heads."""
    defaults = dict(
        vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, n_layer=6, n_head=4, n_kv_head=2,
        layer_types=(CONV, CONV, FULL, CONV, CONV, CONV), n_dense_layers=2,
        n_experts=8, n_experts_per_tok=2, max_seq_len=256)
    defaults.update(kw)
    return Lfm2MoeConfig(**defaults)


# --- parameters ---------------------------------------------------------------
# One holder module a RUN of layers (``Lfm2MoeConfig.runs``), every leaf with
# a leading layer axis: the forward below is plain functions of the leaves,
# so a ``lax.scan`` can run a run's layers through one traced body without
# lifted transforms. The third field of a leaf: how it is drawn.

def _leaves(cfg: Lfm2MoeConfig, kind: str, routed: bool
            ) -> list[tuple[str, tuple, str]]:
    d, hd = cfg.hidden_size, cfg.head_dim
    q, kv = cfg.n_head * hd, cfg.n_kv_head * hd
    out = [("op_norm", (d,), "one"), ("ffn_norm", (d,), "one")]
    if kind == CONV:
        out += [("w_in", (d, 3 * d), "normal"),
                ("conv_w", (cfg.conv_L_cache, d), "conv_taps"),
                ("w_out", (d, d), "normal")]
    else:
        out += [("w_q", (d, q), "normal"), ("w_k", (d, kv), "normal"),
                ("w_v", (d, kv), "normal"), ("w_o", (q, d), "normal"),
                ("q_norm", (hd,), "one"), ("k_norm", (hd,), "one")]
    if routed:
        e, w = cfg.n_experts, cfg.moe_intermediate_size
        out += [("router", (d, e), "normal"),
                ("expert_bias", (e,), "expert_bias"),
                ("w_gate", (e, d, w), "normal"), ("w_up", (e, d, w), "normal"),
                ("w_down", (e, w, d), "normal")]
    else:
        i = cfg.intermediate_size
        out += [("w1", (d, i), "normal"), ("w3", (d, i), "normal"),
                ("w2", (i, d), "normal")]
    return out


# the seeded selection bias: a tenth of the scores' range, so that it
# reorders the experts as a trained one does AND a router that adds it to
# the weights is 5-10% off at every position (at N(0, 0.01) that fault
# moved a weight by 1%, under a bf16 forward's rounding: PERF.md, PR 52)
EXPERT_BIAS_STD = 0.1


def _draw(kind: str, key, shape, dtype, std: float = 0.02):
    if kind == "one":
        return jnp.ones(shape, dtype)
    if kind == "conv_taps":     # a depthwise kernel's default: U(-1, 1) / sqrt(taps)
        return (jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)
                * shape[-2] ** -0.5).astype(dtype)
    if kind == "expert_bias":   # float32 whatever the weights' dtype
        return EXPERT_BIAS_STD * jax.random.normal(key, shape, jnp.float32)
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


class _Leaves(nn.Module):
    spec: tuple
    stack: int

    @nn.compact
    def __call__(self):
        return {name: self.param(
            name, lambda key, shape=(self.stack,) + shape, kind=kind: _draw(
                kind, key, shape, jnp.float32))
            for name, shape, kind in self.spec}


# --- the forward, plain functions of the leaves ------------------------------

def _mm(x, w, compute):
    return jnp.dot(x.astype(compute), w.astype(compute),
                   preferred_element_type=compute)


def _rms_norm(x, scale, eps):
    h = x.astype(jnp.float32)
    h = h * jax.lax.rsqrt(jnp.mean(jnp.square(h), axis=-1, keepdims=True)
                          + eps)
    return (h * scale.astype(jnp.float32)).astype(x.dtype)


def _rows(b, cache, valid, l):
    """``(start (B,), valid (B,) or None)`` of a cached call."""
    start = jnp.broadcast_to(jnp.asarray(cache["index"], jnp.int32), (b,))
    if valid is not None:
        valid = jnp.minimum(valid.astype(jnp.int32), l)
    return start, valid


def conv_mixer(cfg, p, h, cache, valid):
    """``(out, cache)``; ``cache``: None or the layer's entry, ``valid``
    (B,) or None: how many of the call's positions are real, a row."""
    compute = jnp.dtype(cfg.compute_dtype)
    b, l, d = h.shape
    taps = cfg.conv_L_cache
    with jax.named_scope(SHORT_CONV_SCOPE):
        gate_b, gate_c, u = jnp.split(_mm(h, p["w_in"], compute), 3, axis=-1)
        g = gate_b * u
        if cache is None:
            tail = jnp.zeros((b, taps - 1, d), g.dtype)
        else:
            start, valid = _rows(b, cache, valid, l)
            # a sequence that starts here starts from nothing, whatever the
            # slot's last tenant left (a row that is not live keeps what it
            # holds, wherever its index points)
            fresh = start == 0
            if valid is not None:
                fresh &= valid > 0
            tail = jnp.where(fresh[:, None, None], 0,
                             cache["conv"]).astype(g.dtype)
        ext = jnp.concatenate([tail, g], axis=1)            # (B, 2 + L, d)
        w = p["conv_w"].astype(jnp.float32)
        c = sum(w[j] * ext[:, j:j + l].astype(jnp.float32)
                for j in range(taps))
        gated = gate_c * c.astype(compute)
    out = _mm(gated, p["w_out"], compute)
    if cache is not None:
        # the last two REAL rows of [tail ‖ g]
        at = jnp.full((b,), l, jnp.int32) if valid is None else valid
        new_tail = jax.vmap(lambda e, i: jax.lax.dynamic_slice_in_dim(
            e, i, taps - 1, axis=0))(ext, at)
        cache = dict(cache, conv=new_tail.astype(cache["conv"].dtype),
                     index=cache["index"] + l)
    return out, cache


def attention_mixer(cfg, p, h, cache, pages):
    """``(out, cache)``; ``pages``: the decode program's one
    ``swa.paged_rows`` (every attention layer reads the same rows)."""
    compute = jnp.dtype(cfg.compute_dtype)
    b, l, _ = h.shape
    nh, hk, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    q = _mm(h, p["w_q"], compute).reshape(b, l, nh, hd)
    k = _mm(h, p["w_k"], compute).reshape(b, l, hk, hd)
    v = _mm(h, p["w_v"], compute).reshape(b, l, hk, hd)
    q = _rms_norm(q, p["q_norm"], cfg.norm_eps)
    k = _rms_norm(k, p["k_norm"], cfg.norm_eps)
    start = jnp.zeros((b,), jnp.int32)
    if cache is not None:
        start = jnp.broadcast_to(jnp.asarray(cache["index"], jnp.int32), (b,))
    positions = start[:, None] + jnp.arange(l)[None, :]
    cos, sin = rope_ops.precompute_cos_sin(hd, cfg.max_seq_len,
                                           cfg.rope_theta)
    q, k = (rope_ops.apply_rotary_emb(
        t, cos, sin, positions=positions, interleaved=False).astype(compute)
        for t in (q, k))
    scale = hd ** -0.5
    if cache is None:
        out = swa.prefill_attention(q, k, v, start, scale=scale)
    elif PAGES_KEY in cache:
        # over the pool's PAGES (a decode program, l == 1): the new row
        # goes into its page, and the query walks the row's pages to its
        # true length; a row that is not live writes into the trash page
        # and reads nothing
        pool_k, pool_v = (
            layers.page_row_write(cache[key], pages["table"], start,
                                  cache[VALID_KEY], new.reshape(b, -1))
            for key, new in (("k", k), ("v", v)))
        out = swa.paged_decode_attention(
            q, pool_k, pool_v, scale=scale, kv_heads=hk, v_dim=hd, **pages)
        cache = dict(cache, k=pool_k, v=pool_v, index=cache["index"] + l)
    else:
        # FLAT rows (init_cache): one vector of Hk * head_dim, whole lanes
        k_all = layers.cache_update(cache["k"], k.reshape(b, l, hk * hd),
                                    cache["index"])
        v_all = layers.cache_update(cache["v"], v.reshape(b, l, hk * hd),
                                    cache["index"])
        if l == 1:
            out = swa.decode_attention(q, k_all.astype(compute),
                                       v_all.astype(compute), start,
                                       scale=scale)
        else:
            rows = k_all.shape[1]
            out = swa.prefill_attention(
                q, k_all.astype(compute).reshape(b, rows, hk, hd),
                v_all.astype(compute).reshape(b, rows, hk, hd), start,
                scale=scale)
        cache = dict(cache, k=k_all, v=v_all, index=cache["index"] + l)
    return _mm(out.reshape(b, l, nh * hd), p["w_o"], compute), cache


def dense_ffn(cfg, p, h):
    compute = jnp.dtype(cfg.compute_dtype)
    return _mm(nn.silu(_mm(h, p["w1"], compute)) * _mm(h, p["w3"], compute),
               p["w2"], compute)


def choose_experts(cfg, router, bias, flat):
    """``(ids (N, k), weights (N, k) float32)`` of the published router."""
    kw = dict(norm_topk=cfg.norm_topk_prob, scoring="sigmoid",
              bias=bias if cfg.use_expert_bias else None,
              scale=cfg.routed_scaling_factor, norm_eps=ROUTE_NORM_EPS)
    return route(flat, router, cfg.n_experts_per_tok, **kw)


def routed_ffn(cfg, p, h, experts, offset):
    """The routed layer whose leaves are ``p``. ``experts``: the RUN's
    stacked ``(w_gate, w_up, w_down)``, each ``(layers * experts, ·, ·)``,
    and ``offset``: this layer's first row of them. Returns ``(y, ids (N,
    k), assignments an expert (experts,))``."""
    compute = jnp.dtype(cfg.compute_dtype)
    flat = h.reshape(-1, h.shape[-1])
    ids, weights = choose_experts(cfg, p["router"], p["expert_bias"], flat)
    y = grouped_expert_ffn(flat.astype(compute), ids + offset, weights,
                           *(w.astype(compute) for w in experts))
    return (y.reshape(h.shape).astype(h.dtype), ids,
            held_counts(ids, cfg.held))


def _counted(cache, ids, counts, valid, b, l):
    """``cache`` with the routed layer's statistics filled in (where the
    serving program asked: ``serve/step_stats.py``): the experts of each
    row's last REAL position (a padded chunk's last row is padding)."""
    if cache is None or LOAD_KEY not in cache:
        return cache
    at = (jnp.full((b,), l - 1) if valid is None
          else jnp.clip(valid.astype(jnp.int32), 1, l) - 1)
    chosen = ids.reshape(b, l, ids.shape[-1])
    return dict(cache, **{
        LOAD_KEY: cache[LOAD_KEY] + jnp.stack([
            jnp.int32(1), jnp.sum(counts), jnp.sum(counts > 0),
            jnp.max(counts)]),
        ROUTE_KEY: jnp.take_along_axis(chosen, at[:, None, None],
                                       axis=1)[:, 0]})


def layer(cfg, kind, routed, p, x, cache, *, valid, pages, experts=None,
          offset=0):
    """One layer whose leaves are ``p``: ``(x, cache)``."""
    h = _rms_norm(x, p["op_norm"], cfg.norm_eps)
    if kind == CONV:
        mixed, cache = conv_mixer(cfg, p, h, cache, valid)
    else:
        mixed, cache = attention_mixer(cfg, p, h, cache, pages)
    x = x + mixed.astype(x.dtype)
    h = _rms_norm(x, p["ffn_norm"], cfg.norm_eps)
    if not routed:
        return x + dense_ffn(cfg, p, h).astype(x.dtype), cache
    y, ids, counts = routed_ffn(cfg, p, h, experts, offset)
    return x + y, _counted(cache, ids, counts, valid, *x.shape[:2])


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def run_layers(cfg, run, P, x, caches, *, valid, pages):
    """A run's layers over ``x``; ``caches``: None or the run's entries, in
    layer order. A run of conv layers longer than one is ONE traced body."""
    kind, routed, _, count = run
    cached = caches is not None
    experts = None
    if routed:
        # the whole stack, as it is stored: (layers * experts, ·, ·)
        experts = tuple(P[name].reshape((-1,) + P[name].shape[2:])
                        for name in EXPERT_LEAVES)
    small = {k: v for k, v in P.items() if k not in EXPERT_LEAVES}
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)     # noqa: E731
    if kind != CONV or count == 1:
        out = []
        for i in range(count):
            x, c = layer(cfg, kind, routed, at(small, i), x,
                         caches[i] if cached else None, valid=valid,
                         pages=pages, experts=experts,
                         offset=i * cfg.n_experts)
            out.append(c)
        return x, out if cached else None

    # what the body carries a layer: the tail and, where the program
    # counts, the routed layer's statistics; the rest of an entry (index,
    # valid) is the same for every layer of the run
    moving = [k for k in ("conv", LOAD_KEY, ROUTE_KEY)
              if cached and k in caches[0]]
    bufs = {k: jnp.stack([c[k] for c in caches]) for k in moving}

    def body(x, xs):
        p, mine, i = xs
        c = dict(caches[0], **mine) if cached else None
        x, c = layer(cfg, kind, routed, p, x, c, valid=valid, pages=pages,
                     experts=experts, offset=i * cfg.n_experts)
        return x, {k: c[k] for k in moving}

    x, bufs = jax.lax.scan(body, x, (small, bufs, jnp.arange(count)))
    if not cached:
        return x, None
    l = x.shape[1]
    return x, [dict(c, index=c["index"] + l,
                    **{k: bufs[k][i] for k in moving})
               for i, c in enumerate(caches)]


class Lfm2Moe(nn.Module):
    """``model(idx) -> logits``; with ``cache`` (the engines' per-layer
    list) returns ``(logits, cache)``."""

    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, idx: jax.Array, *, deterministic: bool = True,
                 cache: list[Cache] | None = None,
                 return_hidden: bool = False, head_only: bool = False):
        # ``return_hidden`` / ``head_only``: the forward in two halves
        # (see models/qwen3.py)
        cfg = self.cfg
        compute = jnp.dtype(cfg.compute_dtype)
        embed = self.param(
            "tok_embed", lambda key: _draw(
                "normal", key, (cfg.vocab_size, cfg.hidden_size),
                jnp.float32))

        def head(x):        # tied
            return jax.lax.dot_general(
                x.astype(compute), embed.astype(compute),
                (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        if head_only:
            return head(idx)
        P = {first: _Leaves(tuple(_leaves(cfg, kind, routed)), count,
                            name=f"run_{first}")()
             for kind, routed, first, count in cfg.runs}
        ln_f = self.param("ln_f", nn.initializers.ones, (cfg.hidden_size,))
        x = jnp.take(embed, idx, axis=0).astype(compute)
        # how many of the call's positions are real, a row: the serving
        # programs tell the layers that own their writes (the tails); the
        # routed layers report their experts at the last real one
        valid = next((c[VALID_KEY] for c in cache or () if VALID_KEY in c),
                     None)
        # a decode program whose attention layers read their pages in
        # place: ONE walk of the rows' pages (they all read the same rows)
        pages = next((swa.paged_rows(c[PAGES_KEY], c["index"], c[VALID_KEY],
                                     c["v"].shape[1])
                      for c in cache or () if PAGES_KEY in c), None)
        new_caches = [] if cache is not None else None
        for run in cfg.runs:
            _, _, first, count = run
            x, out = run_layers(
                cfg, run, P[first], x,
                None if cache is None else cache[first:first + count],
                valid=valid, pages=pages)
            if cache is not None:
                new_caches += out
        x = _rms_norm(x, ln_f, cfg.norm_eps)
        if return_hidden:
            return (x, new_caches) if cache is not None else x
        logits = head(x)
        if cache is not None:
            return logits, new_caches
        return logits

    # -- convenience API shared by every in-tree model family -----------------
    @property
    def config(self) -> Lfm2MoeConfig:
        return self.cfg

    def init_params(self, rng, example_len: int = 8):
        return self.init(rng, jnp.ones((1, example_len), jnp.int32))["params"]

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        """Per layer: an attention layer's FLAT rows that follow
        ``max_len``, ``Hk * head_dim`` wide; a conv layer's two-row tail in
        the compute dtype (module docstring)."""
        cfg = self.cfg
        kv = cfg.n_kv_head * cfg.head_dim
        index = jnp.zeros((), jnp.int32)
        return [
            {"index": index,
             "conv": jnp.zeros((batch, cfg.conv_L_cache - 1, cfg.hidden_size),
                               jnp.dtype(cfg.compute_dtype))}
            if kind == CONV else
            {"index": index, "k": jnp.zeros((batch, max_len, kv), dtype),
             "v": jnp.zeros((batch, max_len, kv), dtype)}
            for kind in cfg.layer_types]

    @property
    def cache_slot_axis(self) -> int:
        return 0

    #: a decode program hands the attention layers the pool's pages as they
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


def random_params(cfg: Lfm2MoeConfig, seed: int, dtype=jnp.bfloat16,
                  std: float = 0.02) -> dict:
    """Seeded weights made ON THE DEVICE in ``dtype``, a stacked leaf one
    layer at a time: N(0, ``std``), norm scales 1, ``expert_bias`` N(0,
    ``EXPERT_BIAS_STD``) float32, and the convolution's taps U(-1, 1) / sqrt(taps), a
    depthwise kernel's own default: taps of N(0, 0.02) leave a mixer's
    output at 0.02 beside an MLP's 0.9 on a unit-norm input, under a bf16
    forward's rounding, where no logit could see three layers of four go
    wrong. Every layer and every expert is a distinct draw."""
    root = jax.random.PRNGKey(int(seed) % (2 ** 31))
    # one program a LAYER's leaf, stacked outside it: a program that drew
    # and stacked a run's three 400 MB expert leaves at once did not come
    # out of the chip's compiler in ten minutes (PERF.md section 6, PR 52)
    draw = jax.jit(_draw, static_argnums=(0, 2, 3, 4))
    out = {"tok_embed": draw("normal", jax.random.fold_in(root, 0),
                             (cfg.vocab_size, cfg.hidden_size), dtype, std),
           "ln_f": jnp.ones((cfg.hidden_size,), dtype)}
    for kind, routed, first, count in cfg.runs:
        key = jax.random.fold_in(root, first + 1)
        out[f"run_{first}"] = {
            leaf: jnp.stack([
                draw(how, jax.random.fold_in(jax.random.fold_in(key, i), n),
                     shape, dtype, std) for n in range(count)])
            for i, (leaf, shape, how) in enumerate(
                _leaves(cfg, kind, routed))}
    return out
