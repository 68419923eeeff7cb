"""Phi-4-mini-flash-reasoning (``model_type`` ``phi4flash``,
huggingface.co/microsoft/Phi-4-mini-flash-reasoning; SambaY,
arXiv:2507.06607): a SELF-DECODER of Mamba layers and window attention
that ends in one full-attention layer, and a CROSS-DECODER that only
READS: gated memory units fed the last Mamba layer's scan output, and
cross-attention over the full layer's keys and values (YOCO,
arXiv:2405.05254). Every attention is differential (arXiv:2410.05258).

**Layer kinds by index** ``n`` (0-based, ``half = n_layer / 2``): ``n``
even, ``n <= half`` -> MAMBA (layer ``half`` also exports its scan output
``m``); ``n`` odd, ``n < half`` -> WINDOW attention (``sliding_window``
positions, self included); ``n = half + 1`` -> FULL attention, whose keys
and values are the cross-decoder's cache; ``n`` even, ``n > half`` -> GMU;
``n`` odd, ``n > half + 1`` -> CROSS attention. Layers ``0 .. half + 1``
are the self-decoder.

**Block**: ``x <- x + mixer(LN_a(x))``; ``x <- x + MLP(LN_b(x))``,
LayerNorm with scale and bias; embedding unscaled, NO position in any
layer; a final LayerNorm; ``logits = x E^T`` (tied). **MLP**: ``[g ‖ u] =
h W1``; ``y = (u * silu(g)) W2``.

**Mamba** (arXiv:2312.00752): ``[xs ‖ z] = h W_in``; ``xc_t = silu(b_c +
sum_i w_c[i] * xs_{t-3+i})`` (depthwise, causal); ``[delta ‖ B_t ‖ C_t] =
xc_t W_x``; ``dt_t = softplus(delta W_dt + b_dt)``; ``A = -exp(A_log)``;
``S_t = exp(dt_t A) * S_{t-1} + (dt_t * xc_t) B_t``; ``y_t = S_t C_t + D *
xc_t``; ``out_t = (y_t * silu(z_t)) W_out``; layer ``half``: ``m_t = y_t``.
**GMU**: ``out_t = (m_t * silu(h_t W_1)) W_2``, ``m_t`` of the SAME token.
**Differential attention**: ``q`` -> ``n_head`` heads, ``k``, ``v`` ->
``n_kv_head`` heads of ``head_dim`` (a cross layer has ``W_q`` only and
takes the full layer's ``k``, ``v``). Pair ``p``: ``q1 = `` head ``2p``,
``q2 = `` head ``2p + 1``; K/V pair ``j``: ``k1 = `` head ``2j``, ``k2 = ``
head ``2j + 1``, ``v_j = [v_{2j} ‖ v_{2j+1}]``; pair ``p`` reads K/V pair
``p // group``. ``a_i = softmax(q_i k_i^T / sqrt(head_dim) + mask) v``;
``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init``, ``lam_init = 0.8 -
0.6 exp(-0.3 n)``; ``o_p = RMSNorm(a_1 - lam a_2) * (1 - lam_init)``;
``out = [o_0 ‖ ...] W_o + b_o``.

**A forward with a cache answers for ONE position a row**: the
cross-decoder only reads (the full layer's rows, ``m`` of the same token),
so a prompt's positions other than its last need the self-decoder alone:
prefill linear in the prompt, the architecture's published point. A cached
call over ``L > 1`` positions runs layers ``half + 2 ..``, the final norm
and the head at each row's last REAL position only (``(B, 1, vocab)``
logits; ``return_hidden`` repeats that state over ``L``), and, told by the
serving programs under :data:`layers.FINISH_KEY` that no row's prompt ends
in this call, not at all. A call without a cache is the plain forward at
every position.

**The layers of a kind are STACKED, weights and cache alike**, and run
through ONE traced body a kind (``lax.scan``): the ``half / 2`` (Mamba,
window) pairs, then the last Mamba layer and the full layer, then the (GMU,
cross) pairs: six layer bodies a program where unrolled layers would be 32
(a fifth of the tracing, lowering and loading of every serving program:
PERF.md, PR 47). **The cache is three entries** (``serve/paged_kv.py`` reads
their kinds off the template): the Mamba layers' STATE ``{"ssm": (B,
layers, N, d_inner) float32, "conv": (B, layers, 3, d_inner)}``, REPLACED
at every position and never appended (states-major:
``ops/selective_scan.py``); the window layers' RINGS ``{"k1", "k2": (B, R,
layers, pairs, head_dim), "v": (B, R, layers, pairs, 2 head_dim)}``, ``R =
min(max_len, window)``; the full layer's FLAT rows that follow
``max_len``, ``{"k1", "k2": (B, max_len, pairs * head_dim), "v": (B,
max_len, pairs * 2 head_dim)}``. A GMU or cross layer holds nothing. The
two keys of a pair are stored apart, so each half's softmax reads its own
buffer whole, and the values once for both. **In a decode program the full
layer's entry is the page pool itself** (the model declares
``reads_pages``): the three buffers as ``serve/paged_kv.py`` stores them by
pages and each row's block table under ``layers.PAGES_KEY``; the layer
writes its new row into its page and all eight readers walk the pages
where they lie, to each row's true length
(``swa.paged_paired_decode_attention``). A chunk row (``L > 1``) keeps the
gathered view. The state's dtype is the
configuration's (float32), not the cache's. State discipline: a row whose
``valid`` is 0 keeps ring, tail and state bit for bit (wherever its index
points); padding does not advance them; a live call that starts at
position 0 starts from zeros whatever the buffers hold (a ring is masked by
position, a recurrence is not).

**Assumed** (the configuration has no key for them): Mamba-1 with
``d_inner = 2 hidden``, ``d_state`` 16, ``d_conv`` 4, ``dt_rank =
ceil(hidden / 16)``; interleaved pairing of heads; the window counts the
query itself; biases on the attention projections, the convolution and
``dt``, none elsewhere; a float32 state. **Storage**: ``w_qkv`` is ``[q ‖ k
‖ v]`` by columns, ``a_log`` and ``conv_w`` are states-major / taps-major
(``(N, d_inner)``, ``(4, d_inner)``); a stack's leaves carry a leading
layer axis (``_stacks``; the reference's ``layer_params`` names layer
``n``'s stack and index). **Refused by name**
(``from_hf_config``): untied embeddings, an MLP or head bias, another
activation, ``mb_per_layer`` other than 2, an odd layer count.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from llm_in_practise_tpu.models import layers
from llm_in_practise_tpu.ops import selective_scan as ssm
from llm_in_practise_tpu.ops import swa_attention as swa

Cache = dict[str, Any]
VALID_KEY, FINISH_KEY = layers.VALID_KEY, layers.FINISH_KEY
PAGES_KEY = layers.PAGES_KEY
MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"
SHARED_DECODE_SCOPE = "shared_kv_decode_attention"
GMU_SCOPE = "gmu"


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    n_layer: int
    n_head: int
    n_kv_head: int
    window: int
    d_state: int = 16
    d_conv: int = 4
    layer_norm_eps: float = 1e-5
    max_seq_len: int = 4096
    compute_dtype: str = "bfloat16"
    # the recurrent state's dtype (the check's control sets bfloat16)
    ssm_state_dtype: str = "float32"

    def __post_init__(self):
        if self.n_layer % 2 or self.n_layer < 6:
            raise ValueError("phi4flash: an even number of layers, >= 6")
        if self.n_head % 2 or self.n_kv_head % 2:
            raise ValueError("differential attention pairs its heads")
        if (self.n_head // 2) % (self.n_kv_head // 2):
            raise ValueError("query pairs must divide into K/V pairs")

    def replace(self, **kw) -> "Phi4FlashConfig":
        return dataclasses.replace(self, **kw)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.n_head

    @property
    def d_inner(self) -> int:
        return 2 * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return math.ceil(self.hidden_size / 16)

    @property
    def half(self) -> int:
        return self.n_layer // 2

    def kind(self, n: int) -> str:
        if n % 2 == 0:
            return MAMBA if n <= self.half else GMU
        if n < self.half:
            return WINDOW
        return FULL if n == self.half + 1 else CROSS

    @property
    def kinds(self) -> tuple[str, ...]:
        return tuple(self.kind(n) for n in range(self.n_layer))

    def lambda_init(self, n):
        """``n``: the layer's index, a number or a traced scalar."""
        return 0.8 - 0.6 * jnp.exp(-0.3 * n)

    @classmethod
    def from_hf_config(cls, hf: dict, **overrides) -> "Phi4FlashConfig":
        """Build from the model's ``config.json`` keys. What this file
        does not implement is refused by name, not ignored."""
        def refuse(key, got, want):
            raise ValueError(f"phi4flash: {key}={got!r} is not implemented "
                             f"(only {want})")

        if not hf.get("tie_word_embeddings", True):
            refuse("tie_word_embeddings", False, "true")
        if int(hf.get("mb_per_layer", 2)) != 2:
            refuse("mb_per_layer", hf["mb_per_layer"], "2")
        if hf.get("hidden_act", "silu") not in ("silu", "swiglu"):
            refuse("hidden_act", hf["hidden_act"], "'silu'")
        for key in ("mlp_bias", "lm_head_bias"):
            if hf.get(key):
                refuse(key, hf[key], "false")
        if hf.get("rope_scaling") is not None:
            refuse("rope_scaling", hf["rope_scaling"], "null (no position)")
        heads = hf["num_attention_heads"]
        if hf.get("head_dim", hf["hidden_size"] // heads) * heads != \
                hf["hidden_size"]:
            refuse("head_dim", hf["head_dim"], "hidden_size / heads")
        # read and not applied: the dropout rates (inference), the token
        # ids, use_cache, initializer_range (seeded weights carry their own)
        cfg = cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            n_layer=hf["num_hidden_layers"],
            n_head=heads,
            n_kv_head=hf["num_key_value_heads"],
            window=int(hf["sliding_window"]),
            layer_norm_eps=float(hf.get("layer_norm_eps", 1e-5)),
            max_seq_len=int(hf.get("max_position_embeddings", 4096)),
        )
        return cfg.replace(**overrides)

    def param_count(self) -> int:
        shapes = jax.eval_shape(
            lambda: Phi4Flash(self).init(jax.random.PRNGKey(0),
                                         jnp.ones((1, 8), jnp.int32)))
        return sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))


def phi4flash_config(vocab_size: int = 512, **kw) -> Phi4FlashConfig:
    """Tiny-default constructor for tests: 8 layers = [M, W, M, W, M, F, G,
    X] by the index rule, 4 query / 2 K/V heads of 16, a window of 8."""
    defaults = dict(vocab_size=vocab_size, hidden_size=64,
                    intermediate_size=128, n_layer=8, n_head=4, n_kv_head=2,
                    window=8, max_seq_len=256)
    defaults.update(kw)
    return Phi4FlashConfig(**defaults)


# --- parameters ---------------------------------------------------------------
# One holder module a STACK of layers of one kind (``_stacks``): the forward
# below is plain functions of the leaves, so a ``lax.scan`` can run the
# layers of a stack through ONE traced body (a program of 6 layer bodies,
# not 32: a fifth of the tracing, lowering and loading) and a ``lax.cond``
# can skip the cross-decoder, without lifted transforms. ``kind`` of a
# leaf: how ``random_params`` draws it.

def _leaves(cfg: Phi4FlashConfig, kind: str) -> list[tuple[str, tuple, str]]:
    d, di, n = cfg.hidden_size, cfg.d_inner, cfg.d_state
    hd = cfg.head_dim
    q, kv = cfg.n_head * hd, cfg.n_kv_head * hd
    out = [("ln_a_scale", (d,), "one"), ("ln_a_bias", (d,), "zero"),
           ("ln_b_scale", (d,), "one"), ("ln_b_bias", (d,), "zero"),
           ("mlp_w1", (d, 2 * cfg.intermediate_size), "normal"),
           ("mlp_w2", (cfg.intermediate_size, d), "normal")]
    if kind == MAMBA:
        out += [("w_in", (d, 2 * di), "normal"),
                ("conv_w", (cfg.d_conv, di), "conv_taps"),
                ("conv_b", (di,), "zero"),
                ("w_x", (di, cfg.dt_rank + 2 * n), "normal"),
                ("w_dt", (cfg.dt_rank, di), "normal"),
                ("b_dt", (di,), "dt_bias"), ("a_log", (n, di), "a_log"),
                ("d_skip", (di,), "one"), ("w_out", (di, d), "normal")]
    elif kind == GMU:
        out += [("w_1", (d, di), "normal"), ("w_2", (di, d), "normal")]
    else:
        wide = q if kind == CROSS else q + 2 * kv
        out += [("w_qkv", (d, wide), "normal"), ("b_qkv", (wide,), "zero"),
                ("w_o", (q, d), "normal"), ("b_o", (d,), "zero"),
                ("subln", (2 * hd,), "one")]
        out += [(f"lambda_{s}", (hd,), "lambda")
                for s in ("q1", "k1", "q2", "k2")]
    return out


def _draw(kind: str, key, shape, dtype, std: float = 0.02):
    if kind == "one":
        return jnp.ones(shape, dtype)
    if kind == "zero":
        return jnp.zeros(shape, dtype)
    if kind == "a_log":     # log(1 .. N), every channel (and layer)
        n = jnp.arange(1, shape[-2] + 1, dtype=jnp.float32)
        return jnp.broadcast_to(jnp.log(n)[:, None], shape).astype(dtype)
    if kind == "conv_taps":     # Mamba's own: U(-1, 1) / sqrt(taps)
        return (jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)
                * shape[-2] ** -0.5).astype(dtype)
    if kind == "dt_bias":   # softplus(b) log-uniform in [1e-3, 1e-1]
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    sigma = 0.1 if kind == "lambda" else std
    return (sigma * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


class _Leaves(nn.Module):
    spec: tuple
    stack: int = 0      # how many layers' leaves are stacked (0: one)

    @nn.compact
    def __call__(self):
        lead = (self.stack,) if self.stack else ()
        return {name: self.param(
            name, lambda key, shape=lead + shape, kind=kind: _draw(
                kind, key, shape, jnp.float32))
            for name, shape, kind in self.spec}


# --- the forward, plain functions of the leaves ------------------------------

def _mm(x, w, compute, out=None):
    return jnp.dot(x.astype(compute), w.astype(compute),
                   preferred_element_type=out or compute)


def _layer_norm(x, scale, bias, eps):
    h = x.astype(jnp.float32)
    mu = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(h - mu), axis=-1, keepdims=True)
    h = (h - mu) * jax.lax.rsqrt(var + eps)
    return (h * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _mlp(p, h, compute):
    g, u = jnp.split(_mm(h, p["mlp_w1"], compute), 2, axis=-1)
    return _mm(u * nn.silu(g), p["mlp_w2"], compute)


def _rows(b, cache, l):
    """``(start (B,), valid (B,) or None)`` of a cached call."""
    start = jnp.broadcast_to(jnp.asarray(cache["index"], jnp.int32), (b,))
    valid = cache.get(VALID_KEY)
    if valid is not None:
        valid = jnp.minimum(valid.astype(jnp.int32), l)
    return start, valid


def mamba_mixer(cfg, p, h, cache):
    """``(out, y before the gate, cache)``."""
    compute = jnp.dtype(cfg.compute_dtype)
    b, l, _ = h.shape
    di, n, taps = cfg.d_inner, cfg.d_state, cfg.d_conv
    xs, z = jnp.split(_mm(h, p["w_in"], compute), 2, axis=-1)
    valid = None
    if cache is None:
        tail = jnp.zeros((b, taps - 1, di), xs.dtype)
        s0 = jnp.zeros((b, n, di), jnp.float32)
    else:
        start, valid = _rows(b, cache, l)
        # a sequence that starts here starts from nothing, whatever the
        # slot's last tenant left (a row that is not live keeps what it
        # holds, wherever its index points)
        fresh = start == 0
        if valid is not None:
            fresh &= valid > 0
        fresh = fresh[:, None, None]
        tail = jnp.where(fresh, 0, cache["conv"]).astype(xs.dtype)
        s0 = jnp.where(fresh, 0, cache["ssm"]).astype(jnp.float32)
    ext = jnp.concatenate([tail, xs], axis=1)               # (B, 3 + L, di)
    w = p["conv_w"].astype(jnp.float32)
    xc = p["conv_b"].astype(jnp.float32) + sum(
        w[i] * ext[:, i:i + l].astype(jnp.float32) for i in range(taps))
    xc = nn.silu(xc)
    dbc = _mm(xc, p["w_x"], compute, jnp.float32)
    delta, bmat, cmat = jnp.split(dbc, [cfg.dt_rank, cfg.dt_rank + n],
                                  axis=-1)
    dt = jax.nn.softplus(_mm(delta, p["w_dt"], compute, jnp.float32)
                         + p["b_dt"].astype(jnp.float32))
    dt = ssm.mask_steps(dt, valid)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    y, s1 = ssm.selective_scan(xc, dt, bmat, cmat, a,
                               p["d_skip"].astype(jnp.float32), s0)
    y = y.astype(compute)
    out = _mm(y * nn.silu(z), p["w_out"], compute)
    if cache is not None:
        # the last three REAL rows of [tail ‖ xs]
        at = jnp.full((b,), l, jnp.int32) if valid is None else valid
        new_tail = jax.vmap(lambda e, i: jax.lax.dynamic_slice_in_dim(
            e, i, taps - 1, axis=0))(ext, at)
        cache = dict(cache, conv=new_tail.astype(cache["conv"].dtype),
                     ssm=s1.astype(cache["ssm"].dtype),
                     index=cache["index"] + l)
    return out, y, cache


def gmu_mixer(cfg, p, h, m):
    compute = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope(GMU_SCOPE):
        gate = nn.silu(_mm(h, p["w_1"], compute))
        return _mm(m.astype(compute) * gate, p["w_2"], compute)


def _halves(t, pairs, width):
    """(B, L, 2 * pairs * width) interleaved heads -> the even heads and
    the odd heads, each (B, L, pairs, width)."""
    b, l, _ = t.shape
    t = t.reshape(b, l, pairs, 2, width)
    return t[:, :, :, 0], t[:, :, :, 1]


def _differential(cfg, p, layer, a1, a2):
    """``RMSNorm(a1 - lam a2) * (1 - lam_init)`` through ``W_o``; ``a1``,
    ``a2`` (B, L, pairs, 2 head_dim)."""
    compute = jnp.dtype(cfg.compute_dtype)
    f32 = jnp.float32
    lam_init = cfg.lambda_init(layer)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"].astype(f32)
                           * p["lambda_k1"].astype(f32)))
           - jnp.exp(jnp.sum(p["lambda_q2"].astype(f32)
                             * p["lambda_k2"].astype(f32))) + lam_init)
    o = a1.astype(f32) - lam * a2.astype(f32)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + cfg.layer_norm_eps)
    o = o * p["subln"].astype(f32) * (1.0 - lam_init)
    b, l = o.shape[:2]
    return (_mm(o.reshape(b, l, -1), p["w_o"], compute)
            + p["b_o"].astype(compute))


def attention_mixer(cfg, p, h, cache, layer, kind, shared=None):
    """One differential-attention layer. ``shared``: what the FULL layer
    hands the cross layers, ``(k1, k2, v)``: the keys and values a query
    may read, flat rows of a cache view or ``(B, L, pairs, ·)`` of a call
    without a cache; or ``(k1, k2, v, pages)``: the pool's buffers as they
    are stored by pages and the keywords that name each row's
    (``swa.paged_paired_decode_attention``: block table, lengths, work
    list). Returns ``(out, cache, shared)``."""
    compute, f32 = jnp.dtype(cfg.compute_dtype), jnp.float32
    b, l, _ = h.shape
    hd = cfg.head_dim
    qp, kp = cfg.n_head // 2, cfg.n_kv_head // 2
    scale = hd ** -0.5
    qkv = _mm(h, p["w_qkv"], compute) + p["b_qkv"].astype(compute)
    q1, q2 = _halves(qkv[..., :cfg.n_head * hd], qp, hd)
    if kind != CROSS:
        kv = cfg.n_kv_head * hd
        k1, k2 = _halves(qkv[..., cfg.n_head * hd:cfg.n_head * hd + kv],
                         kp, hd)
        v = qkv[..., cfg.n_head * hd + kv:].reshape(b, l, kp, 2 * hd)
    window = cfg.window if kind == WINDOW else None
    if cache is None:
        if kind == CROSS:
            k1, k2, v = shared
        a1, a2 = (swa.prefill_attention(q, k, v, 0, scale=scale,
                                        window=window, out_dtype=f32)
                  for q, k in ((q1, k1), (q2, k2)))
        return (_differential(cfg, p, layer, a1, a2), None,
                (k1, k2, v))
    start, valid = _rows(b, cache, l)
    if kind == CROSS:
        # one query a row, at the position the full layer just wrote
        k1, k2, v, *pages = shared
        with jax.named_scope(SHARED_DECODE_SCOPE):
            if pages:
                a1, a2 = swa.paged_paired_decode_attention(
                    (q1, q2), (k1, k2), v, scale=scale, kv_heads=kp,
                    **pages[0])
            else:
                a1, a2 = swa.paired_decode_attention(
                    (q1, q2), (k1, k2), v, start, scale=scale)
        return _differential(cfg, p, layer, a1, a2), cache, shared
    if kind == WINDOW:
        live = jnp.full((b,), l, jnp.int32) if valid is None else valid
        rings = {key: swa.ring_write(cache[key], new, start, live)
                 for key, new in (("k1", k1), ("k2", k2), ("v", v))}
        if l == 1:
            a1, a2 = swa.paired_ring_decode_attention(
                (q1, q2), (rings["k1"], rings["k2"]), rings["v"], start,
                scale=scale, window=cfg.window)
        else:
            a1, a2 = (swa.prefill_attention(
                q, k, v, start, scale=scale, window=cfg.window,
                cached=(cache[key], cache["v"]), out_dtype=f32)
                for q, k, key in ((q1, k1, "k1"), (q2, k2, "k2")))
        cache = dict(cache, **rings, index=cache["index"] + l)
        return _differential(cfg, p, layer, a1, a2), cache, None
    if PAGES_KEY in cache:
        # the full layer over the pool's PAGES (a decode program, l == 1):
        # the new row goes into its page and every reader walks the
        # row's pages to its true length; a row that is not live (the
        # programs always say which: ``valid``) writes into the trash page
        # and reads nothing
        table = cache[PAGES_KEY]
        pool = {key: layers.page_row_write(cache[key], table, start, valid,
                                           new.reshape(b, -1))
                for key, new in (("k1", k1), ("k2", k2), ("v", v))}
        pages = swa.paged_rows(table, start, valid, pool["v"].shape[1])
        with jax.named_scope(SHARED_DECODE_SCOPE):
            a1, a2 = swa.paged_paired_decode_attention(
                (q1, q2), (pool["k1"], pool["k2"]), pool["v"], scale=scale,
                kv_heads=kp, **pages)
        cache = dict(cache, **pool, index=cache["index"] + l)
        return (_differential(cfg, p, layer, a1, a2), cache,
                (pool["k1"], pool["k2"], pool["v"], pages))
    # the full layer: FLAT rows, stored by pages, attended as they lie
    rows = {key: layers.cache_update(cache[key], new.reshape(b, l, -1),
                                     cache["index"])
            for key, new in (("k1", k1), ("k2", k2), ("v", v))}
    view = {key: buf.astype(compute) for key, buf in rows.items()}
    if l == 1:
        with jax.named_scope(SHARED_DECODE_SCOPE):
            a1, a2 = swa.paired_decode_attention(
                (q1, q2), (view["k1"], view["k2"]), view["v"], start,
                scale=scale)
    else:
        w = view["v"].shape[1]
        a1, a2 = (swa.prefill_attention(
            q, view[key].reshape(b, w, kp, hd),
            view["v"].reshape(b, w, kp, 2 * hd), start, scale=scale,
            out_dtype=f32)
            for q, key in ((q1, "k1"), (q2, "k2")))
    cache = dict(cache, **rows, index=cache["index"] + l)
    return (_differential(cfg, p, layer, a1, a2), cache,
            (view["k1"], view["k2"], view["v"]))


def _block(cfg, p, x, mixed):
    compute = jnp.dtype(cfg.compute_dtype)
    x = x + mixed.astype(x.dtype)
    h = _layer_norm(x, p["ln_b_scale"], p["ln_b_bias"], cfg.layer_norm_eps)
    return x + _mlp(p, h, compute).astype(x.dtype)


def _norm_a(cfg, p, x):
    return _layer_norm(x, p["ln_a_scale"], p["ln_a_bias"],
                       cfg.layer_norm_eps)


def _take(a, i, axis):
    return jax.lax.dynamic_index_in_dim(a, i, axis, keepdims=False)


def _put(a, new, i, axis):
    return jax.lax.dynamic_update_index_in_dim(a, new.astype(a.dtype), i,
                                               axis)


def self_decoder(cfg, P, x, cache):
    """Layers ``0 .. half + 1``: a ``lax.scan`` over the ``half / 2``
    (Mamba, window) pairs, one traced body for all of them, then the last
    Mamba layer and the full layer. ``cache``: None or the three entries
    of :meth:`Phi4Flash.init_cache`. Returns ``(x, m, shared, cache)``."""
    n_pairs = cfg.half // 2
    cached = cache is not None
    if cached:
        state, rings, full = cache
        l = x.shape[1]

    def pair(carry, xs):
        x, bufs = carry
        pm, pw, i = xs
        c = None
        if cached:
            c = dict(state, ssm=_take(bufs["ssm"], i, 1),
                     conv=_take(bufs["conv"], i, 1))
        out, _, c = mamba_mixer(cfg, pm, _norm_a(cfg, pm, x), c)
        x = _block(cfg, pm, x, out)
        cw = None
        if cached:
            cw = dict(rings, **{k: _take(bufs[k], i, 2)
                                for k in ("k1", "k2", "v")})
        out, cw, _ = attention_mixer(cfg, pw, _norm_a(cfg, pw, x), cw,
                                     2 * i + 1, WINDOW)
        x = _block(cfg, pw, x, out)
        if cached:
            bufs = dict(
                ssm=_put(bufs["ssm"], c["ssm"], i, 1),
                conv=_put(bufs["conv"], c["conv"], i, 1),
                **{k: _put(bufs[k], cw[k], i, 2) for k in ("k1", "k2", "v")})
        return (x, bufs), None

    bufs = ({} if not cached else
            {"ssm": state["ssm"], "conv": state["conv"],
             **{k: rings[k] for k in ("k1", "k2", "v")}})
    (x, bufs), _ = jax.lax.scan(
        pair, (x, bufs),
        (P["pair_mamba"], P["pair_window"], jnp.arange(n_pairs)))
    pm, c = P["mamba_last"], None
    if cached:
        c = dict(state, ssm=bufs["ssm"][:, n_pairs],
                 conv=bufs["conv"][:, n_pairs])
    out, m, c = mamba_mixer(cfg, pm, _norm_a(cfg, pm, x), c)
    x = _block(cfg, pm, x, out)
    pf = P["full"]
    out, full_c, shared = attention_mixer(
        cfg, pf, _norm_a(cfg, pf, x), full if cached else None,
        cfg.half + 1, FULL)
    x = _block(cfg, pf, x, out)
    if not cached:
        return x, m, shared, None
    state = dict(state, index=state["index"] + l,
                 ssm=bufs["ssm"].at[:, n_pairs].set(
                     c["ssm"].astype(bufs["ssm"].dtype)),
                 conv=bufs["conv"].at[:, n_pairs].set(
                     c["conv"].astype(bufs["conv"].dtype)))
    rings = dict(rings, index=rings["index"] + l,
                 **{k: bufs[k] for k in ("k1", "k2", "v")})
    return x, m, shared, [state, rings, full_c]


def cross_decoder(cfg, P, x, m, shared, at):
    """Layers ``half + 2 ..``: a ``lax.scan`` over the (GMU, cross) pairs.
    They read ``m`` and ``shared`` and write nothing. ``at``: the queries'
    positions (B,) against a cache view, None without a cache."""
    entry = None if at is None else {"index": at}

    def pair(x, xs):
        pg, pc, i = xs
        x = _block(cfg, pg, x, gmu_mixer(cfg, pg, _norm_a(cfg, pg, x), m))
        out, _, _ = attention_mixer(cfg, pc, _norm_a(cfg, pc, x), entry,
                                    cfg.half + 3 + 2 * i, CROSS, shared)
        return _block(cfg, pc, x, out), None

    n = (cfg.n_layer - cfg.half - 2) // 2
    x, _ = jax.lax.scan(pair, x,
                        (P["cross_gmu"], P["cross_attn"], jnp.arange(n)))
    return x


def _stacks(cfg) -> dict:
    """``{stack: (the kind of its layers, how many stacked; 0: one)}``."""
    n = cfg.half // 2
    return {"pair_mamba": (MAMBA, n), "pair_window": (WINDOW, n),
            "mamba_last": (MAMBA, 0), "full": (FULL, 0),
            "cross_gmu": (GMU, (cfg.n_layer - cfg.half - 2) // 2),
            "cross_attn": (CROSS, (cfg.n_layer - cfg.half - 2) // 2)}


class Phi4Flash(nn.Module):
    """``model(idx) -> logits``; with ``cache`` (the engines' list of cache
    entries) returns ``(logits, cache)``, the logits of each row's last
    real position, ``(B, 1, vocab)`` (module docstring)."""

    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(self, idx: jax.Array, *, deterministic: bool = True,
                 cache: list[Cache] | None = None,
                 return_hidden: bool = False, head_only: bool = False):
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
        P = {name: _Leaves(tuple(_leaves(cfg, kind)), stack, name=name)()
             for name, (kind, stack) in _stacks(cfg).items()}
        ln_f = _Leaves((("scale", (cfg.hidden_size,), "one"),
                        ("bias", (cfg.hidden_size,), "zero")), name="ln_f")()

        def final(x):
            return _layer_norm(x, ln_f["scale"], ln_f["bias"],
                               cfg.layer_norm_eps)

        x = jnp.take(embed, idx, axis=0).astype(compute)
        b, l = idx.shape
        x, m, shared, caches = self_decoder(cfg, P, x, cache)
        if cache is None:
            x = final(cross_decoder(cfg, P, x, m, shared, None))
            return x if return_hidden else head(x)
        # one position a row: the last real one
        start, valid = _rows(b, cache[0], l)
        if l > 1:
            at = (jnp.full((b,), l - 1) if valid is None
                  else jnp.maximum(valid - 1, 0))
            x, m = (jnp.take_along_axis(t, at[:, None, None], axis=1)
                    for t in (x, m))
            start = start + at

        def cross(x, m, shared):
            return final(cross_decoder(cfg, P, x, m, shared, start))

        finish = cache[0].get(FINISH_KEY)
        if finish is None or l == 1:
            x = cross(x, m, shared)
        else:
            x = jax.lax.cond(jnp.any(finish), cross,
                             lambda x, m, shared: jnp.zeros_like(x),
                             x, m, shared)
        if return_hidden:
            return jnp.broadcast_to(x, (b, l, x.shape[-1])), caches
        return head(x), caches

    # -- convenience API shared by every in-tree model family -----------------
    @property
    def config(self) -> Phi4FlashConfig:
        return self.cfg

    def init_params(self, rng, example_len: int = 8):
        return self.init(rng, jnp.ones((1, example_len), jnp.int32))["params"]

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        """Three entries, each layer kind's buffers STACKED over its layers
        (module docstring): the states, the rings, the full layer's flat
        rows that follow ``max_len``."""
        cfg = self.cfg
        hd, kp = cfg.head_dim, cfg.n_kv_head // 2
        n_pairs = cfg.half // 2
        index = jnp.zeros((), jnp.int32)
        widths = (("k1", hd), ("k2", hd), ("v", 2 * hd))
        rows = min(max_len, cfg.window)
        return [
            {"index": index,
             "ssm": jnp.zeros((batch, n_pairs + 1, cfg.d_state, cfg.d_inner),
                              jnp.dtype(cfg.ssm_state_dtype)),
             "conv": jnp.zeros((batch, n_pairs + 1, cfg.d_conv - 1,
                                cfg.d_inner), jnp.dtype(cfg.compute_dtype))},
            {"index": index, **{
                key: jnp.zeros((batch, rows, n_pairs, kp, width), dtype)
                for key, width in widths}},
            {"index": index, **{
                key: jnp.zeros((batch, max_len, kp * width), dtype)
                for key, width in widths}}]

    @property
    def cache_slot_axis(self) -> int:
        return 0

    #: the serving programs tell this model whether a prompt ends in a
    #: chunk (``layers.FINISH_KEY``): its cross-decoder runs only then
    reads_finish = True
    #: a decode program hands the paged layer the pool's pages as they are
    #: stored and each row's block table (``layers.PAGES_KEY``), not a
    #: gathered view: its eight readers walk the pages where they lie
    reads_pages = True

    def step_stats(self, rows: int) -> list[dict]:
        """No cache entry counts anything on the device
        (``serve/step_stats.py`` books this model's rows from what the
        host dispatched)."""
        return [{}, {}, {}]

    def census(self) -> dict:
        """For the step statistics (``serve/step_stats.py``): a model with
        recurrent layers and a cross-decoder; ``shared_readers`` layers
        attend the one paged layer's view."""
        return {"shared_readers": 1 + self.cfg.kinds.count(CROSS)}


def random_params(cfg: Phi4FlashConfig, seed: int, dtype=jnp.bfloat16,
                  std: float = 0.02) -> dict:
    """Seeded weights made ON THE DEVICE in ``dtype``, one (stacked) leaf
    at a time: N(0, ``std``), the lambdas N(0, 0.1), norm scales 1 and
    biases 0, and Mamba's initialisation where N(0, 0.02) would neither
    remember nor forget nor weigh: ``a_log = log(1 .. N)``, ``D = 1``,
    ``softplus(b_dt)`` log-uniform in [1e-3, 1e-1], the convolution's taps
    U(-1, 1) / sqrt(taps) (its own default: taps of N(0, 0.02) leave
    ``xc`` at 0.02 and the whole Mamba path, the GMUs' memory with it, at
    1.4% of an MLP's output, under the rounding of a bf16 forward)."""
    root = jax.random.PRNGKey(int(seed) % (2 ** 31))
    draw = jax.jit(_draw, static_argnums=(0, 2, 3, 4))
    out = {"tok_embed": draw("normal", jax.random.fold_in(root, 0),
                             (cfg.vocab_size, cfg.hidden_size), dtype, std),
           "ln_f": {"scale": jnp.ones((cfg.hidden_size,), dtype),
                    "bias": jnp.zeros((cfg.hidden_size,), dtype)}}
    for j, (name, (kind, stack)) in enumerate(_stacks(cfg).items()):
        key = jax.random.fold_in(root, j + 1)
        lead = (stack,) if stack else ()
        out[name] = {
            leaf: draw(how, jax.random.fold_in(key, i), lead + shape, dtype,
                       std)
            for i, (leaf, shape, how) in enumerate(_leaves(cfg, kind))}
    return out
