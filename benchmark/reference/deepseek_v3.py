"""Plain reference for DeepSeek-V3 (``model_type`` ``deepseek_v3``,
huggingface.co/deepseek-ai/DeepSeek-V3): the published equations in
``jax.numpy``, float32 at ``highest`` matmul precision, the NAIVE attention
form only, the full forward of ONE whole sequence, a plain loop over
experts, no cache, no paging, no kernel, no batching. From the program it
takes nothing but the weights (a nested dict of arrays, whatever dtype:
each is read up to float32 where it is used, a layer, a group of heads and
an expert at a time, and attention runs a block of queries at a time, so
the serving cut fits beside the engine at 7168 wide).

Equations (``eps`` 1e-6). Per layer, ``u = RMSNorm(x)``, ``h = x +
Attn(u)``, ``y = h + FFN(RMSNorm(h))``.

``Attn`` (MLA): ``c_q = RMSNorm(W_qa u)``; ``q = W_qb c_q`` gives each
head ``[q_nope | q_rope]``; ``[c_kv | k_rope] = W_kva u``, ``c_kv <-
RMSNorm(c_kv)``; RoPE at absolute positions on ``q_rope`` and on
``k_rope``, the ONE rope key a token that all heads share; ``[k_nope_h |
v_h] = W_kvb,h c_kv``; ``score_h(i, j) = (q_nope_h,i . k_nope_h,j +
q_rope_h,i . k_rope_j) * s`` for ``j <= i``, ``s = (d_nope + d_rope)^-1/2
* m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``; softmax; ``o =
W_o concat_h sum_j p_h(i, j) v_h,j``.

RoPE / YaRN on the rope dims, taken INTERLEAVED (pair ``(2i, 2i+1)``
turns by ``f_i``: the pairs the published code rotates after its
de-interleave; the model file says why a checkpoint's columns then load
as they are): ``f_i = theta^(-2i/d)``, interpolated ``f_i / factor``,
blended by the linear ramp between the correction dimensions of
``beta_fast`` and ``beta_slow`` over ``original_max_position_embeddings``;
cos / sin scaled by ``mscale(factor, mscale) / mscale(factor,
mscale_all_dim)``.

``FFN``: layers below ``first_k_dense_replace`` are SwiGLU. The others,
with ``v = RMSNorm(h)``: ``s = sigmoid(W_g v)`` over ALL routed experts;
``s' = s + b`` selects only; ``n_group`` equal groups, a group's score the
sum of its two largest ``s'``; the ``topk_group`` best groups stay; ``S``
= the ``top_k`` largest ``s'`` inside them; ``w_e = scale * s_e / sum_{j
in S} s_j``; ``MoE(v) = sum_{e in S and HELD} w_e E_e(v) + E_shared(v)``.
The reference is given the held range (first id, count) and leaves out
the same absent experts as the program: what they would add is missing
from both, and the partial result goes on to the next layer.

Final RMSNorm, then an UNTIED head over the vocabulary rows held here.
Multi-token prediction is left out, as in the model file.

**Routing is discrete.** The engine routes on bf16 activations, the
reference on float32 ones, and the selection flips where the k-th and
(k+1)-th ``s'`` nearly tie. At the JUDGED positions, where the engine's
expert set differs from the reference's and every expert the engine chose
instead lies within ``ROUTE_MARGIN`` of the reference's k-th best ``s'``
(``s'_e >= s'_kth - ROUTE_MARGIN``; the group cut can also flip, so the
k-th best is taken among ALL experts' ``s'`` inside the reference's own
groups and the engine's expert may lie outside them), the reference takes
the ENGINE's set for that (token, layer) pair, weights from its own
``s``. A flip outside the margin fails the comparison, and the share of
pairs that flip at all is bounded. Nowhere else is the routing forced.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# Every limit lies between two chip readings (my chip runs, PR 34; PERF.md
# section 6): the largest the engine gave over seven seeds at the
# configuration's precision (bf16 weights, activations and latent rows;
# float32 router at `highest`; float32 logits) against float32 `highest`
# of the SAME bf16 weights, and what the nearest precision below gave on
# two seeds: the latent rows stored in fp8 (e4m3), everything else as it
# was. fp8 fails four of the five limits; the route margin does not tell
# the two apart and is not meant to (it bounds what the reference may take
# from the engine). tests/test_deepseek_v3_serving.py holds the check to a
# wrong EQUATION (no m^2) at toy size.
#
# Logits at one position, in units of the reference logits' spread there.
# Read rms 0.0135-0.0151, max 0.055-0.068; fp8 latent 0.077, 0.32-0.34.
LOGIT_RMS_TOL = 0.03
LOGIT_MAX_TOL = 0.15
# A greedy token's reference logit may trail the reference's best at its
# position by this much of the row's spread. Read 0-0.003; fp8 0.12-0.22.
TOKEN_MARGIN_TOL = 0.06
# A differing expert's biased score must lie this close below the
# reference's k-th best: sigmoid' <= 1/4, and the router logit carries the
# hidden state's bf16 error times |W_g| sqrt(hidden). Worst shortfall read
# 0.0017-0.0194 (fp8 0.018-0.033: no separation here).
ROUTE_MARGIN = 0.04
# and no more than this share of the judged (token, layer) pairs may flip.
# Read 12.5-17.2% of 128 pairs (the 8th and 9th best of ~128 eligible
# sigmoid scores lie ~0.01 apart; one pair is 0.8%, a binomial sigma 3%);
# fp8 49-55%.
ROUTE_FLIP_SHARE_TOL = 0.3

QUERY_BLOCK = 256       # queries attended at a time
HEAD_GROUP = 16         # heads projected and attended at a time
VOCAB_STEP = 16384      # vocabulary columns per head matmul


def geometry(cfg) -> dict:
    """What the reference needs of a ``DeepSeekV3Config`` (plain
    numbers; the reference imports nothing of the program)."""
    return {"n_head": cfg.n_head, "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "rms_norm_eps": cfg.rms_norm_eps,
            "rope_theta": cfg.rope_theta, "yarn": cfg.yarn,
            "first_k_dense_replace": cfg.first_k_dense_replace,
            "top_k": cfg.n_experts_per_tok, "n_group": cfg.n_group,
            "topk_group": cfg.topk_group,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "norm_topk": cfg.norm_topk_prob, "held": tuple(cfg.held)}


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(scale)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_frequencies(geom: dict) -> np.ndarray:
    """The ``d_rope / 2`` frequencies (published
    ``yarn_find_correction_range`` / ``yarn_linear_ramp_mask``)."""
    d, theta = geom["qk_rope_head_dim"], geom["rope_theta"]
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if geom["yarn"] is None:
        return f
    factor, original, fast, slow, _, _ = geom["yarn"]

    def correction_dim(rotations):
        return (d * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(fast)), 0)
    high = min(math.ceil(correction_dim(slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return (f / factor) * ramp + f * (1.0 - ramp)


def softmax_scale(geom: dict) -> float:
    s = (geom["qk_nope_head_dim"] + geom["qk_rope_head_dim"]) ** -0.5
    if geom["yarn"] is not None and geom["yarn"][5]:
        m = yarn_mscale(geom["yarn"][0], geom["yarn"][5])
        s = s * m * m
    return s


def _rope(x, cos, sin):
    """Interleaved pairs: lanes (2i, 2i+1) turn by frequency i."""
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def attention_inputs(x, block, geom: dict):
    """``u -> (c_q, c_kv, rotated k_rope, cos, sin)`` of one layer."""
    eps, rank = geom["rms_norm_eps"], geom["kv_lora_rank"]
    attn = block["attn"]
    n = x.shape[0]
    u = _rms(x, block["ln1"]["scale"], eps)
    c_q = _rms(u @ _f32(attn["q_a_proj"]["kernel"]),
               attn["q_a_norm"]["scale"], eps)
    kv = u @ _f32(attn["kv_a_proj"]["kernel"])
    c_kv = _rms(kv[:, :rank], attn["kv_a_norm"]["scale"], eps)
    ang = (jnp.arange(n, dtype=jnp.float32)[:, None]
           * jnp.asarray(rope_frequencies(geom), jnp.float32)[None, :])
    m = 1.0
    if geom["yarn"] is not None:
        m = (yarn_mscale(geom["yarn"][0], geom["yarn"][4])
             / yarn_mscale(geom["yarn"][0], geom["yarn"][5]))
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    return c_q, c_kv, _rope(kv[:, rank:], cos, sin), cos, sin


def head_group_attention(c_q, c_kv, k_rope, cos, sin, w_qb, w_kvb, w_o,
                         geom: dict):
    """The naive form for ONE group of heads: ``w_qb`` (q_rank, G, dn +
    dr), ``w_kvb`` (rank, G, dn + dv), ``w_o`` (G, dv, hidden). Returns
    the group's part of ``W_o concat_h o_h``, (n, hidden)."""
    dn = geom["qk_nope_head_dim"]
    n = c_q.shape[0]
    q = jnp.einsum("nc,chd->nhd", c_q, _f32(w_qb))
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], cos[:, None],
                                        sin[:, None])
    kv = jnp.einsum("nc,chd->nhd", c_kv, _f32(w_kvb))
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scale = softmax_scale(geom)
    qb = min(QUERY_BLOCK, n)
    pad = -n % qb

    def block(i):
        rows = i * qb + jnp.arange(qb)
        qn = jnp.take(q_nope, rows, axis=0, mode="clip")
        qr = jnp.take(q_rope, rows, axis=0, mode="clip")
        s = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
             + jnp.einsum("qhr,kr->hqk", qr, k_rope)) * scale
        s = jnp.where(jnp.arange(n)[None, None, :] <= rows[None, :, None],
                      s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, jnp.arange((n + pad) // qb))
    o = o.reshape((n + pad,) + o.shape[2:])[:n]
    return jnp.einsum("nhd,hdm->nm", o, _f32(w_o))


def swiglu(x, mlp):
    gate = x @ _f32(mlp["gate_proj"]["kernel"])
    up = x @ _f32(mlp["up_proj"]["kernel"])
    return (jax.nn.silu(gate) * up) @ _f32(mlp["down_proj"]["kernel"])


def router_scores(hn, moe):
    """``(s, s')``: sigmoid scores of all routed experts, and the biased
    scores that select."""
    s = jax.nn.sigmoid(hn @ _f32(moe["router"]))
    return s, s + _f32(moe["e_score_correction_bias"])[None, :]


def held_experts(hn, weights, moe):
    """``sum_e weights[:, e] * E_e(hn)`` over the experts HELD (the
    stacked leaves): a plain loop, each read up to float32 on its turn.
    ``weights`` (n, held) holds 0 where a token did not choose the
    expert."""
    def one(e, acc):
        gate = hn @ _f32(moe["w_gate"][e])
        up = hn @ _f32(moe["w_up"][e])
        out = (jax.nn.silu(gate) * up) @ _f32(moe["w_down"][e])
        return acc + weights[:, e][:, None] * out

    return jax.lax.fori_loop(0, moe["w_gate"].shape[0], one,
                             jnp.zeros_like(hn))


def choose(s: np.ndarray, biased: np.ndarray, geom: dict,
           engine_sets: np.ndarray | None, last: int):
    """The reference's expert sets and weights for scores ``s`` /
    ``biased`` (n, E) as a dense (n, E) weight matrix (the scaling
    factor included), and what it found at the last ``last`` positions
    where ``engine_sets`` (last, k) differ (module docstring)."""
    n, e = s.shape
    k, groups, keep = geom["top_k"], geom["n_group"], geom["topk_group"]
    per = e // groups
    grouped = biased.reshape(n, groups, per)
    top2 = -np.sort(-grouped, axis=2)[:, :, :2].sum(axis=2)
    best = np.argsort(-top2, axis=1, kind="stable")[:, :keep]
    allowed = np.zeros((n, groups), bool)
    np.put_along_axis(allowed, best, True, axis=1)
    masked = np.where(np.repeat(allowed, per, axis=1), biased, -np.inf)
    order = np.argsort(-masked, axis=1, kind="stable")
    sets = order[:, :k].copy()
    kth = np.take_along_axis(masked, order[:, k - 1:k], axis=1)[:, 0]
    found = {"pairs": 0, "flipped": 0, "outside_margin": 0,
             "worst_shortfall": 0.0}
    if engine_sets is not None:
        found["pairs"] = last
        for t in range(last):
            row = n - last + t
            mine = set(sets[row].tolist())
            theirs = {int(x) for x in engine_sets[t]}
            if mine == theirs:
                continue
            found["flipped"] += 1
            short = max(float(kth[row] - biased[row, x])
                        for x in theirs - mine)
            found["worst_shortfall"] = max(found["worst_shortfall"], short)
            if short <= ROUTE_MARGIN and len(theirs) == k:
                sets[row] = sorted(theirs)
            else:
                found["outside_margin"] += 1
    w = np.take_along_axis(s, sets, axis=1)
    if geom["norm_topk"]:
        w = w / (w.sum(axis=1, keepdims=True) + 1e-20)
    dense = np.zeros_like(s)
    np.put_along_axis(dense, sets, w * geom["routed_scaling_factor"], axis=1)
    return dense, found


class Reference:
    """``geom``: :func:`geometry` of the configuration."""

    def __init__(self, geom: dict):
        self.geom = geom
        eps = geom["rms_norm_eps"]
        self._inputs = jax.jit(functools.partial(attention_inputs,
                                                 geom=geom))
        self._heads = jax.jit(functools.partial(head_group_attention,
                                                geom=geom))
        self._swiglu = jax.jit(swiglu)
        self._scores = jax.jit(router_scores)
        self._held = jax.jit(held_experts)
        self._norm = jax.jit(lambda x, scale: _rms(x, scale, eps))
        self._head = jax.jit(
            lambda x, scale, cols: _rms(x, scale, eps) @ _f32(cols))

    def attention(self, x, block):
        c_q, c_kv, k_rope, cos, sin = self._inputs(x, block)
        attn, g = block["attn"], HEAD_GROUP
        h, dv = self.geom["n_head"], self.geom["v_head_dim"]
        w_qb = attn["q_b_proj"]["kernel"].reshape(c_q.shape[1], h, -1)
        w_o = attn["o_proj"]["kernel"].reshape(h, dv, -1)
        out = x
        for i in range(0, h, g):
            out = out + self._heads(
                c_q, c_kv, k_rope, cos, sin, w_qb[:, i:i + g],
                attn["kv_b_proj"][:, i:i + g], w_o[i:i + g])
        return out

    def logits(self, params: dict, ids, last: int = 1,
               engine_experts=None):
        """Logits (last, vocab) of the last ``last`` positions of ONE
        sequence ``ids``, and what the routing comparison found.
        ``engine_experts``: per ROUTED layer the (last, k) experts the
        engine chose at those positions, or None."""
        geom = self.geom
        n_layer = sum(1 for k in params if k.startswith("block_"))
        first, count = geom["held"]
        found = {"pairs": 0, "flipped": 0, "outside_margin": 0,
                 "worst_shortfall": 0.0}
        with jax.default_matmul_precision("highest"):
            x = _f32(params["tok_embed"]["embedding"][jnp.asarray(ids)])
            routed = 0
            for i in range(n_layer):
                block = params[f"block_{i}"]
                h = self.attention(x, block)
                hn = self._norm(h, block["ln2"]["scale"])
                if i < geom["first_k_dense_replace"]:
                    x = h + self._swiglu(hn, block["mlp"])
                    continue
                moe = block["moe"]
                s, biased = self._scores(hn, moe)
                dense, f = choose(
                    np.asarray(s), np.asarray(biased), geom,
                    None if engine_experts is None
                    else engine_experts[routed], last)
                routed += 1
                for key in ("pairs", "flipped", "outside_margin"):
                    found[key] += f[key]
                found["worst_shortfall"] = max(found["worst_shortfall"],
                                               f["worst_shortfall"])
                x = (h + self._held(
                    hn, jnp.asarray(dense[:, first:first + count]), moe)
                    + self._swiglu(hn, moe["shared"]))
            x = x[-last:]
            head = params["lm_head"]
            out = [np.asarray(self._head(
                x, params["ln_f"]["scale"], head[:, i:i + VOCAB_STEP]))
                for i in range(0, head.shape[1], VOCAB_STEP)]
        return np.concatenate(out, axis=-1), found


def logit_error(got: np.ndarray, want: np.ndarray) -> dict:
    """rms and worst difference in units of the reference logits' spread,
    and whether they are inside the tolerances above."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape or not np.isfinite(got).all():
        return {"ok": False, "why": f"shape {got.shape} vs {want.shape} "
                                    "or non-finite logits"}
    spread = float(np.std(want))
    rms = float(np.sqrt(np.mean((got - want) ** 2))) / spread
    worst = float(np.max(np.abs(got - want))) / spread
    return {"ok": rms <= LOGIT_RMS_TOL and worst <= LOGIT_MAX_TOL,
            "rms_over_std": rms, "max_over_std": worst}


def token_margins(want: np.ndarray, tokens: list[int]) -> dict:
    """``want`` (n, vocab): the reference's logits at the positions that
    produced ``tokens`` (teacher forcing). Every token's reference logit
    within the margin of the reference's best there."""
    want = np.asarray(want, np.float64)
    spread = float(np.std(want))
    gaps = [float(want[i].max() - want[i, t]) / spread
            for i, t in enumerate(tokens)]
    return {"ok": max(gaps) <= TOKEN_MARGIN_TOL,
            "worst_margin_over_std": max(gaps)}
