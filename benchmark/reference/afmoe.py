"""Plain reference for Arcee Trinity (``model_type`` ``afmoe``,
huggingface.co/arcee-ai/Trinity-Large-Preview): the equations below in
``jax.numpy``, float32 at ``highest`` matmul precision, the full forward of
ONE whole sequence, attention as a masked dense softmax a block of queries
at a time (so that 12k tokens fit), a plain loop over experts; no cache, no
ring, no paging, no kernel, no batching. From the program it takes nothing
but the weights (a nested dict of arrays, whatever dtype: each is read up
to float32 where it is used, a layer, a group of heads and an expert at a
time).

Equations (``eps`` 1e-5, RMSNorm with a learned scale, no biases). ``x0 =
embed(ids) * sqrt(hidden)`` (``mup_enabled``). Per layer ``n`` of the
PUBLISHED model:

``h = norm_in(x)``; ``q = h Wq`` -> (H, 128); ``k = h Wk``, ``v = h Wv``
-> (Hk, 128); ``g = h Wg`` -> (H * 128); query head ``i`` reads K/V head
``i // (H / Hk)``. ``q``, ``k`` <- RMSNorm over the 128 (one learned scale
vector each, shared by the heads). ``layer_types[n]`` =
``sliding_attention``: rotary on all 128 dimensions of ``q`` and ``k``,
half-split pairs (``i`` with ``i + 64``), ``f_i = theta^(-2i / 128)``;
``s_ij = q_i . k_j / sqrt(128)`` for ``0 <= i - j < window``.
``full_attention``: NO rotary; ``s_ij`` for ``j <= i``. ``p =
softmax_j(s)`` (no sink); ``o_i = sum_j p_ij v_j``; ``a = (o *
sigmoid(g)) Wo``; ``x <- x + norm_post_attn(a)``.

``h' = norm_pre_mlp(x)``. ``n < num_dense_layers``: ``m`` = SwiGLU. Else
``s = sigmoid(h' Wr)`` (256 experts, float32); ``s' = s + expert_bias``
selects only; ``S`` = the ``top_k`` largest ``s'`` (one group); ``w_e =
route_scale * s_e / (sum_{j in S} s_j + 1e-20)``; ``m = sum_{e in S and
HELD} w_e E_e(h')`` + the shared expert's SwiGLU of ``h'``. The reference
is given the held range and leaves out the same absent experts as the
program. ``x <- x + norm_post_mlp(m)``. Final RMSNorm, then an UNTIED head
over the vocabulary rows held here.

The geometry carries the layer's form as data (which layers turn ``q`` and
``k``, which of the four norms a layer has, whether the gate and the
QK-norm exist, the embedding's factor): ``tests/test_afmoe.py`` hands a
reference with one of them LEFT OUT to the comparison, which must fail.

**Routing is discrete.** As ``reference/mimo_v2.py``: at the JUDGED
positions, where the engine's expert set differs from the reference's and
every expert the engine chose instead lies within ``ROUTE_MARGIN`` of the
reference's k-th best ``s'``, the reference takes the ENGINE's set for
that (token, layer) pair. A flip outside the margin fails, and the share
of pairs that flip at all is bounded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Two chip readings stand behind every limit (my chip runs, PR 44; PERF.md
# section 6). SOUND: the engine at the configuration's precision (bf16
# weights, activations, pages and rings; float32 router at `highest`;
# float32 logits) against float32 `highest` of the SAME bf16 weights, the
# largest over the runs at distinct seeds. CONTROL: the nearest precision
# below, the K/V pages and the rings stored in fp8 (e4m3), everything else as
# it was, through this cell's own `check`, the least over six seeds
# (`tools/swa_check_control.py --cell trinity-large.mixed-lengths`). The two
# LOGIT limits lie between their readings with room on both sides, and every
# control seed breaks both: they are what makes a lower precision come out
# not correct. The other three statistics are extremes or small counts over
# 32 judged positions and do not separate the two precisions (readings
# beside each); their limits sit where a sound run cannot reach them, and
# they catch what the logits at ONE position cannot: a decode path or a
# router that has gone wrong. tests/test_afmoe_serving.py holds the check to
# wrong EQUATIONS at toy size.
#
# Logits at one position, in units of the reference logits' spread there:
# rms sound <= 0.0089 | control >= 0.0273; max sound <= 0.0412 | control
# >= 0.109.
LOGIT_RMS_TOL = 0.016
LOGIT_MAX_TOL = 0.07
# A greedy token's reference logit may trail the reference's best at its
# position by this much of the row's spread. Sound <= 0.0108 (0.0 in four
# runs of six: two logit errors of rms 0.0085 swap a pair 0.016 apart in one
# sound run of fifteen) | control 0.0156 .. 0.076: 3 of 6 seeds break it.
TOKEN_MARGIN_TOL = 0.05
# A differing expert's biased score must lie this close below the
# reference's k-th best (sigmoid' <= 1/4; the router logit carries the
# hidden state's bf16 error). Inside the margin the reference takes the
# ENGINE's set for that pair. Worst shortfall sound <= 0.0027 | control
# 0.0055 .. 0.0082: the largest of a handful of flips, three times the
# sound runs' worst.
ROUTE_MARGIN = 0.008
# and no more than this share of the judged (token, layer) pairs may flip:
# sound 0.8 .. 8.1% of 124 pairs (a count of 1 .. 10) | control 12.9 ..
# 18.5%; a count of 124 that averages 6 reaches 16 once in 7,000 runs.
ROUTE_FLIP_SHARE_TOL = 0.2

QUERY_BLOCK = 256       # queries attended at a time
HEAD_GROUP = 16         # query heads projected and attended at a time
VOCAB_STEP = 16384      # vocabulary columns per head matmul
NORMS = ("norm_in", "norm_post_attn", "norm_pre_mlp", "norm_post_mlp")


def geometry(cfg) -> dict:
    """What the reference needs of an ``AfmoeConfig`` (plain numbers; the
    reference imports nothing of the program)."""
    return {"n_head": cfg.n_head, "n_kv_head": cfg.n_kv_head,
            "head_dim": cfg.head_dim, "window": cfg.window,
            "window_layers": tuple(bool(w) for w in cfg.window_layers),
            # the layers that turn q and k: the window layers, no other
            "rotary": tuple(bool(w) for w in cfg.window_layers),
            "n_dense_layers": cfg.n_dense_layers,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
            "top_k": cfg.n_experts_per_tok, "route_scale": cfg.route_scale,
            "route_norm": cfg.route_norm, "held": tuple(cfg.held),
            "n_shared_experts": cfg.n_shared_experts,
            "embed_scale": cfg.embed_scale, "norms": NORMS,
            "gate": True, "qk_norm": True}


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(scale)


def _rope(x, theta: float):
    """Half-split rotary on the whole last axis of ``x`` (n, heads, d) at
    positions 0 .. n - 1."""
    n, d = x.shape[0], x.shape[-1]
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = (jnp.arange(n, dtype=jnp.float32)[:, None]
           * jnp.asarray(f, jnp.float32)[None, :])[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _norm(x, block, name: str, geom: dict):
    """One of a layer's four norms, where the geometry has it."""
    if name not in geom["norms"]:
        return x
    return _rms(x, block[name]["scale"], geom["rms_norm_eps"])


def _qk(t, scale, rotary: bool, geom: dict):
    """QK-norm over the head's 128, then rotary where the layer turns."""
    if geom["qk_norm"]:
        t = _rms(t, scale, geom["rms_norm_eps"])
    return _rope(t, geom["rope_theta"]) if rotary else t


def project(x, block, *, rotary: bool, geom: dict):
    """``h = norm_in(x)``, the layer's keys (n, Hk, 128) (normed, turned
    in a window layer) and values (n, Hk, 128)."""
    attn = block["attn"]
    n, hk, d = x.shape[0], geom["n_kv_head"], geom["head_dim"]
    h = _norm(x, block, "norm_in", geom)
    k = (h @ _f32(attn["k_proj"]["kernel"])).reshape(n, hk, d)
    v = (h @ _f32(attn["v_proj"]["kernel"])).reshape(n, hk, d)
    return h, _qk(k, attn["k_norm"]["scale"], rotary, geom), v


def head_group_attention(h, k, v, w_q, w_g, w_o, q_scale, *, window: bool,
                         rotary: bool, geom: dict):
    """ONE group of query heads: ``w_q`` / ``w_g`` (hidden, G, 128), ``k``
    / ``v`` (n, G, 128) each head's own K/V head, ``w_o`` (G, 128,
    hidden). Returns the group's part of ``(o * sigmoid(g)) Wo``."""
    n = h.shape[0]
    q = _qk(jnp.einsum("nc,chd->nhd", h, _f32(w_q)), q_scale, rotary, geom)
    qb = min(QUERY_BLOCK, n)
    pad = -n % qb
    cols = jnp.arange(n)

    def rows_of(i):
        rows = i * qb + jnp.arange(qb)
        qs = jnp.take(q, rows, axis=0, mode="clip")
        s = jnp.einsum("qhd,khd->hqk", qs, k) / np.sqrt(geom["head_dim"])
        seen = cols[None, :] <= rows[:, None]
        if window:
            seen &= rows[:, None] - cols[None, :] < geom["window"]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    o = jax.lax.map(rows_of, jnp.arange((n + pad) // qb))
    o = o.reshape((n + pad,) + o.shape[2:])[:n]
    if geom["gate"]:
        o = o * jax.nn.sigmoid(jnp.einsum("nc,chd->nhd", h, _f32(w_g)))
    return jnp.einsum("nhd,hdm->nm", o, _f32(w_o))


def swiglu(x, mlp):
    gate = x @ _f32(mlp["gate_proj"]["kernel"])
    up = x @ _f32(mlp["up_proj"]["kernel"])
    return (jax.nn.silu(gate) * up) @ _f32(mlp["down_proj"]["kernel"])


def router_scores(hn, moe):
    """``(s, s')``: sigmoid scores of all routed experts, and the biased
    scores that select."""
    s = jax.nn.sigmoid(hn @ _f32(moe["router"]))
    return s, s + _f32(moe["expert_bias"])[None, :]


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
    ``biased`` (n, E) as a dense (n, E) weight matrix, and what it found
    at the last ``last`` positions where ``engine_sets`` (last, k) differ
    (module docstring)."""
    n, _ = s.shape
    k = geom["top_k"]
    order = np.argsort(-biased, axis=1, kind="stable")
    sets = order[:, :k].copy()
    kth = np.take_along_axis(biased, order[:, k - 1:k], axis=1)[:, 0]
    found = {"pairs": 0, "flipped": 0, "outside_margin": 0,
             "worst_shortfall": 0.0}
    if engine_sets is not None:
        for t in range(last):
            row = n - last + t
            mine = set(sets[row].tolist())
            theirs = {int(x) for x in engine_sets[t]}
            found["pairs"] += 1
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
    if geom["route_norm"]:
        w = w / (w.sum(axis=1, keepdims=True) + 1e-20)
    dense = np.zeros_like(s)
    np.put_along_axis(dense, sets, w * geom["route_scale"], axis=1)
    return dense, found


class Reference:
    """``geom``: :func:`geometry` of the configuration."""

    def __init__(self, geom: dict):
        self.geom = geom
        eps = geom["rms_norm_eps"]
        # a layer's kind: (window mask, rotary)
        kinds = set(zip(geom["window_layers"], geom["rotary"]))
        self._project = {r: jax.jit(functools.partial(
            project, rotary=r, geom=geom)) for _, r in kinds}
        self._heads = {(w, r): jax.jit(functools.partial(
            head_group_attention, window=w, rotary=r, geom=geom))
            for w, r in kinds}
        self._swiglu = jax.jit(swiglu)
        self._scores = jax.jit(router_scores)
        self._held = jax.jit(held_experts)
        self._norm = jax.jit(functools.partial(_norm, geom=geom),
                             static_argnums=(2,))
        self._head = jax.jit(
            lambda x, scale, cols: _rms(x, scale, eps) @ _f32(cols))

    def attention(self, x, block, window: bool, rotary: bool):
        """``x + norm_post_attn(Attn(norm_in(x)))``, a group of heads at
        a time."""
        geom, attn = self.geom, block["attn"]
        n_head, d = geom["n_head"], geom["head_dim"]
        h, k, v = self._project[rotary](x, block)
        per = n_head // k.shape[1]          # query heads a K/V head
        w_q = attn["q_proj"]["kernel"].reshape(x.shape[1], n_head, d)
        w_g = attn["gate_proj"]["kernel"].reshape(x.shape[1], n_head, d)
        w_o = attn["o_proj"]["kernel"].reshape(n_head, d, -1)
        a = jnp.zeros_like(x)
        for i in range(0, n_head, HEAD_GROUP):
            heads = np.arange(i, min(i + HEAD_GROUP, n_head))
            a = a + self._heads[(window, rotary)](
                h, k[:, heads // per], v[:, heads // per], w_q[:, heads],
                w_g[:, heads], w_o[heads], attn["q_norm"]["scale"])
        return x + self._norm(a, block, "norm_post_attn")

    def feed_forward(self, x, block, routed: bool, engine_sets, last: int):
        """``x + norm_post_mlp(FFN(norm_pre_mlp(x)))`` and what the
        routing comparison found."""
        geom = self.geom
        first, count = geom["held"]
        hn = self._norm(x, block, "norm_pre_mlp")
        if not routed:
            m = self._swiglu(hn, block["mlp"])
            return x + self._norm(m, block, "norm_post_mlp"), None
        moe = block["moe"]
        s, biased = self._scores(hn, moe)
        dense, found = choose(np.asarray(s), np.asarray(biased), geom,
                              engine_sets, last)
        m = self._held(hn, jnp.asarray(dense[:, first:first + count]), moe)
        if geom["n_shared_experts"]:
            m = m + self._swiglu(hn, moe["shared"])
        return x + self._norm(m, block, "norm_post_mlp"), found

    def logits(self, params: dict, ids, last: int = 1,
               engine_experts=None):
        """Logits (last, vocab) of the last ``last`` positions of ONE
        sequence ``ids``, and what the routing comparison found.
        ``engine_experts``: per ROUTED layer the (last, k) experts the
        engine chose at those positions, or None."""
        geom = self.geom
        total = {"pairs": 0, "flipped": 0, "outside_margin": 0,
                 "worst_shortfall": 0.0}
        with jax.default_matmul_precision("highest"):
            x = geom["embed_scale"] * _f32(
                params["tok_embed"]["embedding"][jnp.asarray(ids)])
            routed = 0
            for i, (window, rotary) in enumerate(
                    zip(geom["window_layers"], geom["rotary"])):
                block = params[f"block_{i}"]
                x = self.attention(x, block, window, rotary)
                is_routed = i >= geom["n_dense_layers"]
                x, found = self.feed_forward(
                    x, block, is_routed,
                    None if engine_experts is None or not is_routed
                    else engine_experts[routed], last)
                if found is None:
                    continue
                routed += 1
                for key in ("pairs", "flipped", "outside_margin"):
                    total[key] += found[key]
                total["worst_shortfall"] = max(total["worst_shortfall"],
                                               found["worst_shortfall"])
            x = x[-last:]
            head = params["lm_head"]
            out = [np.asarray(self._head(
                x, params["ln_f"]["scale"], head[:, i:i + VOCAB_STEP]))
                for i in range(0, head.shape[1], VOCAB_STEP)]
        return np.concatenate(out, axis=-1), total


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
