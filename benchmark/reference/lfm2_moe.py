"""Plain reference for LFM2-MoE (``model_type`` ``lfm2_moe``,
huggingface.co/LiquidAI/LFM2-24B-A2B): the equations below in
``jax.numpy``, float32 at ``highest`` matmul precision, ONE whole sequence,
the convolution as three shifted products, attention as a masked dense
softmax a block of queries at a time, the experts as a plain loop over ALL
of them, each over every position times the position's weight for it; no
cache, tail, page, kernel, chunk or batch. From the program it takes nothing
but the weights (a nested dict of arrays, whatever dtype: each is read up to
float32 where it is used).

Equations (``d`` hidden, RMSNorm ``x / sqrt(mean(x^2) + eps) * scale``, eps
1e-5, no bias anywhere). ``x0 = E[ids]``. Layer ``n``: ``h = x +
mixer_n(norm_op(x))``; ``x <- h + ffn_n(norm_ffn(h))``. After the last layer
one RMSNorm; ``logits = x E^T`` (tied: assumed, the configuration's file).

CONV mixer: ``[B ‖ C ‖ u] = n W_in`` (split in that order: assumed); ``g_t =
B_t * u_t``; ``c_t = sum_{j<3} w_j * g_{t-2+j}`` (``g`` before the sequence
zero); ``out_t = (C_t * c_t) W_out``. ATTENTION mixer (``H`` query heads,
``Hk`` K/V heads of ``hd = d / H``): ``q, k, v = n W_q, n W_k, n W_v``;
RMSNorm over each head's ``hd`` values of ``q`` and ``k``; rotary over the
whole head, half-split (``i`` with ``i + hd / 2``), ``theta^(-2i / hd)``;
``softmax_j(q_i . k_j / sqrt(hd))`` over ``j <= i``; ``W_o``. DENSE ffn (the
first ``n_dense`` layers): ``(silu(n W_1) * n W_3) W_2``. ROUTED ffn: ``s =
sigmoid(n W_g)``; the ``k`` experts with the largest ``s + b``; weights ``s_e
/ (sum of the chosen s + 1e-6)`` times ``scale``; ``sum_e weight_e (silu(n
W_1e) * n W_3e) W_2e``.

**Routing is discrete.** The engine routes on bf16 activations, the
reference on float32 ones, and the selection flips where the k-th and
(k+1)-th biased scores nearly tie (top-4 of 64 sigmoid scores under seeded
weights: two thirds of the sets have a gap under 0.02). A flipped expert is
a quarter of a layer's output AT ITS POSITION: one flip at a judged position
moved a probe's logits from 0.02 to 0.08-0.15 of their spread and a tail (two
positions) from 0.02 to 0.08-0.2, so readings that carried the flips
separated nothing. The comparison therefore makes the reference COMPUTE the
program's experts at the judged positions (``forced``: a probe's last prompt
position and its 16 tokens', a filler's every decoded position), weighed by
its own scores, and judges the selection apart: a routed set counts as
FLIPPED only where the reference's own margin there is clear
(``ROUTE_MARGIN``). Every other position is the reference's own; what the
flips there cost reaches the judged positions through attention alone, and
the pool's rows (every position) are read overall, at their worst row and at
their MEDIAN row, which no flip touches.

Storage (shared with the program, like the weights): maximal runs of layers
of one kind are STACKED, ``run_<first layer>`` with a leading layer axis
(:func:`layer_params` names layer ``n``'s); ``conv_w`` is ``(3, d)``,
taps-major.

The geometry carries the forms as data: the tests hand the comparison a
reference with ONE planted fault (``conv_break``: the two-row tail dropped
at every multiple of a chunk; ``pad_advance``: the tail lost where the prompt
ends, as padding rows that advanced it would leave it; ``bias_in_weights``:
the selection bias added to the routing weights) and must see it fail;
``tools/swa_check_control.py --cell lfm2-24b-a2b.chat-concurrent --faults``
does the same at the cell's size, on the chip, through the cell's ``judge``
on the warmed engine, with the K/V rows in e4m3 (``kv_dtype``) and the
probes' slots crossed besides.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Every limit on a STORE, on the tokens and on the routed sets lies between
# two chip readings (my chip runs, PR 52, calls 117 to 119:
# `tools/swa_check_control.py --cell lfm2-24b-a2b.chat-concurrent
# --probe-seeds 3 --faults` on one warmed server, and ten whole runs of the
# cell at weight seeds of their own; PERF.md section 6 has every line): the
# largest of THIRTEEN sound readings (the engine at the configuration's
# precision: bf16 weights, activations, pages and tails, a float32 router,
# float32 logits; every slot live) against float32 `highest` of the SAME
# bf16 weights, and the smallest reading of the control or planted fault
# that the limit is there to catch, each judged on the same observation
# through the cell's own `judge` (`faults()` of the runner; three sets of
# probes each). Each of the five came out NOT ok in three sets of three.
#
# Logits at one position, in units of the reference logits' spread there.
# Sound 0.018 - 0.067 / 0.076 - 0.299, a wide band: a probe's last prompt
# position is the FIRST forced one, so flips at the two positions before it
# still reach it through the conv taps. The same observations with NO set
# forced (`routes_free`: one or two wrong experts at the judged position
# itself) read 0.077 - 0.155 / 0.335 - 0.658, and no planted fault moves
# the prefill's last position, so these two limits separate nothing that
# the band does not already hold: they stand where a sound run passes them
# 999 times in 1,000 by the readings' own scatter (the cell is run
# dozens of times a PR, and one false `correct: false` refuses it) and
# bound only what breaks the last position wholesale. The arithmetic is
# judged by the stores, the selection by `route_flip_share` (the dense
# cells read 0.02 - 0.05 / 0.1 - 0.27).
LOGIT_RMS_TOL = 0.15
LOGIT_MAX_TOL = 0.7
# A greedy token's reference logit may trail the reference's best at its
# position by this much of the row's spread. Sound 0 - 0.028;
# `pad_advance` 4.1 - 5.1 (`routes_free` 0.07 - 0.09).
TOKEN_MARGIN_TOL = 0.3
# A routed set (the k experts of one layer at one judged position) counts as
# FLIPPED only where the reference's own margin between its k-th and (k+1)-th
# biased score exceeds this width: nearer ties turn on the rounding of the
# layers before (103 - 131 of a run's 256 sets have such a margin).
ROUTE_MARGIN = 0.02
LIMITS = {
    "rms_over_std": LOGIT_RMS_TOL, "max_over_std": LOGIT_MAX_TOL,
    "token_margin_over_std": TOKEN_MARGIN_TOL,
    # share of the judged sets with a clear margin whose k experts differ
    # from the reference's own: sound 0 of 103 - 131, thirteen times;
    # `pad_advance` 0.206 - 0.267
    "route_flip_share": 0.08,
    # what a slot HOLDS against the reference's own (rms of the difference
    # over the rms of the reference's). The conv layers' two-row tails, by
    # conv layer in order: sound at most 0.0040, 0.0130, 0.0177, 0.0195,
    # 0.0217, 0.0226, 0.0237, 0.0254 (every layer within 12% over the
    # thirteen);
    # the probes' slots crossed 1.41 - 1.46 in every layer; `bias_in_weights`
    # 0.041 - 0.051 / 0.043 - 0.053 in the last two
    "tail_error": (0.007, 0.021, 0.029, 0.032, 0.035, 0.037, 0.039, 0.042),
    # the attention layers' rows of the prompt and of the tokens after it,
    # by attention layer (the first lies before every routed layer: no flip
    # reaches it). Overall: sound 0.0117 (to four digits, thirteen times) /
    # 0.0445 - 0.0475; e4m3 rows 0.0290 / -, `conv_break` - / 0.068 - 0.069,
    # `pad_advance` 0.042 - 0.044 / 0.063 - 0.066
    "page_rows_error": (0.018, 0.058),
    # at their worst row: sound 0.0145 - 0.0155 / 0.170 - 0.220 (the second
    # layer's worst row is one a flip before it touched); e4m3 rows 0.0332 /
    # -, `conv_break` and `pad_advance` 1.25 - 1.33 / 1.26 - 1.35
    "page_row_worst": (0.023, 0.5),
    # at their MEDIAN row (no flip touches it): sound 0.0116 - 0.0117 /
    # 0.0159 - 0.0162; e4m3 rows 0.0290 / 0.0316, `bias_in_weights` - /
    # 0.0287 - 0.0290 (at the seeded N(0, 0.1) bias; at N(0, 0.01) it was a
    # hundredth of every limit)
    "page_row_median": (0.018, 0.022),
    # a filler's conv tails after its 128 one-position updates: sound at
    # most 0.0040, 0.0121, 0.0169, 0.0191, 0.0201, 0.0216, 0.0231, 0.0245;
    # `bias_in_weights` 0.039 - 0.043 / 0.043 - 0.045 in the last two,
    # `routes_free` up to 0.21
    "filler_tail_error": (0.007, 0.021, 0.029, 0.032, 0.035, 0.037, 0.039,
                          0.042),
}
# Held to nothing, printed beside them: a bfloat16 ROUTER (matmul and scores)
# reads as the sound run does in every set (logits 0.027 - 0.035, no set
# with a clear margin flipped, the median row 0.0159 - 0.0161): its scores
# move by ~0.002, a tenth of the margin a judged set must have, and every
# nearer tie already turns on the bf16 activations before it. The issue
# asked for a limit it breaks; there is none to set.


def limits(worst: dict, slack: float = 1.0) -> dict:
    """The limits of the readings ``worst`` holds; a layer-by-layer limit
    cut to the layers a (toy) model has. ``slack``: a rehearsal's (a toy 64
    wide averages its rounding over a thirtieth of the elements; its
    readings set no limit)."""
    return {name: [v * slack for v in limit[:len(worst[name])]]
            if isinstance(limit, tuple) else limit * slack
            for name, limit in LIMITS.items() if name in worst}


QUERY_BLOCK = 256       # queries attended at a time
ROW_BLOCK = 2048        # positions a dense ffn takes at a time
VOCAB_STEP = 16384      # vocabulary rows per head matmul
CONV, FULL = "conv", "full_attention"


def geometry(cfg) -> dict:
    """What the reference needs of a ``Lfm2MoeConfig`` (plain numbers; the
    reference imports nothing of the program)."""
    return {"kinds": tuple(cfg.layer_types), "n_dense": cfg.n_dense_layers,
            "n_head": cfg.n_head, "n_kv_head": cfg.n_kv_head,
            "head_dim": cfg.hidden_size // cfg.n_head,
            "taps": cfg.conv_L_cache, "theta": cfg.rope_theta,
            "eps": cfg.norm_eps, "n_experts": cfg.n_experts,
            "top_k": cfg.n_experts_per_tok, "norm_topk": cfg.norm_topk_prob,
            "route_scale": cfg.routed_scaling_factor,
            "expert_bias": cfg.use_expert_bias,
            # the published renormalisation's epsilon
            "route_eps": 1e-6,
            # the planted faults, as data (module docstring)
            "conv_break": 0, "pad_advance": False, "bias_in_weights": False,
            # ONE thing in the nearest precision below the configuration's
            # (the controls): the K/V rows as the pool would keep them
            # (``float8_e4m3fn``: ``--kv-cache-dtype fp8``), the router's
            # matmul and scores (``bfloat16``)
            "kv_dtype": None, "router_dtype": "float32"}


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def layer_params(params: dict, geom: dict, n: int) -> dict:
    """Layer ``n``'s leaves out of the stored stacks: the run that holds
    it starts at the last change of (layer type, routed) at or before
    ``n``."""
    kinds, dense = geom["kinds"], geom["n_dense"]
    first = n
    while first > 0 and (kinds[first - 1], first - 1 >= dense) == (
            kinds[n], n >= dense):
        first -= 1
    run, at = params[f"run_{first}"], n - first
    blk = {k: v[at] for k, v in run.items() if k not in EXPERT_LEAVES}
    if EXPERT_LEAVES[0] in run:
        # a run's stacked expert matrices are handed on WHOLE beside the
        # layer's place in them (:func:`routed_ffn` picks one expert's out
        # of them inside its program): a slice of them here is a 1.2 GB
        # copy a layer beside a program that fills the chip
        blk["experts"] = tuple(run[k] for k in EXPERT_LEAVES)
        blk["at"] = np.int32(at)
    return blk


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * _f32(scale)


def swiglu(h, w1, w3, w2):
    return (_silu(h @ _f32(w1)) * (h @ _f32(w3))) @ _f32(w2)


def routed_ffn(h, experts, at, ids, weights):
    """The routed layer over one sequence ``h`` (L, d): a plain loop over
    ALL the experts, each over EVERY position, times the position's weight
    for it (zero where the router did not choose it: sixteen times the
    work of the chosen four, seconds on the chip, and ONE program a
    sequence length where a loop over each expert's own rows was a gather,
    a program call and a scatter an expert a layer on the host).
    ``experts``: the run's stacked ``(w_gate, w_up, w_down)``, ``(layers,
    experts, ., .)``, and ``at``: this layer's place in them."""
    def one(e, y):
        picked = (jax.lax.dynamic_slice(
            w, (at, e, 0, 0), (1, 1) + w.shape[2:])[0, 0] for w in experts)
        share = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        return y + share[:, None] * swiglu(h, *picked)

    return jax.lax.fori_loop(0, experts[0].shape[1], one, jnp.zeros_like(h))


def conv_mixer(h, w_in, conv_w, w_out, lost, *, taps):
    """One sequence ``h`` (L, d). ``lost`` (L,) bool: positions BEFORE
    which the tail is lost (the planted faults; all False: the published
    form). Returns ``(out, the last taps - 1 rows of g)``."""
    length = h.shape[0]
    gate_b, gate_c, u = jnp.split(h @ _f32(w_in), 3, axis=-1)
    g = gate_b * u
    w = _f32(conv_w)
    t = jnp.arange(length)
    # the last position at or before t where the tail was lost
    floor = jax.lax.cummax(jnp.where(lost, t, 0))
    c = jnp.zeros_like(g)
    for j in range(taps):
        back = taps - 1 - j
        seen = (t - back >= floor)[:, None]     # and t - back >= 0
        c = c + w[j] * jnp.where(seen, jnp.roll(g, back, axis=0), 0.0)
    return (gate_c * c) @ _f32(w_out), g[-(taps - 1):]


def rotary(x, first, *, theta):
    """Half-split rotary of ``x`` (L, heads, hd) at positions ``first +
    0..``."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = (first + jnp.arange(x.shape[0], dtype=jnp.float32))[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def qkv(h, blk, *, geom):
    """``q`` (L, H, hd), ``k``, ``v`` (L, Hk, hd) of one sequence, QK-norm
    and rotary applied."""
    hd, nq, nk = geom["head_dim"], geom["n_head"], geom["n_kv_head"]
    q = (h @ _f32(blk["w_q"])).reshape(-1, nq, hd)
    k = (h @ _f32(blk["w_k"])).reshape(-1, nk, hd)
    v = (h @ _f32(blk["w_v"])).reshape(-1, nk, hd)
    q = rms_norm(q, blk["q_norm"], geom["eps"])
    k = rotary(rms_norm(k, blk["k_norm"], geom["eps"]), 0,
               theta=geom["theta"])
    if geom["kv_dtype"]:    # the control: what such a pool would hold
        k, v = (t.astype(geom["kv_dtype"]).astype(jnp.float32)
                for t in (k, v))
    return rotary(q, 0, theta=geom["theta"]), k, v


def attend(q, k, v, first):
    """Causal softmax of a block of queries ``q`` (Lq, H, hd) at positions
    ``first + 0..`` over ``k``, ``v`` (L, H, hd) (K/V heads already repeated
    to the query heads)."""
    i = first + jnp.arange(q.shape[0])[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    s = jnp.einsum("qhd,khd->hqk", q, k) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where((j <= i)[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v)


def router(h, w_g, bias, forced, *, geom):
    """``(ids (L, k), weights (L, k), own (L, k), margin (L,))``: the
    experts the layer computes, their weights, the reference's OWN choice
    and the gap between its k-th and (k+1)-th biased score. ``forced`` (n,
    k) or None: the experts of the LAST n positions are these (the
    program's there), weighed by the reference's own scores."""
    k = geom["top_k"]
    low = jnp.dtype(geom["router_dtype"])
    s = jax.nn.sigmoid(h.astype(low) @ jnp.asarray(w_g).astype(low))
    s = s.astype(jnp.float32)
    biased = s + _f32(bias) if geom["expert_bias"] else s
    top, own = jax.lax.top_k(biased, k + 1)
    own = ids = own[:, :k]
    if forced is not None:
        ids = own.at[own.shape[0] - forced.shape[0]:].set(forced)
    # the published form weighs by the UNBIASED scores (the planted fault:
    # by the biased ones)
    w = jnp.take_along_axis(biased if geom["bias_in_weights"] else s, ids,
                            axis=-1)
    if geom["norm_topk"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + geom["route_eps"])
    return ids, w * geom["route_scale"], own, top[:, k - 1] - top[:, k]


class Reference:
    """``geom``: :func:`geometry` of the configuration."""

    def __init__(self, geom: dict):
        self.geom = geom
        self._norm = jax.jit(functools.partial(rms_norm, eps=geom["eps"]))
        self._swiglu = jax.jit(swiglu)
        self._routed = jax.jit(routed_ffn)
        self._conv = jax.jit(functools.partial(conv_mixer,
                                               taps=geom["taps"]))
        self._qkv = jax.jit(functools.partial(qkv, geom=geom))
        self._attend = jax.jit(attend)
        self._router = jax.jit(functools.partial(router, geom=geom))
        self._dot = jax.jit(lambda x, w: x @ _f32(w))
        self._head = jax.jit(lambda x, rows: x @ _f32(rows).T)

    def attention(self, h, blk):
        """``(out, (k rows, v rows) as the pool keeps them: flat)``."""
        g = self.geom
        q, k, v = self._qkv(h, blk)
        rep = g["n_head"] // g["n_kv_head"]
        kr, vr = (jnp.repeat(t, rep, axis=1) for t in (k, v))
        out = jnp.concatenate([
            self._attend(q[i:i + QUERY_BLOCK], kr, vr, i)
            for i in range(0, q.shape[0], QUERY_BLOCK)])
        rows = tuple(np.asarray(t).reshape(t.shape[0], -1) for t in (k, v))
        return self._dot(out.reshape(out.shape[0], -1), blk["w_o"]), rows

    def routed(self, h, blk, forced=None):
        """``(y, the reference's own choice, its margin)`` of one routed
        layer."""
        ids, w, own, margin = self._router(h, blk["router"],
                                           blk["expert_bias"], forced)
        y = self._routed(h, blk["experts"], blk["at"], ids, w)
        return y, np.asarray(own), np.asarray(margin)

    def logits(self, params: dict, ids, last: int = 1,
               stores: dict | None = None, prompt: int | None = None,
               forced=None):
        """Logits (last, vocab) of the last ``last`` positions of ONE
        sequence ``ids``. ``stores`` (a dict to fill): what every caching
        layer holds after the sequence, in layer order: ``"tail"`` (2, d) of
        each conv layer, ``"pages"`` ``(k, v)`` of EVERY position of each
        attention layer, flat rows as the pool keeps them; and ``"route"``,
        by routed layer, the reference's OWN ``(ids (last, k), margin
        (last,))`` at the judged positions. ``prompt``: where the prompt
        ends (the planted ``pad_advance``'s position). ``forced`` (routed
        layers, n, k): the last n positions COMPUTE these experts (the
        program's there: module docstring)."""
        g = self.geom
        keep = stores if stores is not None else {}
        n = len(ids)
        lost = np.zeros((n,), bool)
        if g["conv_break"]:
            lost[g["conv_break"]::g["conv_break"]] = True
        if g["pad_advance"] and prompt is not None and prompt < n:
            lost[prompt] = True
        with jax.default_matmul_precision("highest"):
            x = _f32(params["tok_embed"][jnp.asarray(ids)])
            for i, kind in enumerate(g["kinds"]):
                blk = layer_params(params, g, i)
                h = self._norm(x, blk["op_norm"])
                if kind == CONV:
                    mixed, tail = self._conv(h, blk["w_in"], blk["conv_w"],
                                             blk["w_out"], jnp.asarray(lost))
                    keep.setdefault("tail", []).append(np.asarray(tail))
                else:
                    mixed, rows = self.attention(h, blk)
                    keep.setdefault("pages", []).append(rows)
                x = x + mixed
                h = self._norm(x, blk["ffn_norm"])
                if i < g["n_dense"]:
                    x = x + jnp.concatenate([
                        self._swiglu(h[j:j + ROW_BLOCK], blk["w1"],
                                     blk["w3"], blk["w2"])
                        for j in range(0, n, ROW_BLOCK)])
                else:
                    at = len(keep.setdefault("route", []))
                    y, chosen, margin = self.routed(
                        h, blk, None if forced is None
                        else jnp.asarray(forced[at], jnp.int32))
                    keep["route"].append(
                        (chosen[n - last:], margin[n - last:]))
                    x = x + y
            x = self._norm(x[n - last:], params["ln_f"])
            rows = params["tok_embed"]
            out = [np.asarray(self._head(x, rows[i:i + VOCAB_STEP]))
                   for i in range(0, rows.shape[0], VOCAB_STEP)]
        return np.concatenate(out, axis=-1)


def logit_error(got: np.ndarray, want: np.ndarray) -> dict:
    """rms and worst difference in units of the reference logits' spread."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape or not np.isfinite(got).all():
        return {"why": f"shape {got.shape} vs {want.shape} or non-finite "
                       "logits"}
    spread = float(np.std(want))
    return {"rms_over_std": float(np.sqrt(np.mean((got - want) ** 2)))
            / spread,
            "max_over_std": float(np.max(np.abs(got - want))) / spread}


def store_error(got, want) -> float:
    """rms of ``got - want`` over the rms of ``want``: how far a store of
    the program (a tail, the pool's rows) lies from the reference's."""
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def worst_row_error(got, want) -> float:
    """The largest rms of ONE row's difference over the rms of ``want``:
    a fault at a few positions (a chunk's boundary) that
    :func:`store_error` would average away over thousands of rows."""
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    return float(np.sqrt(np.max(np.mean((got - want) ** 2, axis=-1))
                         / np.mean(want ** 2)))


def median_row_error(got, want) -> float:
    """The MEDIAN row's rms difference over the rms of ``want``: what the
    arithmetic costs a row that no routing flip before it touched (a flip
    moves a quarter of a layer's output at its position, and more than
    half of a prompt's rows meet none), so a routed layer that weighs its
    experts wrongly at EVERY position shows here and a flip does not."""
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    return float(np.sqrt(np.median(np.mean((got - want) ** 2, axis=-1))
                         / np.mean(want ** 2)))


def token_margin(want: np.ndarray, tokens: list[int]) -> float:
    """``want`` (n, vocab): the reference's logits at the positions that
    produced ``tokens`` (teacher forcing). The largest gap between a token's
    reference logit and the reference's best there, in spreads."""
    want = np.asarray(want, np.float64)
    spread = float(np.std(want))
    return max(float(want[i].max() - want[i, t]) / spread
               for i, t in enumerate(tokens))


def route_flips(got, want) -> tuple[int, int]:
    """``(flipped, judged)``: of the routed sets ``got`` (layers, positions,
    k) the program chose, how many differ as SETS from the reference's
    ``want`` (by layer: ``(ids, margin)``) among those whose reference
    margin is clear (:data:`ROUTE_MARGIN`)."""
    flipped = judged = 0
    for mine, (ids, margin) in zip(got, want):
        for a, b, m in zip(np.asarray(mine), ids, margin):
            if m > ROUTE_MARGIN:
                judged += 1
                flipped += set(a.tolist()) != set(b.tolist())
    return flipped, judged
