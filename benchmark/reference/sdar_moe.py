"""Plain reference for SDAR-MoE (``model_type`` ``sdar_moe``,
huggingface.co/JetLM/SDAR-30B-A3B-Chat): the published equations in
``jax.numpy``, float32 at ``highest`` matmul precision, the full forward
of ONE whole sequence, a plain loop over experts, no cache, no paging, no
kernel, no batching. From the program it takes nothing but the weights
(a nested dict of arrays, whatever dtype: each is read up to float32
where it is used, one layer and one expert at a time, so the 6-layer
serving cut fits beside the engine).

Equations. Per layer, with ``u = RMSNorm(x)``:

    h = x + Attn(u)
    y = h + MoE(RMSNorm(h))

``Attn``: q/k/v projections without bias, ``n_head`` query and
``n_kv_head`` key/value heads of ``head_dim``; RMSNorm over ``head_dim``
on every q and k head; rotate-half RoPE (theta ``rope_theta``) at
ABSOLUTE positions; softmax attention under the BLOCK-CAUSAL mask, query
``i`` sees key ``j`` iff ``j // B <= i // B`` (bidirectional inside a
block of B positions, causal across blocks); output projection.

``MoE(v)``: ``r = W_g v`` in float32 over all E experts; ``p =
softmax(r)``; ``S = top_k(p)``; ``w_e = p_e / sum_{j in S} p_j``
(``norm_topk_prob``); ``MoE(v) = sum_{e in S} w_e W_down,e (silu(W_gate,e
v) * W_up,e v)``. Every layer is sparse (``decoder_sparse_step`` 1,
``mlp_only_layers`` []); there is no shared expert.

Final RMSNorm, then an UNTIED head. The logits at position ``i`` predict
token ``i`` itself (no shift): a position fed ``<|MASK|>`` is denoised in
place.

Generation (the family's ``block_diffusion_generate``) is not re-run
here: the engine keeps each request's reveal log, and :func:`block_inputs`
gives the reference the block exactly as the engine's pass saw it
(teacher forcing), so that every pass is judged on its own.

Departures from the published model, shared with
``benchmark/configs/sdar-30b-a3b-bf16-serve.json``: seeded random weights;
``num_hidden_layers`` 6 of 48; block length, denoising steps, threshold
and mask id are the family's inference defaults as recalled (the
``config.json`` gives none). The candidate's confidence is its
probability under ``softmax(logits)``, also when the request samples with
a temperature (the family's sampler reads it off the tempered
distribution).

**Routing is discrete.** The engine routes on bf16 activations, the
reference on float32 ones, and top-k flips where the k-th and (k+1)-th
probabilities nearly tie. Where the engine's expert set for a (token,
layer) of the judged block differs from the reference's, and every
expert the engine chose instead lies within ``ROUTE_MARGIN`` of the
reference's k-th best (``p_e >= p_kth * (1 - ROUTE_MARGIN)``), the
reference takes the ENGINE's set for that pair (weights renormalised
from its own probabilities), so that a benign flip is not read as a
logit error. A flip outside the margin fails the comparison. Nowhere
else is the routing forced.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Every limit below lies between two chip readings (my chip runs, PR 29):
# the largest the engine gave over 22 runs at the configuration's own
# precision (bf16 weights, activations and K/V; float32 router and
# logits) against float32 `highest` of the SAME bf16 weights, and what the
# nearest precision below, fp8 (e4m3) K/V, gave on one of those seeds. fp8
# K/V fails by every one of them.
#
# Logits of the block program, in units of the reference logits' standard
# deviation. The roundings (2^-9 relative at each of ~12 tensors a layer)
# add like a random walk over depth: at 6 layers rms 0.0073-0.0113, max
# over 4 x 151,936 logits and 37 passes 0.038-0.059 (~5 sigma of the rms).
# fp8 K/V: rms 0.038, max 0.213.
LOGIT_RMS_TOL = 0.02
LOGIT_MAX_TOL = 0.1
# A revealed token's reference logit may trail the reference's best at its
# position by this much of the rows' spread: the engine picks another
# candidate only where its error on the DIFFERENCE of two logits (about
# 1.4 x the rms above, 0.013) exceeds their gap. Read 0.0 in most runs and
# up to 0.018; fp8 K/V 0.051. 0.04 is three sigma of that difference.
TOKEN_MARGIN_TOL = 0.04
# The revealed position's reference log-confidence may trail the bar the
# masked positions' best confidences set by this much of the spread (a
# confidence is exp(logit - logsumexp): its log carries the logit's error).
# Read 0.0007-0.018; fp8 K/V 0.068.
CONF_MARGIN_TOL = 0.045
# A differing expert must lie this close (relatively) below the
# reference's k-th best probability: router logits carry the hidden
# state's bf16 error times |W_g| sqrt(hidden), ~0.01-0.03, and p ~ exp(r).
# Worst shortfall read 0.019-0.043; fp8 K/V 0.124 (8 pairs outside 0.08).
ROUTE_MARGIN = 0.08
# and no more than this share of the judged (token, layer) pairs may flip
# at all: read 4.1-8.0% of 888 pairs (top-8 of 128 near-tied softmax
# probabilities under seeded weights); fp8 K/V 22.0%.
ROUTE_FLIP_SHARE_TOL = 0.13


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def attention_and_router(x, block, geom: dict):
    """``h = x + Attn(RMSNorm(x))`` over the whole sequence, then the
    router's probabilities of ``v = RMSNorm(h)``. Returns ``(h, v, p)``."""
    n_head, n_kv, hd = geom["n_head"], geom["n_kv_head"], geom["head_dim"]
    eps, theta, blk = (geom["rms_norm_eps"], geom["rope_theta"],
                       geom["block_length"])
    attn = block["attn"]
    n = x.shape[0]
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    cos, sin = jnp.cos(pos * inv)[:, None], jnp.sin(pos * inv)[:, None]
    u = _rms(x, block["ln1"]["scale"], eps)
    q = (u @ _f32(attn["q_proj"]["kernel"])).reshape(n, n_head, hd)
    k = (u @ _f32(attn["k_proj"]["kernel"])).reshape(n, n_kv, hd)
    v = (u @ _f32(attn["v_proj"]["kernel"])).reshape(n, n_kv, hd)
    q = _rope(_rms(q, attn["q_norm"]["scale"], eps), cos, sin)
    k = _rope(_rms(k, attn["k_norm"]["scale"], eps), cos, sin)
    k = jnp.repeat(k, n_head // n_kv, axis=1)
    v = jnp.repeat(v, n_head // n_kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    i = jnp.arange(n)
    sees = (i[None, :] // blk) <= (i[:, None] // blk)     # [query, key]
    s = jnp.where(sees, s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    h = x + a.reshape(n, -1) @ _f32(attn["out_proj"]["kernel"])
    hn = _rms(h, block["ln2"]["scale"], eps)
    p = jax.nn.softmax(hn @ _f32(block["moe"]["router"]), axis=-1)
    return h, hn, p


def experts(h, hn, weights, moe):
    """``h + sum_e weights[:, e] * down_e(silu(gate_e(hn)) * up_e(hn))``:
    a plain loop over the experts, each read up to float32 on its turn;
    ``weights`` (n, E) holds 0 for the experts a token did not choose."""
    def one(e, acc):
        gate = hn @ _f32(moe["w_gate"][e])
        up = hn @ _f32(moe["w_up"][e])
        out = (jax.nn.silu(gate) * up) @ _f32(moe["w_down"][e])
        return acc + weights[:, e][:, None] * out

    return h + jax.lax.fori_loop(0, moe["w_gate"].shape[0], one,
                                 jnp.zeros_like(h))


def choose(p: np.ndarray, top_k: int, norm: bool,
           engine_sets: np.ndarray | None, last: int):
    """The reference's expert sets and weights for probabilities ``p``
    (n, E), as a dense (n, E) weight matrix; and what it found at the
    last ``last`` positions where ``engine_sets`` (last, k) differ (see
    the module docstring)."""
    n, _ = p.shape
    order = np.argsort(-p, axis=1, kind="stable")
    sets = order[:, :top_k].copy()
    kth = np.take_along_axis(p, order[:, top_k - 1:top_k], axis=1)[:, 0]
    found = {"pairs": 0, "flipped": 0, "outside_margin": 0,
             "worst_shortfall": 0.0}
    if engine_sets is not None:
        found["pairs"] = last
        for t in range(last):
            row = n - last + t
            mine, theirs = set(sets[row].tolist()), set(
                int(e) for e in engine_sets[t])
            if mine == theirs:
                continue
            found["flipped"] += 1
            short = max(1.0 - p[row, e] / kth[row] for e in theirs - mine)
            found["worst_shortfall"] = max(found["worst_shortfall"],
                                           float(short))
            if short <= ROUTE_MARGIN and len(theirs) == top_k:
                sets[row] = sorted(theirs)
            else:
                found["outside_margin"] += 1
    w = np.take_along_axis(p, sets, axis=1)
    if norm:
        w = w / w.sum(axis=1, keepdims=True)
    dense = np.zeros_like(p)
    np.put_along_axis(dense, sets, w, axis=1)
    return dense, found


class Reference:
    """``geom``: ``n_head``, ``n_kv_head``, ``head_dim``, ``rms_norm_eps``,
    ``rope_theta``, ``block_length``, ``top_k``, ``norm_topk``."""

    VOCAB_STEP = 16384                  # vocabulary columns per head matmul

    def __init__(self, geom: dict):
        self.geom = geom
        self._attn = jax.jit(functools.partial(attention_and_router,
                                               geom=geom))
        self._experts = jax.jit(experts)
        eps = geom["rms_norm_eps"]

        @jax.jit
        def head(x, scale, cols):
            return _rms(x, scale, eps) @ cols.astype(jnp.float32)

        self._head = head

    def logits(self, params: dict, ids, last: int = 1,
               engine_experts: np.ndarray | None = None):
        """Logits (last, vocab) of the last ``last`` positions of ONE
        sequence ``ids`` under the block-causal mask, and what the
        routing comparison found. ``engine_experts`` (layers, last, k):
        the experts the engine chose at those positions, or None."""
        n_layer = sum(1 for k in params if k.startswith("block_"))
        found = {"pairs": 0, "flipped": 0, "outside_margin": 0,
                 "worst_shortfall": 0.0}
        with jax.default_matmul_precision("highest"):
            x = _f32(params["tok_embed"]["embedding"][jnp.asarray(ids)])
            for i in range(n_layer):
                block = params[f"block_{i}"]
                h, hn, p = self._attn(x, block)
                dense, f = choose(
                    np.asarray(p), self.geom["top_k"],
                    self.geom["norm_topk"],
                    None if engine_experts is None else engine_experts[i],
                    last)
                for key in ("pairs", "flipped", "outside_margin"):
                    found[key] += f[key]
                found["worst_shortfall"] = max(found["worst_shortfall"],
                                               f["worst_shortfall"])
                x = self._experts(h, hn, jnp.asarray(dense), block["moe"])
            x = x[-last:]
            head = params["lm_head"]
            out = [np.asarray(self._head(
                x, params["ln_f"]["scale"], head[:, i:i + self.VOCAB_STEP]))
                for i in range(0, head.shape[1], self.VOCAB_STEP)]
        return np.concatenate(out, axis=-1), found


def block_inputs(prompt: list[int], reveal_log: list, block_length: int,
                 mask_id: int, block: int, upto_pass: int):
    """The sequence the engine's pass ``upto_pass`` of generated block
    ``block`` saw, rebuilt from the request's reveal log: the prompt, the
    earlier blocks whole, and the block itself with the positions
    revealed in EARLIER passes (and, in block 0, the prompt's remainder)
    in place and ``mask_id`` elsewhere. Returns ``(ids, revealed)``."""
    B = block_length
    whole = len(prompt) // B * B
    ids = list(prompt[:whole])
    for b in range(block + 1):
        cur = [mask_id] * B
        rev = [False] * B
        if b == 0:
            for j, t in enumerate(prompt[whole:]):
                cur[j], rev[j] = t, True
        for (lb, lp, pos, tok) in reveal_log:
            if lb == b and (b < block or lp < upto_pass):
                cur[pos], rev[pos] = tok, True
        if b < block and not all(rev):
            raise ValueError(f"reveal log leaves block {b} unfinished")
        ids += cur
    return ids, rev


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


def reveal_margins(want: np.ndarray, revealed_before: list[bool],
                   revealed_now: list[tuple[int, int]]) -> dict:
    """One denoise pass against the reference's logits ``want`` (B, vocab)
    of the same input block. ``revealed_now``: (position, token) the
    engine revealed in this pass. Token margin: how far the token's
    reference logit trails the row's best; confidence margin: how far the
    position's reference log-confidence trails the bar set by the masked
    positions' best confidences; both in units of the rows' spread."""
    want = np.asarray(want, np.float64)
    spread = float(np.std(want))
    lse = np.log(np.exp(want - want.max(1, keepdims=True)).sum(1)) \
        + want.max(1)
    # the bar: with q positions revealed in the pass, the q-th most
    # confident masked position
    bar = sorted((want[j].max() - lse[j]
                  for j in range(len(revealed_before))
                  if not revealed_before[j]), reverse=True)
    best_conf = bar[min(len(revealed_now), len(bar)) - 1]
    tok_gap = conf_gap = 0.0
    for pos, tok in revealed_now:
        tok_gap = max(tok_gap, (want[pos].max() - want[pos, tok]) / spread)
        conf_gap = max(conf_gap,
                       (best_conf - (want[pos, tok] - lse[pos])) / spread)
    return {"token_margin_over_std": float(tok_gap),
            "conf_margin_over_std": float(conf_gap)}
