"""Plain reference for Phi-4-mini-flash-reasoning (``model_type``
``phi4flash``, huggingface.co/microsoft/Phi-4-mini-flash-reasoning; SambaY
arXiv:2507.06607, Samba arXiv:2406.07522, YOCO arXiv:2405.05254,
Differential Transformer arXiv:2410.05258, Mamba arXiv:2312.00752): the
equations below in ``jax.numpy``, float32 at ``highest`` matmul precision,
ONE whole sequence, the scan as a plain sequential recurrence (a
``lax.scan`` over positions), attention as a masked dense softmax a block
of queries at a time, the cross-decoder at every position it is asked for;
no cache, ring, page, kernel, chunk or skip. From the program it takes
nothing but the weights (a nested dict of arrays, whatever dtype: each is
read up to float32 where it is used).

Equations (``d`` hidden, ``half = layers / 2``, LayerNorm with scale and
bias, eps 1e-5). ``x0 = E[ids]`` (unscaled, no position anywhere). Layer
``n``: ``x <- x + mixer_n(LN_a(x))``; ``x <- x + MLP(LN_b(x))``, ``MLP(h) =
(u * silu(g)) W2`` with ``[g ‖ u] = h W1``. After the last layer a final
LayerNorm; ``logits = x E^T``.

Kinds: ``n`` even, ``n <= half``: MAMBA; ``n`` odd, ``n < half``: WINDOW
attention; ``n = half + 1``: FULL attention; ``n`` even above: GMU; ``n``
odd above: CROSS attention over layer ``half + 1``'s keys and values.

MAMBA (``d_inner = 2d``, ``N`` = 16, 4 taps, ``R = ceil(d / 16)``): ``[xs ‖
z] = h W_in``; ``xc_t = silu(b_c + sum_{i<4} w_c[i] * xs_{t-3+i})`` (zeros
before the sequence); ``[delta ‖ B_t ‖ C_t] = xc_t W_x``; ``dt_t =
softplus(delta W_dt + b_dt)``; ``A = -exp(A_log)``; ``S_t = exp(dt_t A) *
S_{t-1} + (dt_t * xc_t) B_t`` (``S_{-1} = 0``); ``y_t = S_t C_t + D *
xc_t``; ``out_t = (y_t * silu(z_t)) W_out``. Layer ``half`` exports ``m_t
= y_t``. GMU: ``out_t = (m_t * silu(h_t W_1)) W_2``.

DIFFERENTIAL ATTENTION (``H`` query heads, ``Hk`` K/V heads of ``hd = d /
H``): ``[q ‖ k ‖ v] = h W_qkv + b`` (a cross layer: ``q`` alone). Pair
``p``: ``q1 = `` head ``2p``, ``q2 = `` head ``2p + 1``. K/V pair ``j``:
``k1 = `` head ``2j``, ``k2 = `` head ``2j + 1``, ``v_j = [v_{2j} ‖
v_{2j+1}]``. Pair ``p`` reads K/V pair ``p // (H / Hk)``. ``a_i =
softmax(q_i k_i^T / sqrt(hd) + mask) v``; mask: ``j <= i`` and, in a
window layer, ``i - j < window``. ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) +
lam_init``, ``lam_init = 0.8 - 0.6 exp(-0.3 n)``. ``o_p = RMSNorm_{2hd}(a_1
- lam a_2) * scale * (1 - lam_init)``; ``out = [o_0 ‖ ...] W_o + b_o``.

Storage (shared with the program, like the weights): ``w_qkv`` is ``[q ‖ k
‖ v]`` by columns; ``a_log`` is ``(N, d_inner)``, ``conv_w`` ``(4,
d_inner)``; the layers' leaves are STACKED by kind (:func:`layer_params`
names the stack and the index of layer ``n``).

The geometry carries the forms as data: ``tests/test_phi4flash.py`` hands
the comparison a reference with ONE of them left out (``lambda_learned``,
``subln``, ``gmu_memory``, ``d_skip``, ``window_mask``, ``conv_break``: the
convolution's tail dropped at every multiple of a chunk; ``memory_shift``:
the GMUs read ``m`` of the token that many positions EARLIER, a
cross-decoder run at the wrong row) and must see it fail;
``tools/swa_check_control.py --faults`` does the same at the cell's size,
on the chip, through the cell's own ``check``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# Two chip readings stand behind each limit (my chip runs, PR 47, the
# readings call of the second session: `tools/swa_check_control.py --cell
# phi-4-mini-flash.grounded-reasoning`, every slot live; PERF.md section 6).
# SOUND: the engine at the configuration's precision (bf16 weights,
# activations, pages, rings and convolution tails; a float32 recurrent
# state; float32 logits) against float32 `highest` of the SAME bf16 weights:
# the largest of 8 sets of probes on one server, the 6 on the control
# servers for the stores a control does not lower, and the final tree's
# three runs of the cell at weight seeds of their own. UPPER: the least
# reading of what the limit is there to catch: a CONTROL, the nearest
# precision below in ONE store through the engine (`--kv-cache-dtype fp8`:
# pages and rings in e4m3; `--ssm-state-dtype bfloat16`), three sets of
# probes each; or a FAULT, the same observation judged against the
# reference with one form left out (`--faults`: lambda, the sub-norm, the
# GMU's memory, the memory of the token before, the D skip, the window, a
# convolution tail dropped at a chunk's boundary) or with the two probes'
# slots crossed. Every control and every fault breaks at least one limit
# that stands 1.5 times or more from both its readings.
#
# Logits at one position, in units of the reference logits' spread there:
# sound rms 0.0450 .. 0.0551, max 0.212 .. 0.2695 (the controls read the
# same: 32 layers of single-pass bf16 matmuls put a floor of 0.033 under
# them, which no store's precision moves) | the least fault they catch, the
# learned lambda left out: 0.132 / 0.621; the memory of the token before
# 0.203 / 0.902; the GMU fed no memory 0.411 / 1.97; the sub-norm, the D
# skip, the window 1.17 .. 1.44 / 5.1 .. 7.4. NOT caught by them: a
# convolution tail dropped at a chunk's boundary (0.0517 / 0.249: three
# positions of 7,168) and crossed slots (the logits are the program's own).
LOGIT_RMS_TOL = 0.085
LOGIT_MAX_TOL = 0.4
# A greedy token's reference logit may trail the reference's best at its
# position by this much of the row's spread: sound 0.031 .. 0.146 (the
# largest of 32 near-ties; the fp8 control 0.091 .. 0.235: not separated) |
# the memory of the token before 0.452, the GMU fed no memory 1.18, the
# others 5.3 .. 6.2 (lambda left out 0.243: not separated, the logits'
# limits catch it). A near-tie at 0.3 is a 4-sigma event of a sound run
# (sigma of a logit pair's error 0.07): the limit stands above that.
TOKEN_MARGIN_TOL = 0.35
# a filler's state is judged over the elements that remember this many
# one-position updates (:func:`long_memory`)
SLOW_HORIZON = 128
# What a slot HOLDS against the reference's own (rms of the difference over
# the rms of the reference's), layer by layer, each limit TWICE the largest
# sound reading of its layer (the error of the layers before a store adds
# up with depth): any structural fault reads 15 times the sound or more
# (crossed slots 1.07 .. 1.47 in every layer of every store; the D skip
# 1.10 .. 1.43; the sub-norm and the window 0.52 .. 1.34 from the second
# layer of a kind on), lambda left out 1.5 .. 3.4 times (it breaks the
# limits of layers 4 - 16). The largest sound reading: over the readings
# call's 8 sets and the final tree's 3 runs. Two first-layer limits do a
# control's work:
LIMITS = {
    "rms_over_std": LOGIT_RMS_TOL, "max_over_std": LOGIT_MAX_TOL,
    "token_margin_over_std": TOKEN_MARGIN_TOL,
    # sound 0.0036 .. 0.0041, 0.014 .. 0.019, 0.020 .. 0.025, 0.017 ..
    # 0.029, ... 0.038 .. 0.051 (layer 16)
    "state_error": (0.0082, 0.039, 0.050, 0.058, 0.074, 0.086, 0.086,
                    0.097, 0.103),
    # sound 0.00234 .. 0.00239, 0.0118 .. 0.0123, ... 0.0324 .. 0.0369
    "tail_error": (0.0048, 0.025, 0.035, 0.042, 0.050, 0.056, 0.062,
                   0.068, 0.074),
    # layer 1: sound 0.00810 .. 0.00814 | fp8 K/V 0.02788 .. 0.02790 (3.4
    # times: the limit that makes that control come out not correct; the
    # error of ONE layer before the store is least there). Layers 3 .. 15:
    # sound 0.0143 .. 0.0339
    "rows_error": (0.015, 0.030, 0.039, 0.046, 0.052, 0.058, 0.063, 0.068),
    # the paged layer's rows of the prompt: sound 0.0348 .. 0.0359 | a
    # tail dropped at a chunk's boundary 0.083, lambda 0.093, fp8 0.047
    # (not separated there), crossed 1.41
    "page_rows_error": 0.055,
    # ... at their WORST row: sound 0.045 .. 0.051 | a tail dropped at a
    # chunk's boundary 1.43 (the rows at the boundary; nothing else sees
    # it), crossed 1.56
    "page_row_worst": 0.15,
    # a filler's first-layer state after 128 one-position updates, over
    # its long-memory elements: sound 0.00225 .. 0.00345 (14 runs) | a
    # bfloat16 state 0.0173 .. 0.0192 (5 times: a state rounded at every
    # update; the limit that makes that control come out not correct),
    # crossed 0.92. The same over the WHOLE state reads 0.0038 .. 0.0047 |
    # 0.0074 .. 0.0080 (1.6 times) and a probe's first-layer state 0.0041 |
    # 0.0058 (1.4 times): printed, held to no limit of a precision's.
    "filler_slow_state_error": 0.007,
}


def limits(worst: dict, slack: float = 1.0) -> dict:
    """The limits of the readings ``worst`` holds; a layer-by-layer limit
    cut to the layers a (toy) model has. ``slack``: a rehearsal's (a toy 64
    wide averages its rounding over a fortieth of the elements; its
    readings set no limit)."""
    return {name: [v * slack for v in limit[:len(worst[name])]]
            if isinstance(limit, tuple) else limit * slack
            for name, limit in LIMITS.items() if name in worst}


QUERY_BLOCK = 256       # queries attended at a time
ROW_BLOCK = 2048        # positions an MLP takes at a time
VOCAB_STEP = 16384      # vocabulary rows per head matmul
MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"


def geometry(cfg) -> dict:
    """What the reference needs of a ``Phi4FlashConfig`` (plain numbers;
    the reference imports nothing of the program)."""
    n, half = cfg.n_layer, cfg.n_layer // 2

    def kind(i):
        if i % 2 == 0:
            return MAMBA if i <= half else GMU
        return WINDOW if i < half else FULL if i == half + 1 else CROSS

    return {"kinds": tuple(kind(i) for i in range(n)),
            "n_head": cfg.n_head, "n_kv_head": cfg.n_kv_head,
            "head_dim": cfg.hidden_size // cfg.n_head,
            "window": cfg.window, "d_state": cfg.d_state,
            "d_conv": cfg.d_conv,
            "dt_rank": math.ceil(cfg.hidden_size / 16),
            "eps": cfg.layer_norm_eps,
            # the forms, as data
            "lambda_learned": True, "subln": True, "gmu_memory": True,
            "d_skip": True, "window_mask": True, "conv_break": 0,
            "memory_shift": 0,
            # float32; a lower one is the small control of the state's
            # precision (tests)
            "state_dtype": "float32"}


def layer_params(params: dict, kinds: tuple, n: int) -> dict:
    """Layer ``n``'s leaves out of the stored stacks: the (Mamba, window)
    pairs ``pair_mamba`` / ``pair_window`` (layers ``2i``, ``2i + 1``), the
    last Mamba layer ``mamba_last`` and the full layer ``full`` alone, the
    (GMU, cross) pairs ``cross_gmu`` / ``cross_attn``."""
    half, kind = len(kinds) // 2, kinds[n]
    if kind in (MAMBA, WINDOW) and n < half:
        stack, i = "pair_" + kind, n // 2
    elif kind in (MAMBA, FULL):
        stack, i = ("mamba_last" if kind == MAMBA else "full"), None
    else:
        stack, i = ("cross_gmu" if kind == GMU else "cross_attn",
                    (n - half - 2) // 2)
    leaves = params[stack]
    return leaves if i is None else {k: v[i] for k, v in leaves.items()}


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(scale) + _f32(bias)


def mlp(h, w1, w2):
    g, u = jnp.split(h @ _f32(w1), 2, axis=-1)
    return (u * _silu(g)) @ _f32(w2)


def mamba_inputs(h, blk, *, geom):
    """``(xc, z, dt, B, C, the last three rows of xs)`` of one sequence
    ``h`` (L, d)."""
    taps, n, rank = geom["d_conv"], geom["d_state"], geom["dt_rank"]
    length = h.shape[0]
    xs, z = jnp.split(h @ _f32(blk["w_in"]), 2, axis=-1)
    w = _f32(blk["conv_w"])
    t = jnp.arange(length)
    xc = _f32(blk["conv_b"])[None, :]
    for i in range(taps):
        back = taps - 1 - i
        src = jnp.where((t >= back)[:, None], jnp.roll(xs, back, axis=0), 0.0)
        if geom["conv_break"]:
            # the left-out form: a tail dropped at a chunk's boundary
            c = geom["conv_break"]
            src = jnp.where(((t - back) // c == t // c)[:, None], src, 0.0)
        xc = xc + w[i] * src
    xc = _silu(xc)
    dbc = xc @ _f32(blk["w_x"])
    delta, bmat, cmat = (dbc[:, :rank], dbc[:, rank:rank + n],
                         dbc[:, rank + n:])
    dt = jax.nn.softplus(delta @ _f32(blk["w_dt"]) + _f32(blk["b_dt"]))
    return xc, z, dt, bmat, cmat, xs[-(taps - 1):]


def recurrence(xc, dt, bmat, cmat, a_log, *, dtype):
    """``y_t = S_t C_t`` over one sequence, one position a step, and the
    state after the last; the state is kept in ``dtype`` between steps."""
    a = -jnp.exp(_f32(a_log))                               # (N, di)

    def step(s, at):
        x, d, b, c = at
        s = (jnp.exp(d[None, :] * a) * s.astype(jnp.float32)
             + (d * x)[None, :] * b[:, None])
        return s.astype(dtype), jnp.sum(s * c[:, None], axis=0)

    last, y = jax.lax.scan(step, jnp.zeros(a.shape, dtype),
                           (xc, dt, bmat, cmat))
    return y, last


def attend(q1, q2, k1, k2, v, first, *, window):
    """The two softmaxes of a block of queries at positions ``first +
    0..``: ``q_i`` (Lq, P, hd), ``k_i`` (L, P, hd), ``v`` (L, P, 2 hd)
    (K/V pairs already repeated to the query pairs). Returns ``a_1``,
    ``a_2`` (Lq, P, 2 hd)."""
    i = first + jnp.arange(q1.shape[0])[:, None]
    j = jnp.arange(k1.shape[0])[None, :]
    live = j <= i
    if window:
        live &= i - j < window
    out = []
    for q, k in ((q1, k1), (q2, k2)):
        s = jnp.einsum("qpd,kpd->pqk", q, k) * q.shape[-1] ** -0.5
        p = jax.nn.softmax(jnp.where(live[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("pqk,kpe->qpe", p, v))
    return out


class Reference:
    """``geom``: :func:`geometry` of the configuration."""

    def __init__(self, geom: dict):
        self.geom = geom
        eps = geom["eps"]
        self._ln = jax.jit(functools.partial(layer_norm, eps=eps))
        self._mlp = jax.jit(mlp)
        self._inputs = jax.jit(functools.partial(mamba_inputs, geom=geom))
        self._scan = jax.jit(functools.partial(
            recurrence, dtype=jnp.dtype(geom["state_dtype"])))
        self._attend = {w: jax.jit(functools.partial(attend, window=w))
                        for w in (0, geom["window"])}
        self._dot = jax.jit(lambda x, w: x @ _f32(w))
        self._head = jax.jit(lambda x, rows: x @ _f32(rows).T)

    # -- mixers (one sequence) ---------------------------------------------------

    def mamba(self, h, blk):
        """``(out, y before the gate, (the state, the convolution's tail)
        after the sequence)``."""
        xc, z, dt, bmat, cmat, tail = self._inputs(h, blk)
        y, state = self._scan(xc, dt, bmat, cmat, blk["a_log"])
        if self.geom["d_skip"]:
            y = y + _f32(blk["d_skip"]) * xc
        return self._dot(y * _silu(z), blk["w_out"]), y, (state, tail)

    def keys_values(self, h, blk):
        """Layer's ``k1``, ``k2`` (L, Pk, hd) and ``v`` (L, Pk, 2 hd) by K/V
        pair, and the same repeated to the query pairs."""
        g = self.geom
        hd, nq, nk = g["head_dim"], g["n_head"], g["n_kv_head"]
        kv = self._dot(h, blk["w_qkv"][:, nq * hd:]) + _f32(
            blk["b_qkv"][nq * hd:])
        k = kv[:, :nk * hd].reshape(-1, nk // 2, 2, hd)
        v = kv[:, nk * hd:].reshape(-1, nk // 2, 2 * hd)
        rep = (nq // 2) // (nk // 2)
        own = (k[:, :, 0], k[:, :, 1], v)
        return own, tuple(jnp.repeat(t, rep, axis=1) for t in own)

    def attention(self, h, blk, layer, keys, first, window):
        """Differential attention of the queries ``h`` (Lq, d) at
        positions ``first + 0..`` over ``keys`` = (k1, k2, v)."""
        g = self.geom
        hd, nq = g["head_dim"], g["n_head"]
        q = self._dot(h, blk["w_qkv"][:, :nq * hd]) + _f32(
            blk["b_qkv"][:nq * hd])
        q = q.reshape(-1, nq // 2, 2, hd)
        lam_init = 0.8 - 0.6 * math.exp(-0.3 * layer)
        lam = lam_init
        if g["lambda_learned"]:
            lam = lam + (
                jnp.exp(jnp.sum(_f32(blk["lambda_q1"]) * _f32(blk["lambda_k1"])))
                - jnp.exp(jnp.sum(_f32(blk["lambda_q2"])
                                  * _f32(blk["lambda_k2"]))))
        out = []
        for i in range(0, q.shape[0], QUERY_BLOCK):
            a1, a2 = self._attend[window if g["window_mask"] else 0](
                q[i:i + QUERY_BLOCK, :, 0], q[i:i + QUERY_BLOCK, :, 1],
                *keys, first + i)
            o = a1 - lam * a2
            if g["subln"]:
                o = o * jax.lax.rsqrt(
                    jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                    + g["eps"]) * _f32(blk["subln"])
            out.append((o * (1.0 - lam_init)).reshape(o.shape[0], -1))
        return self._dot(jnp.concatenate(out), blk["w_o"]) + _f32(blk["b_o"])

    def gmu(self, h, blk, m):
        gate = _silu(self._dot(h, blk["w_1"]))
        return self._dot(m * gate if self.geom["gmu_memory"] else gate,
                         blk["w_2"])

    def block(self, x, blk, mixed):
        x = x + mixed
        h = self._ln(x, blk["ln_b_scale"], blk["ln_b_bias"])
        return x + jnp.concatenate([
            self._mlp(h[i:i + ROW_BLOCK], blk["mlp_w1"], blk["mlp_w2"])
            for i in range(0, h.shape[0], ROW_BLOCK)])

    # -- the forward --------------------------------------------------------------

    def logits(self, params: dict, ids, last: int = 1,
               stores: dict | None = None, depth: int | None = None):
        """Logits (last, vocab) of the last ``last`` positions of ONE
        sequence ``ids``: the self-decoder over every position, the
        cross-decoder and the head at the judged ones. ``stores`` (a dict
        to fill): what every caching layer holds after the sequence, in
        layer order: ``"state"`` (N, d_inner) and ``"tail"`` (3, d_inner,
        the last rows of ``xs``) of each recurrent layer, ``"rows"``
        ``(k1, k2, v)`` of the last ``window`` positions of each window
        layer, ``"pages"`` ``(k1, k2, v)`` of EVERY position of the full
        layer, flat rows as the pool keeps them (:func:`store_error`).
        ``depth``: stop after that many layers and return None (the first
        layers' stores alone)."""
        kinds = self.geom["kinds"]
        keep = stores if stores is not None else {}
        with jax.default_matmul_precision("highest"):
            x = _f32(params["tok_embed"][jnp.asarray(ids)])
            first = x.shape[0] - last
            m = keys = None
            for n, kind in enumerate(kinds[:depth]):
                blk = layer_params(params, kinds, n)
                if kind in (GMU, CROSS) and x.shape[0] != last:
                    # the cross-decoder from here on: the judged rows (the
                    # left-out form: the memory of an earlier token)
                    back = self.geom["memory_shift"]
                    x, m = x[first:], m[first - back:m.shape[0] - back]
                h = self._ln(x, blk["ln_a_scale"], blk["ln_a_bias"])
                if kind == MAMBA:
                    mixed, y, (state, tail) = self.mamba(h, blk)
                    m = y       # the last Mamba layer's stands
                    keep.setdefault("state", []).append(
                        np.asarray(state, np.float32))
                    keep.setdefault("tail", []).append(np.asarray(tail))
                elif kind == GMU:
                    mixed = self.gmu(h, blk, m)
                elif kind == CROSS:
                    mixed = self.attention(h, blk, n, keys, first, 0)
                else:
                    own, keys = self.keys_values(h, blk)
                    if kind == WINDOW:
                        keep.setdefault("rows", []).append(tuple(
                            np.asarray(t[-self.geom["window"]:])
                            for t in own))
                    else:
                        keep["pages"] = tuple(
                            np.asarray(t).reshape(t.shape[0], -1)
                            for t in own)
                    mixed = self.attention(
                        h, blk, n, keys, 0,
                        self.geom["window"] if kind == WINDOW else 0)
                x = self.block(x, blk, mixed)
            if depth is not None:
                return None
            x = self._ln(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
            rows = params["tok_embed"]
            out = [np.asarray(self._head(x, rows[i:i + VOCAB_STEP]))
                   for i in range(0, rows.shape[0], VOCAB_STEP)]
        return np.concatenate(out, axis=-1)


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


def store_error(got, want, where=None) -> float:
    """rms of ``got - want`` over the rms of ``want``: how far a store of
    the program (a state, a tail, a ring's or the pool's rows) lies from
    the reference's; ``where``: over those elements alone."""
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    if where is not None:
        got, want = got[where], want[where]
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def worst_row_error(got, want) -> float:
    """The largest rms of ONE row's difference over the rms of ``want``:
    a fault at a few positions (a chunk's boundary) that
    :func:`store_error` would average away over thousands of rows."""
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    return float(np.sqrt(np.max(np.mean((got - want) ** 2, axis=-1))
                         / np.mean(want ** 2)))


def long_memory(blk: dict, horizon: int = SLOW_HORIZON) -> np.ndarray:
    """The elements (N, d_inner) of a recurrent layer's state that forget
    slowest: ``softplus(b_dt) exp(a_log) <= 1 / horizon`` (the seeded
    ``dt`` is its bias to within a tenth), so that what ``horizon``
    one-position updates did to them is still there. A state ROUNDED at
    every update shows in them first."""
    rate = (np.logaddexp(0.0, np.asarray(blk["b_dt"], np.float32))[None, :]
            * np.exp(np.asarray(blk["a_log"], np.float32)))
    return rate <= 1.0 / horizon


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
