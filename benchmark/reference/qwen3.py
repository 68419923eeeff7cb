"""Plain reference for dense Qwen3: the published equations in
``jax.numpy``, float32 at ``highest`` matmul precision, no cache, no
paging, no batching tricks, no kernels.

It follows the model's description (huggingface.co/Qwen/Qwen3-8B,
``modeling_qwen3``): pre-norm blocks, RMSNorm, grouped-query attention
with a per-head RMSNorm on q and k before rotary embedding (rotate-half
lanes), SwiGLU MLP. Departures it shares with the configurations under
``benchmark/configs``: the output head is the transposed embedding (tied).

From the program it takes nothing: packed weights are read by
``benchmark/reference/packed.py``, so the comparison that decides
``correct`` does not move with the code under test. Originals this was
copied from: ``chip_smoke.py::reference_logits``, ``logit_error``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Engine (bf16 activations, 2^-9 relative rounding at each of ~10 tensors
# per layer, packed weights decoded in bf16) against float32 `highest` of
# the SAME packed weights: the roundings add like a random walk over
# depth, ~1% of the logits' spread at 36 layers (measured 0.0215, PR 24),
# and the maximum over 151,936 logits sits ~4.5 sigma out (measured
# 0.104). Bounds are in units of the reference logits' standard
# deviation; computing in a lower precision than the configuration states
# (int8 activations, fp8 KV) lands several times outside them.
LOGIT_RMS_TOL = 0.05
LOGIT_MAX_TOL = 0.25
# Greedy tokens out of the chunk, mixed-step and paged decode programs,
# whose logits the engine does not give out, are judged against the
# reference's logits at the same position (the reference is fed the
# engine's own tokens): how far the emitted token's reference logit
# trails the reference's best. The engine picks another token than the
# reference only where its error on the DIFFERENCE of two logits (rms
# ~0.03 of the spread) exceeds their gap, so the margin is of that size:
# worst 0.022 over the 16 tokens of each of 45 runs (my chip runs, PR 26).
# 0.1 is 4.5 times that and half the typical gap between the two best of
# 151,936 near-gaussian logits (~0.2): it admits the best token and a
# close second, not the third. A KV cache or a chunk path in a lower
# precision than the configuration states multiplies the error and
# leaves the margin within a few tokens.
TOKEN_MARGIN_TOL = 0.1


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(x, block, geom: dict, decode):
    """One decoder block on ``x`` (rows, hidden), causal over the rows."""
    n_head, n_kv, hd = geom["n_head"], geom["n_kv_head"], geom["head_dim"]
    eps, theta = geom["rms_norm_eps"], geom["rope_theta"]

    def w(*path):
        return decode(functools.reduce(lambda d, k: d[k], path, block)
                      ["kernel"])

    n = x.shape[0]
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    cos, sin = jnp.cos(pos * inv)[:, None], jnp.sin(pos * inv)[:, None]
    h = _rms(x, block["ln1"]["scale"], eps)
    q = (h @ w("attn", "q_proj")).reshape(n, n_head, hd)
    k = (h @ w("attn", "k_proj")).reshape(n, n_kv, hd)
    v = (h @ w("attn", "v_proj")).reshape(n, n_kv, hd)
    q = _rope(_rms(q, block["attn"]["q_norm"]["scale"], eps), cos, sin)
    k = _rope(_rms(k, block["attn"]["k_norm"]["scale"], eps), cos, sin)
    k = jnp.repeat(k, n_head // n_kv, axis=1)
    v = jnp.repeat(v, n_head // n_kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    x = x + a.reshape(n, -1) @ w("attn", "out_proj")
    h = _rms(x, block["ln2"]["scale"], eps)
    gate = jax.nn.silu(h @ w("mlp", "gate_proj"))
    return x + (gate * (h @ w("mlp", "up_proj"))) @ w("mlp", "down_proj")


class Reference:
    """The reference of one configuration: ``geom`` holds ``n_head``,
    ``n_kv_head``, ``head_dim``, ``rms_norm_eps`` and ``rope_theta``;
    ``decode`` turns a packed kernel into float32."""

    VOCAB_STEP = 16384                  # vocabulary rows per head matmul

    def __init__(self, geom: dict, decode):
        self.eps = geom["rms_norm_eps"]
        self._layer = jax.jit(functools.partial(layer, geom=geom,
                                                decode=decode))

        @jax.jit
        def head(x, scale, rows):
            return _rms(x, scale, self.eps) @ rows.astype(jnp.float32).T

        @jax.jit
        def nll_sum(x, y, scale, embedding):
            m = jnp.full((x.shape[0],), -jnp.inf)
            s = jnp.zeros((x.shape[0],))
            pick = jnp.zeros((x.shape[0],))
            for i in range(0, embedding.shape[0], self.VOCAB_STEP):
                z = head(x, scale, embedding[i:i + self.VOCAB_STEP])
                m2 = jnp.maximum(m, z.max(-1))
                s = s * jnp.exp(m - m2) + jnp.exp(z - m2[:, None]).sum(-1)
                m = m2
                inside = (y >= i) & (y < i + z.shape[1])
                idx = jnp.clip(y - i, 0, z.shape[1] - 1)
                pick = pick + jnp.where(
                    inside, jnp.take_along_axis(z, idx[:, None], 1)[:, 0],
                    0.0)
            return jnp.sum(m + jnp.log(s) - pick)

        self._head, self._nll_sum = head, nll_sum

    def hidden_states(self, embedding, blocks, ids):
        """Final-norm input of every position of ONE sequence. ``blocks``
        yields one block's parameters at a time (weights are decoded to
        float32 one layer at a time, so an 8B tree fits)."""
        x = embedding[jnp.asarray(ids)].astype(jnp.float32)
        for block in blocks:
            x = self._layer(x, block)
        return x

    def logits(self, embedding, ln_f_scale, blocks, ids,
               last: int = 1) -> np.ndarray:
        """Logits (last, vocab) of the last ``last`` positions."""
        with jax.default_matmul_precision("highest"):
            x = self.hidden_states(embedding, blocks, ids)[-last:]
            out = [np.asarray(self._head(
                x, ln_f_scale, embedding[i:i + self.VOCAB_STEP]))
                for i in range(0, embedding.shape[0], self.VOCAB_STEP)]
        return np.concatenate(out, axis=-1)

    def mean_loss(self, embedding, ln_f_scale, blocks_of, batch_x,
                  batch_y) -> float:
        """Mean next-token cross-entropy of a batch, one sequence at a
        time. ``blocks_of()`` returns a fresh iterator over the blocks."""
        total, count = 0.0, 0
        with jax.default_matmul_precision("highest"):
            for row_x, row_y in zip(np.asarray(batch_x),
                                    np.asarray(batch_y)):
                x = self.hidden_states(embedding, blocks_of(), row_x)
                total += float(self._nll_sum(x, jnp.asarray(row_y),
                                             ln_f_scale, embedding))
                count += len(row_y)
        return total / count


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
            "rms_over_std": rms, "max_over_std": worst,
            "argmax_agrees": bool(got.argmax() == want.argmax())}


def token_margins(want: np.ndarray, tokens: list[int]) -> dict:
    """For greedy ``tokens`` emitted at the positions of ``want``
    (len(tokens), vocab): how far, in units of each row's spread, the
    reference's logit of the emitted token trails the reference's best."""
    want = np.asarray(want, np.float32)
    gaps = [float((row.max() - row[t]) / np.std(row))
            for row, t in zip(want, tokens)]
    return {"ok": max(gaps) <= TOKEN_MARGIN_TOL, "worst_margin_over_std":
            max(gaps), "agree": sum(g == 0.0 for g in gaps),
            "of": len(gaps)}
