"""Shared transformer building blocks (flax.linen).

One block implementation serves the whole from-scratch model family of the
reference curriculum — MiniGPT (post-LN encoder blocks, reference
``llm-demo/minigpt2/model.py:40-74``), GPTLike (pre-LN decoder,
``GPTLike_wikitext2_learned_pe.py:118-160``) — via the ``norm_first`` switch.
Attention funnels through :func:`llm_in_practise_tpu.ops.attention.dot_product_attention`
so the Pallas flash kernel is picked up everywhere on TPU.

KV caches are explicit pytrees (dict with ``k``, ``v``, ``index``) threaded
through ``__call__`` — no mutable module state, so the decode step jits
cleanly and shards like any other value.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from llm_in_practise_tpu.ops import rope as rope_ops
from llm_in_practise_tpu.ops.attention import dot_product_attention

Cache = dict[str, Any]

dense_init = nn.initializers.normal(stddev=0.02)


# --- scan sideband ---------------------------------------------------------
# Trace-time channel between a scan-over-layers body and flax method
# interceptors installed OUTSIDE the scan (peft/fused.py): the body
# publishes its per-iteration sliced side inputs (e.g. one layer's packed
# quantized weights, arriving as scanned ``xs``) so the interceptor can
# serve the *current* layer's tensors even though its closure only holds
# the full stacked tree. The published values are tracers; they are only
# meaningful during the single trace of the scan body, which is exactly
# when interceptors run. Thread-local: engines trace their jitted
# programs from their own threads (one per engine under OpenAIServer
# adapters), and a shared stack would cross-talk between traces.
import threading as _threading

_SCAN_SIDEBAND = _threading.local()


class scan_sideband:
    """Context manager publishing ``value`` for the duration of a scan
    body's trace. Nested scans stack; per-thread."""

    def __init__(self, value):
        self.value = value

    def __enter__(self):
        stack = getattr(_SCAN_SIDEBAND, "stack", None)
        if stack is None:
            stack = _SCAN_SIDEBAND.stack = []
        stack.append(self.value)
        return self.value

    def __exit__(self, *exc):
        _SCAN_SIDEBAND.stack.pop()
        return False


def current_scan_sideband():
    """This thread's innermost published sideband value, or None outside
    a scan body's trace."""
    stack = getattr(_SCAN_SIDEBAND, "stack", None)
    return stack[-1] if stack else None


def remat_apply(block: nn.Module, *args, **call_kwargs):
    """Apply a transformer block under gradient checkpointing.

    Shared by every model family's ``cfg.remat`` path: wraps the block's
    ``__call__`` in flax's lifted ``nn.remat`` so activations are
    recomputed in backward instead of saved (exact — tested in
    tests/test_remat.py). ``call_kwargs`` are closed over (python bools
    stay static; traced arrays like ``positions`` become free variables,
    which ``jax.checkpoint`` handles); the block's cache output is
    dropped — remat only runs on the cache-free training forward.
    """
    def run(mdl, *a):
        return mdl(*a, **call_kwargs)[0]

    return nn.remat(run, prevent_cse=False)(block, *args)


def _activation(name: str):
    return {"gelu": nn.gelu, "relu": nn.relu, "silu": nn.silu}[name]


def cache_positions(index: jax.Array, batch: int, length: int) -> jax.Array:
    """(B, L) absolute positions for the current query block.

    ``index`` is the cache write index — a scalar (all sequences in lockstep,
    plain generate) or a ``(B,)`` vector (continuous batching: every slot at
    its own depth).
    """
    index = jnp.asarray(index)
    if index.ndim == 1:
        return index[:, None] + jnp.arange(length)[None, :]
    pos = index + jnp.arange(length)[None, :]
    return jnp.broadcast_to(pos, (batch, length))


def cache_update(buf: jax.Array, new: jax.Array, index: jax.Array) -> jax.Array:
    """Write ``new`` (B, L, ...) into ``buf`` (B, max_len, ...) at ``index``.

    Scalar index → one dynamic_update_slice; ``(B,)`` vector index → per-slot
    scatter (vmapped), the continuous-batching write path. Works for 4D KV
    buffers and the 3D MLA latent cache alike.
    """
    new = new.astype(buf.dtype)
    index = jnp.asarray(index)
    trailing = (0,) * (buf.ndim - 2)
    if index.ndim == 1:
        return jax.vmap(
            lambda b, n, i: jax.lax.dynamic_update_slice(b, n, (i, *trailing))
        )(buf, new, index)
    return jax.lax.dynamic_update_slice(buf, new, (0, index, *trailing))


# Entries a routed model fills in a serving program's transient cache view
# (serve/step_stats.py; models/deepseek_v3.py): a (4,) int32 running
# [layer passes, held assignments, held experts touched, busiest held
# expert's load], and the (rows, k) experts each row's last position chose.
LOAD_KEY, ROUTE_KEY = "moe_load", "moe_route"
# Entry the serving programs give a layer that owns its cache writes (a
# sliding-window layer's ring, models/mimo_v2.py): (B,) how many of the
# call's positions are real for each row (None: all of them).
VALID_KEY = "valid"
# Entry the chunk program gives such a layer of a model that declares
# ``reads_finish`` (models/phi4flash.py): (B,) bool, whether the row's
# prompt ENDS in this call. A model whose later layers only read (a
# cross-decoder) runs them at a prompt's last position and nowhere else.
FINISH_KEY = "finish"
# Entry the decode programs give the PAGED layer of a model that declares
# ``reads_pages`` (models/phi4flash.py) in place of a gathered view: the
# layer's buffers are then the pool's own, ``(pages, page rows, width up to
# whole lanes)`` as serve/paged_kv.py stores them by pages, and this entry
# ``(B, pages a slot)`` int32 each row's block table: logical page ->
# physical page, page 0 (the pool's trash page) where nothing is mapped. The
# layer writes its new row into the pool itself (``page_row_write``: a row
# whose ``VALID_KEY`` is 0 writes into the trash page) and attends the pages
# where they lie, to each row's true length.
PAGES_KEY = "pages"


def page_row_write(buf, table, index, valid, rows):
    """``buf`` (pages, page rows, lanes), a pool stored by pages, with
    ``rows`` (B, width) written at position ``index`` (B,) of each row's
    pages ``table`` (B, pages a slot); a row whose ``valid`` is 0 (idle,
    mid-prefill) writes into page 0, the trash page."""
    size = buf.shape[1]
    page = jnp.take_along_axis(
        table, jnp.clip(index // size, 0, table.shape[1] - 1)[:, None],
        axis=1)[:, 0]
    rows = jnp.pad(rows.astype(buf.dtype),
                   ((0, 0), (0, buf.shape[2] - rows.shape[-1])))
    return buf.at[jnp.where(valid > 0, page, 0), index % size].set(rows)


# --- a prefill's tail: one row of logits a prompt ---------------------------
# A program that feeds a prompt (or a chunk of one) reads the logits of one
# position a row, so it takes the final-norm hidden state there BEFORE the
# output head: the head over every position is the widest matmul of the
# forward, (L, hidden) x (hidden, vocab), for rows nobody reads. The two
# halves are keywords of every in-tree model's one compact ``__call__``
# (``return_hidden`` / ``head_only``), so both pass through whatever
# interceptors the serving facades install (packed weights, adapters,
# collectives). These are the only callers of the pair in ``serve/``.


def last_position_hidden(model, params, ids, lens, cache):
    """The trunk over ``ids`` (B, L) against ``cache``; each row's
    final-norm hidden state at its last real position ``lens - 1``.
    Returns ``((B, hidden), cache)``."""
    hidden, cache = model.apply(
        {"params": params}, ids, deterministic=True, cache=cache,
        return_hidden=True)
    last = jnp.take_along_axis(
        hidden, jnp.maximum(lens - 1, 0).reshape(-1, 1, 1), axis=1)[:, 0, :]
    return last, cache


def head_logits(model, params, hidden):
    """(B, hidden) final-norm states -> (B, vocab) logits through the
    model's own output head."""
    return model.apply(
        {"params": params}, hidden[:, None, :], deterministic=True,
        head_only=True)[:, 0, :]


def last_position_logits(model, params, ids, lens, cache):
    """``model.apply(ids)``'s logits at ``lens - 1`` for every row,
    computed from that position alone. Returns ``((B, vocab), cache)``."""
    last, cache = last_position_hidden(model, params, ids, lens, cache)
    return head_logits(model, params, last), cache


def init_cache(
    batch: int, max_len: int, n_kv_head: int, head_dim: int, n_layer: int,
    dtype=jnp.bfloat16,
) -> list[Cache]:
    """Pre-allocated static-shape KV cache, one entry per layer."""
    return [
        {
            "k": jnp.zeros((batch, max_len, n_kv_head, head_dim), dtype),
            "v": jnp.zeros((batch, max_len, n_kv_head, head_dim), dtype),
            "index": jnp.zeros((), jnp.int32),
        }
        for _ in range(n_layer)
    ]


class CausalSelfAttention(nn.Module):
    """Multi-head causal self-attention with optional RoPE and KV cache."""

    embed_dim: int
    n_head: int
    dropout: float = 0.0
    use_rope: bool = False
    rope_theta: float = 10000.0
    max_seq_len: int = 2048
    attn_impl: str = "auto"
    # Compute dtype for the projections. flax Dense with dtype=None
    # PROMOTES bf16 activations against the f32 params — the whole layer
    # silently runs f32 and the MXU loses its bf16 peak; pass bfloat16
    # here (params stay f32 masters, cast per-call).
    dtype: object = None

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        *,
        deterministic: bool = True,
        cache: Cache | None = None,
        positions: jax.Array | None = None,
    ) -> tuple[jax.Array, Cache | None]:
        b, l, _ = x.shape
        head_dim = self.embed_dim // self.n_head
        qkv_dense = lambda name: nn.Dense(
            self.embed_dim, kernel_init=dense_init, dtype=self.dtype,
            name=name
        )
        q = qkv_dense("q_proj")(x).reshape(b, l, self.n_head, head_dim)
        k = qkv_dense("k_proj")(x).reshape(b, l, self.n_head, head_dim)
        v = qkv_dense("v_proj")(x).reshape(b, l, self.n_head, head_dim)

        if self.use_rope:
            cos, sin = rope_ops.precompute_cos_sin(
                head_dim, self.max_seq_len, self.rope_theta
            )
            if positions is None and cache is not None:
                positions = cache_positions(cache["index"], b, l)
            # rotation math in f32 (the tables are f32), result back in
            # the compute dtype so attention keeps its bf16 path
            dt = q.dtype
            q = rope_ops.apply_rotary_emb(
                q, cos, sin, positions=positions).astype(dt)
            k = rope_ops.apply_rotary_emb(
                k, cos, sin, positions=positions).astype(dt)

        q_offset = None
        if cache is not None:
            q_offset = cache["index"]  # absolute position of first query
            k_cache = cache_update(cache["k"], k, cache["index"])
            v_cache = cache_update(cache["v"], v, cache["index"])
            cache = {"k": k_cache, "v": v_cache, "index": cache["index"] + l}
            k, v = k_cache.astype(q.dtype), v_cache.astype(q.dtype)

        dropout_rng = None
        if not deterministic and self.dropout > 0.0:
            dropout_rng = self.make_rng("dropout")
        # With a cache, q_offset-based causal masking handles both future
        # prompt positions (multi-token prefill) and unwritten cache slots.
        out = dot_product_attention(
            q, k, v,
            causal=True,
            q_offset=q_offset,
            dropout_rate=0.0 if deterministic else self.dropout,
            dropout_rng=dropout_rng,
            impl=self.attn_impl,
        )
        out = out.reshape(b, l, self.embed_dim)
        out = nn.Dense(self.embed_dim, kernel_init=dense_init,
                       dtype=self.dtype, name="out_proj")(out)
        out = nn.Dropout(self.dropout)(out, deterministic=deterministic)
        return out, cache


class MLP(nn.Module):
    """Position-wise FFN: Dense → activation → Dense → dropout."""

    embed_dim: int
    hidden_dim: int
    dropout: float = 0.0
    activation: str = "gelu"
    dtype: object = None  # see CausalSelfAttention.dtype

    @nn.compact
    def __call__(self, x: jax.Array, *, deterministic: bool = True) -> jax.Array:
        h = nn.Dense(self.hidden_dim, kernel_init=dense_init,
                     dtype=self.dtype, name="fc_in")(x)
        h = _activation(self.activation)(h)
        h = nn.Dense(self.embed_dim, kernel_init=dense_init,
                     dtype=self.dtype, name="fc_out")(h)
        return nn.Dropout(self.dropout)(h, deterministic=deterministic)


class TransformerBlock(nn.Module):
    """Attention + FFN with residuals; pre-LN or post-LN."""

    embed_dim: int
    n_head: int
    mlp_ratio: float = 4.0
    dropout: float = 0.0
    norm_first: bool = True
    activation: str = "gelu"
    use_rope: bool = False
    rope_theta: float = 10000.0
    max_seq_len: int = 2048
    attn_impl: str = "auto"
    dtype: object = None  # see CausalSelfAttention.dtype

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        *,
        deterministic: bool = True,
        cache: Cache | None = None,
        positions: jax.Array | None = None,
    ) -> tuple[jax.Array, Cache | None]:
        attn = CausalSelfAttention(
            self.embed_dim, self.n_head, self.dropout,
            use_rope=self.use_rope, rope_theta=self.rope_theta,
            max_seq_len=self.max_seq_len, attn_impl=self.attn_impl,
            dtype=self.dtype, name="attn",
        )
        mlp = MLP(
            self.embed_dim, int(self.embed_dim * self.mlp_ratio),
            self.dropout, self.activation, dtype=self.dtype, name="mlp",
        )

        def _ln(name):
            # statistics in f32 (dtype=None promotes), output back in the
            # block's compute dtype so residuals stay bf16
            ln = nn.LayerNorm(name=name)
            if self.dtype is None:
                return ln
            return lambda v: ln(v).astype(self.dtype)

        ln1 = _ln("ln1")
        ln2 = _ln("ln2")
        if self.norm_first:
            a, cache = attn(
                ln1(x), deterministic=deterministic, cache=cache, positions=positions
            )
            x = x + a
            x = x + mlp(ln2(x), deterministic=deterministic)
        else:  # post-LN (torch TransformerEncoderLayer default)
            a, cache = attn(
                x, deterministic=deterministic, cache=cache, positions=positions
            )
            x = ln1(x + a)
            x = ln2(x + mlp(x, deterministic=deterministic))
        return x, cache
