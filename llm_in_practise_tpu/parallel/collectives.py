"""Quantized tensor-parallel collectives — the ZeRO++ idiom for serving.

Under tensor parallelism the row-parallel projections (attention
``out_proj``, MLP ``down_proj``/``fc_out``) end in an all-reduce of
bf16/f32 partial sums — at decode batch sizes that traffic is small
next to weights, but on bandwidth-starved interconnects (PCIe hosts,
degraded ICI) it is the serving tax TP pays per token. ZeRO++
(arxiv 2306.10209) bounds it by shipping QUANTIZED blocks instead:
each hop moves int8 payloads plus tiny scales, halving the wire versus
bf16 (4x versus f32) at a bounded quantization error.

XLA's SPMD partitioner emits the plain all-reduce on its own and cannot
be told to quantize it, so this is the one place the serving stack
drops to :func:`jax.shard_map` — everywhere else
(ISSUE 10 tentpole) ``jax.jit`` + ``NamedSharding`` lets the
partitioner schedule the collectives itself. The quantized all-reduce
is the ZeRO++ two-hop:

1. split the local partial sum into ``tp`` chunks, int8-quantize each
   (symmetric, per-chunk scale), ``all_to_all`` so chip ``j`` holds
   every chip's chunk ``j``;
2. dequantize + sum (the reduce half, exact in f32), re-quantize the
   reduced chunk, ``all_gather`` + dequantize (the broadcast half).

Per-chip wire: ``2·(tp-1)/tp`` of the payload in int8 — half the bf16
all-reduce, a quarter of f32.

**Lossy, therefore opt-in and golden-token-checked**: greedy outputs
can flip on near-tie argmaxes. ``--tp-quantized-collectives`` enables
it on the serving CLI, and :func:`golden_token_check` compares the
wrapped forward's greedy tokens against the plain path at startup —
on mismatch the CLI falls back to plain collectives with a warning
(docs/serving-tp.md states the policy).
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

#: Dense module names whose kernels the serving rule table shards
#: row-parallel (first axis over ``model`` — parallel/strategy.py
#: DEFAULT_RULES): their matmuls end in the activation all-reduce this
#: module quantizes. The lm_head is deliberately NOT here: quantizing
#: the logits reduction flips argmaxes far more readily than the
#: residual stream does.
ROW_PARALLEL_TARGETS = ("out_proj", "down_proj", "fc_out")


def _quant_i8(v):
    """Symmetric int8 quantization over the leading axis: ``v`` is
    ``(chunks, m)``; returns ``(int8 (chunks, m), f32 scales
    (chunks, 1))``."""
    amax = jnp.max(jnp.abs(v), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-30).astype(jnp.float32)
    q = jnp.clip(jnp.round(v / scale), -127, 127).astype(jnp.int8)
    return q, scale


def quantized_psum(x, axis_name: str, tp: int):
    """int8 two-hop all-reduce of ``x`` over shard_map axis
    ``axis_name`` (extent ``tp``). Call INSIDE a shard_map body; the
    reduction itself is exact f32 — only the wire payloads are int8."""
    if tp <= 1:
        return x
    shape, dt = x.shape, x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    n = flat.shape[0]
    m = -(-n // tp)
    flat = jnp.pad(flat, (0, m * tp - n)).reshape(tp, m)
    # hop 1: each chip ships chip-local chunk j to chip j, int8
    q, scale = _quant_i8(flat)
    q = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0)
    scale = jax.lax.all_to_all(scale, axis_name, split_axis=0,
                               concat_axis=0)
    reduced = jnp.sum(q.astype(jnp.float32) * scale, axis=0,
                      keepdims=True)                       # (1, m)
    # hop 2: broadcast the reduced chunk back, int8 again
    q2, scale2 = _quant_i8(reduced)
    q2 = jax.lax.all_gather(q2, axis_name, axis=0, tiled=True)
    scale2 = jax.lax.all_gather(scale2, axis_name, axis=0, tiled=True)
    out = (q2.astype(jnp.float32) * scale2).reshape(-1)[:n]
    return out.reshape(shape).astype(dt)


def row_parallel_matmul(x, kernel, mesh, *, axis: str = "model",
                        quantized: bool = True):
    """``x @ kernel`` as an explicit row-parallel shard_map: ``x``'s
    last dim and ``kernel``'s first dim shard over ``axis``, the
    partial-sum reduction runs through :func:`quantized_psum` (or a
    plain ``psum``). Falls back to the implicit-SPMD matmul when the
    contraction dim doesn't divide the axis extent."""
    tp = int(mesh.shape.get(axis, 1))
    k = x.shape[-1]
    if tp <= 1 or k % tp != 0:
        return x @ kernel

    xin = [None] * (x.ndim - 1) + [axis]

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(*xin), P(axis, None)), out_specs=P(),
        check_vma=False)
    def body(xs, ks):
        part = jnp.einsum("...k,kn->...n", xs, ks)
        if quantized:
            return quantized_psum(part, axis, tp)
        return jax.lax.psum(part, axis)

    return body(x, kernel)


class TPQuantizedCollectives:
    """Model facade (the :class:`~..serve.quantized.QuantizedModel`
    idiom): ``apply`` runs the wrapped model under a flax method
    interceptor that reroutes every row-parallel Dense
    (:data:`ROW_PARALLEL_TARGETS`) through
    :func:`row_parallel_matmul` with the int8 quantized all-reduce.
    Everything else — column-parallel projections, norms, embeddings,
    the lm_head — keeps the implicit-SPMD path, so XLA still plans
    those collectives itself.

    Dense-weights trees only: packed quantized leaves
    (``--quantized_dir``) route their matmuls through the
    ``peft/fused.py`` interceptor, which this wrapper does not compose
    with (the serving CLI rejects the combination)."""

    def __init__(self, model, mesh, *, axis: str = "model",
                 targets=ROW_PARALLEL_TARGETS):
        self.model = model
        self.mesh = mesh
        self.axis = axis
        self.targets = tuple(targets)

    @property
    def config(self):
        return self.model.config

    @property
    def cache_slot_axis(self) -> int:
        return getattr(self.model, "cache_slot_axis", 0)

    def init_cache(self, *args, **kwargs):
        return self.model.init_cache(*args, **kwargs)

    def _interceptor(self, next_fn, call_args, call_kwargs, context):
        mod = context.module
        if not (isinstance(mod, nn.Dense)
                and context.method_name == "__call__"
                and mod.name in self.targets):
            return next_fn(*call_args, **call_kwargs)
        kernel = mod.get_variable("params", "kernel")
        x = call_args[0]
        # flax Dense promotes inputs/params to mod.dtype (or the
        # promoted pair dtype) before the matmul — mirror that so the
        # only difference from the plain path is the collective
        dt = mod.dtype or jnp.result_type(x.dtype, kernel.dtype)
        y = row_parallel_matmul(x.astype(dt), kernel.astype(dt),
                                self.mesh, axis=self.axis,
                                quantized=True)
        if mod.use_bias:
            y = y + mod.get_variable("params", "bias").astype(dt)
        return y

    def apply(self, variables, *args, **kwargs):
        with nn.intercept_methods(self._interceptor):
            return self.model.apply(variables, *args, **kwargs)


def maybe_quantized_collectives(model, mesh, params, *,
                                log=print) -> tuple[object, bool]:
    """The opt-in's ONE gate policy (serving CLI and benches share it):
    wrap ``model`` for int8 row-parallel collectives, golden-token-check
    the wrapped forward against the plain one, and return
    ``(model_to_serve, enabled)`` — the wrapped model only when the
    check passed, else the original with a logged fallback."""
    wrapped = TPQuantizedCollectives(model, mesh)
    if golden_token_check(model, wrapped, params,
                          vocab_size=model.config.vocab_size):
        log("tp quantized collectives: ON (int8 row-parallel "
            "all-reduce, golden-token check passed)")
        return wrapped, True
    log("tp quantized collectives: DISABLED — int8 all-reduce flipped "
        "greedy tokens on the probe prompt; serving with plain "
        "collectives (docs/serving-tp.md, quantized-collective "
        "caveats)")
    return model, False


def golden_token_check(model, wrapped, params, *, vocab_size: int,
                       length: int = 16) -> bool:
    """Whether the quantized-collective forward's greedy tokens match
    the plain path's on a fixed probe prompt — the opt-in's acceptance
    gate (docs/serving-tp.md). One cache-free forward each; ``True``
    means byte-identical argmaxes at every probe position."""
    ids = (jnp.arange(length, dtype=jnp.int32)[None, :] * 7 + 3) \
        % max(int(vocab_size), 2)
    plain = model.apply({"params": params}, ids, deterministic=True)
    quant = wrapped.apply({"params": params}, ids, deterministic=True)
    if isinstance(plain, tuple):      # cache-threading model families
        plain, quant = plain[0], quant[0]
    a = jnp.argmax(plain.astype(jnp.float32), axis=-1)
    b = jnp.argmax(quant.astype(jnp.float32), axis=-1)
    return bool(jnp.all(a == b))
