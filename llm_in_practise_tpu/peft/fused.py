"""Fused QLoRA forward: NF4 base streamed through the Pallas kernel.

:func:`llm_in_practise_tpu.peft.qlora.qlora_apply` dequantizes the whole
base to bf16 in HBM before the model runs — simple, but it holds a
transient bf16 copy. This module is the fused path the reference gets
from bitsandbytes' CUDA kernels
(``qwen3-14b-qlora-dist-deepspeed.py:101-107``): a flax method interceptor
replaces every quantized ``nn.Dense`` call with

    ``y = nf4_matmul(x, W_nf4) + (x @ A) @ B · (α/r) + bias``

so the packed 4-bit weight goes straight into VMEM
(:mod:`llm_in_practise_tpu.ops.nf4_matmul`), the LoRA delta runs as two
rank-r matmuls (never materializing ΔW), and the bf16 base never exists in
HBM in either the forward or the backward (base frozen — gradient flows to
``x`` and the LoRA factors only). Non-quantized modules run untouched.

The same interceptor serves PTQ exports: Int4Tensor (GPTQ) and AWQTensor
(AWQ) kernel leaves dispatch to the W4A16 kernel
(:mod:`llm_in_practise_tpu.ops.int4_matmul`) — :func:`fused_quant_apply`
is the adapter-free serving entry point.

**Which path when (measured, one v5e chip, 1.48B Qwen3-arch):** the fused
kernel wins where activations are THIN — serving decode, where per-step
weight traffic dominates and the packed 4-bit stream saves 4x HBM
bandwidth. At training token counts (8K tokens/step) XLA's plain
dequant+matmul runs 77% faster (11.3K vs 6.4K tok/s): wide matmuls are
MXU-bound, XLA schedules them better than the current kernel, and the
dequant amortizes over the whole batch. Training defaults to
``qlora_apply``; serving (``serve/quantized.py``, adapters) stays on the
fused kernels.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from llm_in_practise_tpu.ops.int4_matmul import int4_matmul
from llm_in_practise_tpu.ops.nf4_matmul import nf4_matmul
from llm_in_practise_tpu.peft import lora as lora_lib
from llm_in_practise_tpu.quant.awq import AWQTensor
from llm_in_practise_tpu.quant.int4 import Int4Tensor
from llm_in_practise_tpu.quant.int8 import Int8Tensor
from llm_in_practise_tpu.quant.nf4 import NF4Tensor
from llm_in_practise_tpu.utils.tree import flatten_with_paths

QUANT_LEAVES = (NF4Tensor, Int4Tensor, AWQTensor, Int8Tensor)


def _is_quant(v) -> bool:
    return isinstance(v, QUANT_LEAVES)


def fused_kernel_matmul(x, t, compute_dtype):
    """Dispatch one quantized kernel to its fused Pallas matmul.

    AWQ folds its per-input-channel ``inv_scale`` into the activations
    (``x @ diag(s) @ decode(q) == (x * s) @ decode(q)``), then rides the
    int4 kernel."""
    if isinstance(t, NF4Tensor):
        return nf4_matmul(x, t, compute_dtype)
    if isinstance(t, AWQTensor):
        return int4_matmul(
            x * t.inv_scale.astype(x.dtype), t.q, compute_dtype)
    if isinstance(t, Int8Tensor):
        # int8 is the one format where XLA beats the Pallas kernel even
        # at decode (77 vs 100 ms/token on the 8B 16-slot step,
        # INT8_TILE_PROBE.json): with dequant reduced to one convert,
        # the compiler's own fusion schedules the thin matmul better
        # than the hand tiling. The 4-bit formats stay on their kernels
        # (nibble unpack through XLA costs 2x — DECODE_AB_8B.json).
        from llm_in_practise_tpu.quant import int8 as int8_lib

        return int8_lib.dequant_matmul(x.astype(compute_dtype), t)
    return int4_matmul(x, t, compute_dtype)


def xla_dequant_matmul(x, t, compute_dtype):
    """The SPMD-partitionable path: dequant in plain XLA ops, fused into
    the matmul by the compiler. Pallas custom calls are opaque to the SPMD
    partitioner, so sharded (TP) serving of packed trees runs through this
    (the component shardings come from :mod:`...quant.sharding`); XLA
    emits the same psum/all-gather schedule it would for a dense kernel."""
    from llm_in_practise_tpu.quant import int4 as int4_lib
    from llm_in_practise_tpu.quant import int8 as int8_lib
    from llm_in_practise_tpu.quant import nf4 as nf4_lib

    if isinstance(t, NF4Tensor):
        return x @ nf4_lib.dequantize(t, compute_dtype)
    if isinstance(t, AWQTensor):
        return (x * t.inv_scale.astype(x.dtype)) @ int4_lib.decode(
            t.q, compute_dtype)
    if isinstance(t, Int8Tensor):
        return int8_lib.dequant_matmul(x, t)
    return x @ int4_lib.decode(t, compute_dtype)


def qlora_fused_apply(
    model,
    qparams,
    lora_params,
    cfg: lora_lib.LoRAConfig,
    *args,
    compute_dtype=jnp.bfloat16,
    use_kernels: bool = True,
    **apply_kwargs,
):
    """Run ``model.apply`` with quantized Dense kernels served by the fused
    kernels. ``qparams``: params tree whose kernel leaves may be NF4Tensor
    (:func:`..peft.qlora.quantize_base`), Int4Tensor, or AWQTensor (the
    PTQ exports) — each dispatches to its Pallas matmul via
    :func:`fused_kernel_matmul`; ``lora_params``: factor tree from
    :func:`..peft.lora.init_lora` (may be empty — see
    :func:`fused_quant_apply`). Gradients flow through the closure to
    ``lora_params`` only (quantized bases are non-differentiable
    storage). ``use_kernels=False`` swaps the Pallas matmuls for
    :func:`xla_dequant_matmul` — required under a sharded mesh."""
    quant = {
        k: v for k, v in flatten_with_paths(
            qparams, is_leaf=_is_quant
        ).items()
        if _is_quant(v)
    }
    consumed: set[str] = set()
    # init_lora's tree is already keyed by kernel path: {path: {"a", "b"}}
    lora_by_path: dict[str, dict] = lora_params or {}

    # Scan-layers models: block quant leaves AND block LoRA factors live
    # STACKED under "blocks/block/..." (leading n_layer axis per
    # component). They can't be served from this closure — inside the
    # scan the interceptor needs the CURRENT layer's slice, which only
    # exists as the body's scanned input. Route them through the model's
    # scan_sideband channel as {"q": {path: quant}, "l": {path: {a, b}}}
    # (the body publishes its slice via layers.scan_sideband; the
    # interceptor reads layers.current_scan_sideband). Keys match module
    # paths exactly. Gradients flow through "l" — sideband entries are
    # ordinary scanned xs — which is what makes full-depth QLoRA
    # training under scan differentiable.
    scan_mode = bool(getattr(getattr(model, "config", None) or
                             getattr(model, "cfg", None),
                             "scan_layers", False))
    sideband = None
    if scan_mode:
        q_side = {k: v for k, v in quant.items()
                  if k.startswith("blocks/block/")}
        l_side = {k: v for k, v in lora_by_path.items()
                  if k.startswith("blocks/block/")}
        if q_side or l_side:
            sideband = {"q": q_side, "l": l_side}
            quant = {k: v for k, v in quant.items() if k not in q_side}
            lora_by_path = {k: v for k, v in lora_by_path.items()
                            if k not in l_side}
            apply_kwargs = dict(apply_kwargs, scan_sideband=sideband)
    n_layer = getattr(getattr(model, "config", None) or
                      getattr(model, "cfg", None), "n_layer", None)

    # Dense never reads its kernel when intercepted — swap quantized
    # leaves for tiny placeholders so the params tree stays a valid array
    # pytree without materializing the dequantized weight. Stacked scan
    # leaves get a leading n_layer axis so nn.scan can slice them.
    def _placeholder(path, v):
        if not _is_quant(v):
            return v
        from llm_in_practise_tpu.utils.tree import path_str
        if sideband and path_str(path) in sideband["q"]:
            return jnp.zeros((n_layer, 1, 1), compute_dtype)
        return jnp.zeros((1, 1), compute_dtype)

    placeholders = jax.tree_util.tree_map_with_path(
        _placeholder, qparams, is_leaf=_is_quant,
    )

    def lora_delta(key, x):
        lp = lora_by_path.get(key)
        if lp is None and sideband:
            from llm_in_practise_tpu.models.layers import (
                current_scan_sideband,
            )
            sliced = current_scan_sideband()
            if sliced is not None:
                lp = sliced["l"].get(key)
        if lp is None:
            return None
        a = lp["a"].astype(compute_dtype)
        b = lp["b"].astype(compute_dtype)
        return (x.astype(compute_dtype) @ a) @ b * cfg.scaling

    def interceptor(next_fn, call_args, call_kwargs, context):
        mod = context.module
        if not (isinstance(mod, nn.Dense) and context.method_name == "__call__"):
            return next_fn(*call_args, **call_kwargs)
        key = "/".join(mod.path) + "/kernel"
        t = quant.get(key)
        if t is None and sideband:
            # inside the scan body: the published value holds THIS
            # layer's slices of the stacked quant leaves
            from llm_in_practise_tpu.models.layers import (
                current_scan_sideband,
            )
            sliced = current_scan_sideband()
            if sliced is not None:
                t = sliced["q"].get(key)
        x = call_args[0]
        if t is None:
            # unquantized Dense: normal path, but a LoRA target must still
            # get its delta (qlora_apply adapts every target)
            y = next_fn(*call_args, **call_kwargs)
            delta = lora_delta(key, x)
            return y if delta is None else (y + delta).astype(y.dtype)
        consumed.add(key)
        matmul = fused_kernel_matmul if use_kernels else xla_dequant_matmul
        y = matmul(x.astype(compute_dtype), t, compute_dtype)
        delta = lora_delta(key, x)
        if delta is not None:
            y = y + delta
        if mod.use_bias:
            bias = mod.get_variable("params", "bias")
            y = y + bias.astype(compute_dtype)
        return y.astype(x.dtype) if x.dtype != y.dtype else y

    with nn.intercept_methods(interceptor):
        out = model.apply({"params": placeholders}, *args, **apply_kwargs)
    missed = (set(quant)
              | (set(sideband["q"]) if sideband else set())) - consumed
    # half a forward (the trunk without the head, or the head alone:
    # models/layers.py) reads half the leaves by design; the whole
    # forward of the same model (every decode program) keeps the check
    half = apply_kwargs.get("return_hidden") or apply_kwargs.get("head_only")
    if missed and not half:
        # an unconsumed quantized leaf means some module computed against
        # its (1, 1) placeholder — fail loudly at the source
        raise ValueError(
            "quantized kernels not served by the fused interceptor (module "
            f"is not an nn.Dense?): {sorted(missed)}"
        )
    return out


def make_fused_qlora_loss_fn(model, qparams, cfg: lora_lib.LoRAConfig,
                             base_loss_fn, compute_dtype=jnp.bfloat16):
    """Like :func:`..peft.qlora.make_qlora_loss_fn` but the forward runs
    through the fused kernel. ``base_loss_fn(apply_out_fn, batch, rng)``
    receives a closure ``apply_out_fn(*args, **kw) -> model output``.

    Closes over ``qparams`` — see the closure caveat on
    :func:`..peft.qlora.make_qlora_loss_fn` (docs/perf.md Finding 6)
    before jitting this through a remote/AOT compile path with a
    multi-GB base."""

    def loss_fn(lora_params, batch, rng):
        def apply_out(*args, **kw):
            return qlora_fused_apply(
                model, qparams, lora_params, cfg, *args,
                compute_dtype=compute_dtype, **kw,
            )

        return base_loss_fn(apply_out, batch, rng)

    return loss_fn


def make_fused_qlora_loss_fn_args(model, cfg: lora_lib.LoRAConfig,
                                  base_loss_fn,
                                  compute_dtype=jnp.bfloat16,
                                  use_kernels: bool = False):
    """Args-passing form of :func:`make_fused_qlora_loss_fn`:
    ``loss(lora_params, qparams, batch, rng)`` with the frozen base as a
    jit ARGUMENT (the closure form bakes multi-GB constants into the
    serialized program — docs/perf.md Finding 6).

    The default ``use_kernels=False`` runs every quantized Dense through
    :func:`xla_dequant_matmul`: the compiler dequantizes each kernel AT
    ITS USE SITE and frees it, so peak memory is the packed tree plus
    one layer's bf16 transient — unlike
    :func:`..peft.qlora.make_qlora_loss_fn_args`, whose ``qlora_apply``
    materializes the ENTIRE bf16 base before the forward (≈ 2 bytes/param
    extra; a 7.6B base is 15 GiB, more than a v5e chip). This is the
    builder that makes full-depth multi-B QLoRA steps fit on one chip;
    the price is re-dequantizing in the backward's remat recompute.

    ``base_loss_fn(apply_out, qparams, batch, rng)``: ``apply_out``
    forwards to ``model.apply`` through the interceptor; ``qparams`` is
    passed along for non-quantized leaves the loss needs directly (the
    bf16 embedding for a fused tied-head cross-entropy)."""

    def loss_fn(lora_params, qparams, batch, rng):
        def apply_out(*args, **kw):
            return qlora_fused_apply(
                model, qparams, lora_params, cfg, *args,
                compute_dtype=compute_dtype, use_kernels=use_kernels,
                **kw,
            )

        return base_loss_fn(apply_out, qparams, batch, rng)

    return loss_fn


def fused_quant_apply(model, qtree, *args,
                      compute_dtype=jnp.bfloat16, use_kernels: bool = True,
                      **apply_kwargs):
    """Serve a PTQ-quantized model (Int4/AWQ/NF4 kernel leaves) through the
    fused kernels — no adapters; the W4A16 serving path
    (vLLM ``compressed-tensors`` consumption parity)."""
    return qlora_fused_apply(
        model, qtree, {}, lora_lib.LoRAConfig(), *args,
        compute_dtype=compute_dtype, use_kernels=use_kernels, **apply_kwargs,
    )
