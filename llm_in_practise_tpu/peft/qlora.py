"""QLoRA: NF4-quantized frozen base + trainable LoRA factors.

Parity with the reference north-star fine-tune
(``Fine-Tuning/qwen3-14b-qlora-dist-deepspeed.py:95-123``: 4-bit NF4 double-
quant base, bf16 compute, ``prepare_model_for_kbit_training``, LoRA r=8 on
q_proj/v_proj). TPU shape: the base tree is stored as NF4 (§quant/nf4) and
dequantized to bf16 *inside the jitted step*, where XLA fuses the 16-entry
codebook gather + scale into the consuming matmul; LoRA A/B stay fp32 and are
the only differentiated leaves — so the optimizer state is rank-r small, the
4-bit base is the only full-model memory, and there is no engine in sight.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from llm_in_practise_tpu.peft import lora as lora_lib
from llm_in_practise_tpu.quant import nf4


def _quant_predicate(path: str, leaf, min_size: int) -> bool:
    """Which leaves NF4-quantize: Dense *kernels* (2-D, or 3-D stacked —
    scan-over-layers models, stacked MoE experts) of ``min_size``+
    elements. Restricting to ``.../kernel`` paths keeps norm scales out:
    in the scan layout a stacked RMSNorm scale is 2-D and big enough to
    pass a shape-only check, but norms must never be lossy-compressed
    (bitsandbytes ignores them too). Embedding/lm_head stay bf16
    (reference ``Quantization`` recipes ``ignore=["lm_head"]``)."""
    if not path.endswith("/kernel"):
        return False
    if getattr(leaf, "ndim", 0) not in (2, 3) or leaf.size < min_size:
        return False
    return "embed" not in path and "lm_head" not in path


def quantize_base(params, *, min_size: int = 4096):
    """NF4-quantize the Dense kernels (see :func:`_quant_predicate`)."""
    return nf4.quantize_tree(
        params, lambda p, leaf: _quant_predicate(p, leaf, min_size))


# Module-level jitted helpers: callers that quantize layer-by-layer (the
# multi-B distinct-weights path) hit the same compiled executable for every
# layer — per-call jax.jit wrappers would recompile identical programs.
#
# Donation here "fails" BY DESIGN: the packed outputs are smaller and
# differently-dtyped than the donated f32 input, so XLA has nothing to
# alias into and warns "Some donated buffers were not usable" once per
# compile. The donation still releases the f32 buffer at its last use —
# which is the whole point (peak = shrinking f32 tree + one leaf's
# temps) — and the warning fires at COMPILE time only, never per step
# (the BENCH_r04 tail's warnings traced here; they are not a training-
# loop copy). Suppressed at the call site so the next reader doesn't
# re-chase them.
_DONATE_MSG = "Some donated buffers were not usable"


def _quiet_donate(jitted):
    import functools
    import warnings

    @functools.wraps(jitted)
    def call(leaf):
        with warnings.catch_warnings():
            warnings.filterwarnings("ignore", message=_DONATE_MSG)
            return jitted(leaf)

    return call


_quantize_donated = _quiet_donate(jax.jit(nf4.quantize, donate_argnums=0))
_cast_bf16_donated = _quiet_donate(
    jax.jit(lambda v: v.astype(jnp.bfloat16), donate_argnums=0))


_quantize_int8_jitted = None


def _quantize_int8_donated(leaf):
    # memoized behind the lazy import: a fresh jax.jit wrapper per leaf
    # would re-trace all ~120 MLP kernels of a mixed 14B tree instead of
    # hitting the two shape-distinct cached executables
    global _quantize_int8_jitted
    if _quantize_int8_jitted is None:
        from llm_in_practise_tpu.quant import int8

        _quantize_int8_jitted = _quiet_donate(
            jax.jit(int8.quantize, donate_argnums=0))
    return _quantize_int8_jitted(leaf)


_LOWMEM_QUANTIZERS = {"nf4": _quantize_donated,
                      "int8": _quantize_int8_donated}


def mixed_serve_fmt(path: str) -> str:
    """Per-path format of the ``"mixed"`` serving preset: **int8 MLP +
    NF4 attention**.

    Motivation (round-5 SLA work): a 14B all-int8 tree (~13 GiB) leaves
    no KV room on a 16 GiB chip, while all-NF4 decode misses the 100 ms
    TPOT gate (140 ms measured round 4 — the NF4 VPU-unpack tax on every
    byte). The MLP holds 81% of a Qwen3-14B layer's bytes, so paying
    int8's 2x size ONLY there buys most of int8's decode rate at
    ~10.7 GiB + 1.3 GiB NF4 attention — the one split whose memory AND
    latency arithmetic both close on one v5e.
    """
    return "int8" if "/mlp/" in path else "nf4"


def _resolve_fmt(fmt):
    """``fmt`` may be a format name, the ``"mixed"`` preset, or a
    callable ``path_str -> format name`` (per-leaf choice)."""
    if callable(fmt):
        return lambda p: _LOWMEM_QUANTIZERS[fmt(p)]
    if fmt == "mixed":
        return lambda p: _LOWMEM_QUANTIZERS[mixed_serve_fmt(p)]
    qfn = _LOWMEM_QUANTIZERS[fmt]
    return lambda p: qfn


def quantize_base_lowmem(params, *, min_size: int = 4096,
                         cast_rest_above: int | None = 1_000_000,
                         fmt: str = "nf4"):
    """:func:`quantize_base` for multi-billion-param trees on one chip.

    Quantizing the whole tree in a single jitted program keeps every
    leaf's s32/f32 quantization temps live at once and OOMs HBM around
    ~2B params; here each leaf runs as its own jitted call with the f32
    input **donated**, so peak memory is the (shrinking) f32 tree plus
    one leaf's temps. ``cast_rest_above``: non-quantized float32 leaves
    bigger than this many elements (the embedding) drop to bf16 — they
    are consumed in bf16 anyway and f32 residency wastes HBM.
    ``fmt``: ``"nf4"`` (QLoRA training base), ``"int8"`` (the W8A16
    serving format — 2x NF4's bytes, decode at memory speed),
    ``"mixed"`` (:func:`mixed_serve_fmt` — int8 MLP + NF4 attention,
    the 14B single-chip serving split), or a callable
    ``path_str -> format`` for custom splits.
    """
    from llm_in_practise_tpu.utils.tree import path_str

    pick = _resolve_fmt(fmt)

    def maybe(path, leaf):
        s = path_str(path)
        if _quant_predicate(s, leaf, min_size):
            return pick(s)(leaf)
        if (cast_rest_above is not None
                and getattr(leaf, "dtype", None) == jnp.float32
                and leaf.size > cast_rest_above):
            return _cast_bf16_donated(leaf)
        return leaf

    return jax.tree_util.tree_map_with_path(maybe, params)


def qlora_apply(qparams, lora_params, cfg: lora_lib.LoRAConfig,
                dtype=jnp.bfloat16):
    """Effective bf16 param tree from NF4 base + LoRA delta.

    Call inside the jitted loss: ``model.apply({"params": qlora_apply(...)})``.
    Gradients flow only through ``lora_params`` (NF4 leaves are uint8 —
    non-differentiable constants by construction).
    """
    base = nf4.dequantize_tree(qparams, dtype)
    return lora_lib.apply_lora(base, lora_params, cfg)


def make_qlora_loss_fn(qparams, cfg: lora_lib.LoRAConfig,
                       base_loss_fn, dtype=jnp.bfloat16):
    """Wrap a ``loss_fn(params, batch, rng)`` into one over LoRA params only.

    **Closure caveat**: this closes over ``qparams``, so the frozen tree is
    baked into the jitted program as constants — a multi-GB module to
    serialize, hash for the compile cache and hand to the compiler
    (round 3 measured minutes against seconds with the tree as an
    argument, ``VOCAB_PROBE.json``). Prefer
    :func:`make_qlora_loss_fn_args` for multi-GB bases.
    """
    def loss_fn(lora_params, batch, rng):
        params = qlora_apply(qparams, lora_params, cfg, dtype)
        return base_loss_fn(params, batch, rng)

    return loss_fn


def make_qlora_loss_fn_args(cfg: lora_lib.LoRAConfig, base_loss_fn,
                            dtype=jnp.bfloat16):
    """Like :func:`make_qlora_loss_fn` but the frozen base is an ARGUMENT:
    ``loss(lora_params, qparams, batch, rng)``. The multi-GB NF4 tree
    stays out of the serialized program (jit it with ``qparams`` in
    ``argnums`` position 1 and differentiate w.r.t. position 0 only), so
    remote/AOT compile uploads stay small and compile time is independent
    of base size."""
    def loss_fn(lora_params, qparams, batch, rng):
        params = qlora_apply(qparams, lora_params, cfg, dtype)
        return base_loss_fn(params, batch, rng)

    return loss_fn


def memory_report(params, qparams) -> str:
    full = nf4.tree_nbytes(params)
    quant = nf4.tree_nbytes(qparams)
    return (
        f"base {full / 2**20:.1f} MiB -> NF4 {quant / 2**20:.1f} MiB "
        f"({full / max(quant, 1):.2f}x smaller)"
    )
