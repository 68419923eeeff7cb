"""Where do the other ~90 ms/token go? Int8 decode attribution at 8B.

DECODE_AB_8B.json (round 4) falsified the pure-dequant model of Finding
9: with the NF4 nibble-unpack tax removed entirely (int8 = one native
convert), the 16-slot decode step still runs ~107 ms/token where weight
traffic alone says ~10 ms. Remaining suspects, each probed here on the
SAME resident int8 7.57B base:

- **raw weight-stream floor**: a jitted reduction over every packed
  leaf — the time to read the weights once with no matmul structure at
  all. Anything above this is structure, not bandwidth.
- **grid-program overhead**: the fused kernel at target tiles 512/1024/
  2048 — same weight bytes, 16x fewer grid steps at 2048. If time falls
  with program count, launch/fence overhead dominates thin-activation
  matmuls.
- **XLA dequant path** (zero Pallas calls): the compiler fuses the int8
  convert into its own matmul schedule; materializes bf16 tiles but
  needs no kernel entry/exit at all.

Writes ``INT8_TILE_PROBE.json`` incrementally (crash-safe).
Run: ``python tools/tpu_int8_tile_probe.py``.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp

from bench import _distinct_base_stacked
from llm_in_practise_tpu.models.qwen3 import Qwen3, Qwen3Config
from llm_in_practise_tpu.ops import int8_matmul as int8_mm
from llm_in_practise_tpu.peft import fused as fused_mod
from llm_in_practise_tpu.peft.fused import fused_quant_apply
from llm_in_practise_tpu.quant.int8 import Int8Tensor


def _force_pallas_int8(x, t, compute_dtype):
    """Production dispatch routes Int8Tensor to the XLA path (it
    measured faster — that decision came FROM this probe); the kernel
    sweep must still measure the actual Pallas kernel, so it swaps this
    dispatcher in for its rungs."""
    if isinstance(t, Int8Tensor):
        return int8_mm.int8_matmul(x, t, compute_dtype)
    return fused_mod.xla_dequant_matmul(x, t, compute_dtype)

OUT = os.path.join(REPO, "INT8_TILE_PROBE.json")
GEOM = dict(hidden_size=4096, intermediate_size=12288, n_layer=36,
            n_head=32, n_kv_head=8, head_dim=128)
SLOTS = 16
STEPS = 8


def timeit(fn, n=3):
    jax.block_until_ready(fn())
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main() -> None:
    from llm_in_practise_tpu.core.mesh import require_tpu

    require_tpu()
    cfg = Qwen3Config(
        vocab_size=151936, max_seq_len=1024, rope_theta=1e6,
        tie_word_embeddings=True, remat=False, compute_dtype="bfloat16",
        scan_layers=True, **GEOM,
    )
    print("quantizing int8...", flush=True)
    qparams, q_sec = _distinct_base_stacked(cfg, Qwen3, fmt="int8")
    model = Qwen3(cfg)
    cache0 = model.init_cache(SLOTS, 1024, dtype=jnp.bfloat16)
    cache0[0]["index"] = jnp.full((SLOTS,), 64, jnp.int32)
    tok = jnp.ones((SLOTS, 1), jnp.int32)
    results = {"geom": GEOM, "slots": SLOTS, "steps": STEPS,
               "quantize_s": round(q_sec, 1)}

    def flush(final=False):
        # atomic, and the committed artifact is only replaced by a
        # COMPLETED run — a crash leaves OUT.partial next to the old
        # artifact instead of a truncated overwrite
        tmp = OUT + ".partial"
        with open(tmp, "w") as f:
            json.dump(results, f, indent=2)
        if final:
            os.replace(tmp, OUT)

    # raw floor: read every packed byte once, one jitted reduction
    def weight_stream(qp):
        leaves = jax.tree_util.tree_leaves(qp)
        return sum(jnp.sum(l, dtype=jnp.float32)
                   if l.dtype != jnp.int8
                   else jnp.sum(l.astype(jnp.int32)).astype(jnp.float32)
                   for l in leaves if l.ndim >= 1)

    f_stream = jax.jit(weight_stream)
    dt = timeit(lambda: f_stream(qparams), n=5)
    results["weight_stream_floor_ms"] = round(dt * 1e3, 1)
    print(f"weight stream floor: {dt*1e3:.1f} ms", flush=True)
    flush()

    def multi_step(use_kernels):
        def run(qp, cache, t):
            def body(carry, _):
                tt, c = carry
                logits, c = fused_quant_apply(
                    model, qp, tt, compute_dtype=jnp.bfloat16,
                    use_kernels=use_kernels, cache=c)
                nt = jnp.argmax(
                    logits[:, -1].astype(jnp.float32), -1
                )[:, None].astype(jnp.int32)
                return (nt, c), nt
            (_, cache2), toks = jax.lax.scan(
                body, (t, cache), None, length=STEPS)
            return toks
        f = jax.jit(run)
        return lambda: f(qparams, cache0, tok)

    orig_dispatch = fused_mod.fused_kernel_matmul
    fused_mod.fused_kernel_matmul = _force_pallas_int8
    for tgt in (512, 1024, 2048):
        int8_mm._TGT_N = tgt
        int8_mm._TGT_K = tgt
        try:
            dt = timeit(multi_step(True))
            results[f"kernel_tile{tgt}_ms_per_tok"] = round(dt * 1e3 / STEPS, 1)
            print(f"kernel tile {tgt}: {dt*1e3/STEPS:.1f} ms/token",
                  flush=True)
        except Exception as e:
            results[f"kernel_tile{tgt}_error"] = (
                f"{type(e).__name__}: {str(e)[:200]}")
            print(f"kernel tile {tgt}: FAILED {e}", flush=True)
        flush()
    int8_mm._TGT_N = int8_mm._TGT_K = 512
    fused_mod.fused_kernel_matmul = orig_dispatch

    try:
        dt = timeit(multi_step(False))
        results["xla_ms_per_tok"] = round(dt * 1e3 / STEPS, 1)
        print(f"xla dequant path: {dt*1e3/STEPS:.1f} ms/token", flush=True)
    except Exception as e:
        results["xla_error"] = f"{type(e).__name__}: {str(e)[:200]}"
        print(f"xla: FAILED {e}", flush=True)
    flush(final=True)
    print("wrote", OUT)


if __name__ == "__main__":
    main()
