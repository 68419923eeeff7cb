"""Decode scan-mechanics attribution (Finding 13 follow-up).

Finding 13 bounded the 8B int8 decode's matmuls at 9.2 ms/token against
77 measured and named three suspects for the ~68 ms between them. This
experiment separates them at L8/L16 depth (same d4096 geometry, cheap
to quantize, every program small enough to compile fast):

- **scan vs unrolled** at L8: identical math, the unrolled program has
  no loop mechanics, no xs slice copies, no stacked-KV carry — the
  difference IS the scan machinery.
- **scan_unroll 1 vs 4** at L8: if loop overhead (not slice copies)
  dominates, unrolling the loop body recovers most of the unrolled
  program's speed at O(unroll) program size.
- **cache_len 1024 vs 256** at L8: the stacked-KV slice/update cost
  scales with cache bytes; the weight traffic does not.
- **L8 vs L16 scan**: per-layer marginal cost of everything.

Writes ``DECODE_ATTRIB_L8.json``. Run: ``python tools/tpu_decode_attrib3.py``.
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

from bench import G8B, _distinct_base_stacked
from llm_in_practise_tpu.models.qwen3 import (
    Qwen3, Qwen3Config, unstack_layer_params,
)
from llm_in_practise_tpu.peft.fused import fused_quant_apply

OUT = os.path.join(REPO, "DECODE_ATTRIB_L8.json")
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


def multi_step(model, qparams, cache0, use_kernels=False):
    tok = jnp.ones((SLOTS, 1), jnp.int32)

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
        (_, c2), toks = jax.lax.scan(body, (t, cache), None, length=STEPS)
        return toks

    f = jax.jit(run)
    return lambda: f(qparams, cache0, tok)


def main() -> None:
    from llm_in_practise_tpu.core.mesh import require_tpu

    require_tpu()
    results = {"slots": SLOTS, "steps": STEPS, "geom": "d4096 (8B layer)"}

    def flush():
        with open(OUT, "w") as f:
            json.dump(results, f, indent=2)

    def leg(name, cfg, qparams, cache_len):
        model = Qwen3(cfg)
        cache0 = model.init_cache(SLOTS, cache_len, dtype=jnp.bfloat16)
        for entry in cache0:   # scan layout has 1 entry; unrolled has L
            entry["index"] = jnp.full((SLOTS,), 64, jnp.int32)
        try:
            dt = timeit(multi_step(model, qparams, cache0))
            results[name] = round(dt * 1e3 / STEPS, 2)
            print(f"{name}: {dt*1e3/STEPS:.2f} ms/token", flush=True)
        except Exception as e:
            results[name + "_error"] = f"{type(e).__name__}: {str(e)[:200]}"
            print(f"{name}: FAILED {e}", flush=True)
        flush()

    base = dict(vocab_size=151936, max_seq_len=1024, rope_theta=1e6,
                tie_word_embeddings=True, remat=False,
                compute_dtype="bfloat16", **G8B)

    cfg8 = Qwen3Config(n_layer=8, scan_layers=True, **base)
    q8, secs = _distinct_base_stacked(cfg8, Qwen3, fmt="int8")
    results["quantize_s_L8"] = round(secs, 1)
    leg("scan_L8_cache1024", cfg8, q8, 1024)
    leg("scan_L8_cache256", cfg8.replace(max_seq_len=256), q8, 256)
    leg("scan_unroll4_L8_cache1024", cfg8.replace(scan_unroll=4), q8, 1024)

    # unrolled: same weights, block_i layout — no scan machinery at all
    qu = unstack_layer_params(q8, 8)
    del q8
    leg("unrolled_L8_cache1024",
        Qwen3Config(n_layer=8, scan_layers=False, **base), qu, 1024)
    del qu

    cfg16 = Qwen3Config(n_layer=16, scan_layers=True, **base)
    q16, _ = _distinct_base_stacked(cfg16, Qwen3, fmt="int8")
    leg("scan_L16_cache1024", cfg16, q16, 1024)

    a, b = results.get("scan_L8_cache1024"), results.get("scan_L16_cache1024")
    if a and b:
        results["scan_per_layer_marginal_ms"] = round((b - a) / 8, 3)
    flush()
    print("wrote", OUT)


if __name__ == "__main__":
    main()
