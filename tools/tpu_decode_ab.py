"""Decode-step attribution at 8B scale: fused NF4 kernels vs XLA dequant.

The 8B serving ladder (BENCH_SERVE_QWEN3_r03.json) measured ~140-157 ms
TPOT at 16 slots. Weights-bound decode on paper is ~7 ms (4.5 GiB NF4 +
1.2 GiB bf16 embed at ~800 GB/s), so something is ~18x off. Suspects:
the fused NF4 Pallas kernel's thin-activation tiling at d4096, the f32
151936-vocab lm_head, and the per-dispatch host cost. This tool times a
single 16-slot decode step through each path and shape variant and
writes ``DECODE_AB_8B.json``:

- fused kernels vs XLA dequant (``use_kernels``) — which serves better
  at this scale decides ``QuantizedModel``'s default
- with vs without the lm_head (``return_hidden=True``) — the head's share

Run: ``python tools/tpu_decode_ab.py`` (env ``AB_GEOM=small|8b``).
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
from llm_in_practise_tpu.peft.fused import fused_quant_apply

OUT = os.path.join(REPO, "DECODE_AB_8B.json")
GEOMS = {
    "small": dict(hidden_size=2048, intermediate_size=6144, n_layer=28,
                  n_head=16, n_kv_head=8, head_dim=128),
    "8b": dict(hidden_size=4096, intermediate_size=12288, n_layer=36,
               n_head=32, n_kv_head=8, head_dim=128),
}
SLOTS = 16


def timeit(fn, n=5):
    jax.block_until_ready(fn())  # compile
    jax.block_until_ready(fn())  # warm — retire before the clock starts
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main() -> None:
    from llm_in_practise_tpu.core.mesh import require_tpu

    require_tpu()
    geom = GEOMS[os.environ.get("AB_GEOM", "8b")]
    cfg = Qwen3Config(
        vocab_size=151936, max_seq_len=1024, rope_theta=1e6,
        tie_word_embeddings=True, remat=False, compute_dtype="bfloat16",
        scan_layers=True, **geom,
    )
    print("quantizing...", flush=True)
    qparams, qs_sec = _distinct_base_stacked(cfg, Qwen3)
    model = Qwen3(cfg)
    cache0 = model.init_cache(SLOTS, 1024, dtype=jnp.bfloat16)
    cache0[0]["index"] = jnp.full((SLOTS,), 64, jnp.int32)
    tok = jnp.ones((SLOTS, 1), jnp.int32)
    results = {"geom": geom, "slots": SLOTS, "quantize_s": round(qs_sec, 1)}

    def flush(final=False):
        # crash-safe both ways: every measurement lands in OUT.partial
        # as it completes (the first int8 run OOM'd after 6 good NF4
        # measurements and lost all of them), and the committed artifact
        # is only atomically replaced by a COMPLETED run
        tmp = OUT + ".partial"
        with open(tmp, "w") as f:
            json.dump(results, f, indent=2)
        if final:
            os.replace(tmp, OUT)

    def decode_path(use_kernels, head):
        def step(qp, cache):
            kw = {} if head else {"return_hidden": True}
            # both variants return (out, new_cache): the KV writes stay
            # live in the no-head variant instead of being DCE'd, so the
            # full-vs-no-head delta isolates the lm_head alone
            return fused_quant_apply(
                model, qp, tok, compute_dtype=jnp.bfloat16,
                use_kernels=use_kernels, cache=cache, **kw)

        f = jax.jit(step)
        return lambda: f(qparams, cache0)

    for name, fn in [
        ("fused_full", decode_path(True, head=True)),
        ("fused_no_head", decode_path(True, head=False)),
        ("xla_full", decode_path(False, head=True)),
        ("xla_no_head", decode_path(False, head=False)),
    ]:
        try:
            dt = timeit(fn)
            results[name + "_ms"] = round(dt * 1e3, 1)
            print(f"{name}: {dt*1e3:.1f} ms/step", flush=True)
        except Exception as e:  # record, keep going
            results[name + "_error"] = f"{type(e).__name__}: {str(e)[:200]}"
            print(f"{name}: FAILED {e}", flush=True)
        flush()

    # --- W8A16 leg: same geometry, int8 per-channel base ---------------
    # NF4 decode measured DEQUANT-bound (the nibble unpack through the
    # VPU, not the 4-bit byte stream). Int8 pays 2x the bytes but decodes
    # with one native convert — if the dequant model is right, this leg
    # should land near the weight-traffic bound. Free the NF4 tree first:
    # both bases resident would exceed HBM at 8B.
    import gc

    from llm_in_practise_tpu.quant.int8 import Int8Tensor

    del qparams
    gc.collect()
    print("quantizing int8...", flush=True)
    qparams, q8_sec = _distinct_base_stacked(cfg, Qwen3, fmt="int8")
    results["int8_quantize_s"] = round(q8_sec, 1)
    results["int8_base_bytes"] = int(sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(
            qparams, is_leaf=lambda v: isinstance(v, Int8Tensor))))
    flush()

    for name, fn in [
        ("int8_fused_full", decode_path(True, head=True)),
        ("int8_fused_no_head", decode_path(True, head=False)),
    ]:
        try:
            dt = timeit(fn)
            results[name + "_ms"] = round(dt * 1e3, 1)
            print(f"{name}: {dt*1e3:.1f} ms/step", flush=True)
        except Exception as e:
            results[name + "_error"] = f"{type(e).__name__}: {str(e)[:200]}"
            print(f"{name}: FAILED {e}", flush=True)
        flush()

    flush(final=True)
    print("wrote", OUT)


if __name__ == "__main__":
    main()
