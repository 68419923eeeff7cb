"""Per-matmul streaming rates for thin (decode-shaped) activations.

Finding 11 left a gap: int8 8B decode runs ~77 ms/token against what
looked like a ~25 ms whole-tree read floor. This probe separates
per-DISPATCH fixed cost from the per-iteration marginal cost with a
two-point fit: each op runs in a `lax.scan` chain of 16 and then 256
iterations inside one jit dispatch; ``marginal = (t256·256 −
t16·16)/240`` cancels the fixed part (the fixed part measured ~20 ms in round 5 —
which also contaminated INT8_TILE_PROBE's
"floor": the honest int8 weight floor is bytes/marginal-rate, not that
artifact's 24.8 ms).

Ops probed at m=16 (the 16-slot decode activation), per layer shape of
the 8B geometry: int8 XLA (`dequant_matmul`, the production path), the
int8 Pallas kernel, and plain bf16 dense (2x bytes control). The chain
feeds each output back through a mean-fold so nothing hoists.

Writes ``THIN_MATMUL_PROBE.json``.
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
import numpy as np

from llm_in_practise_tpu.ops import int8_matmul as int8_mm
from llm_in_practise_tpu.quant import int8

OUT = os.path.join(REPO, "THIN_MATMUL_PROBE.json")
M = 16
SHAPES = {  # the distinct matmuls of one 8B layer (d4096); xN = count/layer
    "qkv_q": (4096, 4096, 2),    # q_proj + out_proj
    "kv": (4096, 1024, 2),       # k_proj + v_proj
    "mlp_in": (4096, 12288, 2),  # gate + up
    "mlp_out": (12288, 4096, 1),
}


def dispatch_time(op, x0, iters, n=5):
    def run(x):
        def body(c, _):
            y = op(c)
            c2 = c + jnp.mean(y, axis=-1, keepdims=True).astype(c.dtype)
            return c2, ()
        c, _ = jax.lax.scan(body, x, None, length=iters)
        return c

    f = jax.jit(run)
    jax.block_until_ready(f(x0))
    jax.block_until_ready(f(x0))
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(x0)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def marginal_ms(op, x0):
    t16 = dispatch_time(op, x0, 16)
    t256 = dispatch_time(op, x0, 256)
    fixed = (t16 * 256 - t256 * 16) / 240          # per-dispatch part
    return (t256 - t16) / 240 * 1e3, fixed * 1e3


def main() -> None:
    from llm_in_practise_tpu.core.mesh import require_tpu

    require_tpu()
    rng = np.random.default_rng(0)
    results = {"m": M, "method": "two-point scan fit (16 vs 256 iters)"}
    for name, (k, nn_, per_layer) in SHAPES.items():
        w = jnp.asarray(rng.normal(0, 0.02, (k, nn_)), jnp.float32)
        t8 = int8.quantize(w)
        wb = w.astype(jnp.bfloat16)
        x = jnp.asarray(rng.normal(0, 1, (M, k)), jnp.bfloat16)
        row = {"per_layer": per_layer}
        for label, op, nbytes in [
            ("int8_xla", lambda c: int8.dequant_matmul(c, t8), k * nn_),
            ("int8_kernel", lambda c: int8_mm.int8_matmul(c, t8), k * nn_),
            ("bf16_dense", lambda c: c @ wb, 2 * k * nn_),
        ]:
            try:
                ms, fixed = marginal_ms(op, x)
                row[label] = {"marginal_ms": round(ms, 4),
                              "gbps": round(nbytes / ms / 1e6, 0),
                              "dispatch_fixed_ms": round(fixed, 1)}
                print(f"{name} {label}: {ms:.4f} ms marginal "
                      f"({nbytes/ms/1e6:.0f} GB/s), fixed {fixed:.1f} ms",
                      flush=True)
            except Exception as e:
                row[label] = {"error": f"{type(e).__name__}: {str(e)[:160]}"}
                print(f"{name} {label}: FAILED {e}", flush=True)
        results[name] = row
        with open(OUT, "w") as f:
            json.dump(results, f, indent=2)
    missing = [s for s in SHAPES
               if "marginal_ms" not in results[s].get("int8_xla", {})]
    bound = 36 * sum(
        results[s]["int8_xla"]["marginal_ms"] * results[s]["per_layer"]
        for s in SHAPES if s not in missing)
    results["isolated_matmul_bound_ms_per_token_36L"] = round(bound, 1)
    if missing:
        results["bound_missing_ops"] = missing  # bound understates
    print(f"isolated int8 matmul bound (36L): {bound:.1f} ms/token",
          flush=True)
    with open(OUT, "w") as f:
        json.dump(results, f, indent=2)
    print("wrote", OUT)


if __name__ == "__main__":
    main()
