#!/usr/bin/env python3
"""Grouped expert matmul bake-off on the chip (PR 29): ``jax.lax.ragged_dot``
against the Pallas megablox ``gmm`` for the SDAR-30B-A3B expert layer
(128 experts of width 768 over hidden 2048, top-8) at the two token counts
the serving path runs: N = 64 (a block-decode pass, 16 slots x 4) and
N = 256 (one prefill chunk).

    chiprun -- python tools/moe_bakeoff.py

Prints one JSON line per (N, variant): median milliseconds of the three
grouped matmuls of one layer (gate, up, down) on sorted assignments, and
the share of the weight-streaming floor (1.21 GB at the chip's published
bandwidth). Refuses to run without a TPU.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

E, H, I, K = 128, 2048, 768, 8


def ffn_ragged(xs, sizes, wg, wu, wd):
    g = jax.lax.ragged_dot(xs, wg, sizes)
    u = jax.lax.ragged_dot(xs, wu, sizes)
    return jax.lax.ragged_dot((jax.nn.silu(g) * u).astype(xs.dtype), wd,
                              sizes)


def make_ffn_gmm(t_in, t_out):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    def ffn(xs, sizes, wg, wu, wd):
        g = gmm(xs, wg, sizes, jnp.bfloat16, t_in)
        u = gmm(xs, wu, sizes, jnp.bfloat16, t_in)
        return gmm(jax.nn.silu(g) * u, wd, sizes, jnp.bfloat16, t_out)

    return ffn


def main() -> int:
    from llm_in_practise_tpu.core.mesh import require_tpu

    require_tpu()
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    wg = (0.02 * jax.random.normal(keys[0], (E, H, I))).astype(jnp.bfloat16)
    wu = (0.02 * jax.random.normal(keys[1], (E, H, I))).astype(jnp.bfloat16)
    wd = (0.02 * jax.random.normal(keys[2], (E, I, H))).astype(jnp.bfloat16)
    floor_ms = 1e3 * 3 * E * H * I * 2 / 819e9
    variants = {"ragged_dot": ffn_ragged}
    for t_in, t_out in (((128, 128, 128), (128, 128, 128)),
                        ((128, 512, 256), (128, 256, 512)),
                        ((128, 1024, 768), (128, 768, 1024)),
                        ((128, 2048, 768), (128, 768, 2048)),
                        ((512, 1024, 768), (512, 768, 1024))):
        variants[f"gmm{t_in}{t_out}".replace(" ", "")] = make_ffn_gmm(
            t_in, t_out)
    variants = {name: jax.jit(fn) for name, fn in variants.items()}
    want = {}
    for n in (64, 256):
        rng = np.random.default_rng(n)
        logits = rng.standard_normal((n, E))
        ids = np.argsort(-logits, axis=1)[:, :K].reshape(-1)
        order = np.argsort(ids, kind="stable")
        sizes = jnp.asarray(np.bincount(ids, minlength=E), jnp.int32)
        x = (jax.random.normal(keys[3], (n, H))).astype(jnp.bfloat16)
        xs = x[jnp.asarray(order // K)]
        for name, f in variants.items():
            if name.startswith("gmm(512") and n * K % 512:
                continue
            try:
                out = f(xs, sizes, wg, wu, wd).block_until_ready()
                ts = []
                for _ in range(20):
                    t0 = time.perf_counter()
                    f(xs, sizes, wg, wu, wd).block_until_ready()
                    ts.append(time.perf_counter() - t0)
                ms = 1e3 * float(np.median(ts))
                ref = want.setdefault(n, np.asarray(out, np.float32))
                err = float(np.abs(np.asarray(out, np.float32) - ref).max()
                            / np.abs(ref).max())
                print(json.dumps({
                    "n": n, "assignments": n * K, "variant": name,
                    "median_ms": ms, "min_ms": 1e3 * min(ts),
                    "weight_floor_ms": floor_ms,
                    "floor_share": floor_ms / ms,
                    "experts_touched": int((np.asarray(sizes) > 0).sum()),
                    "max_rel_diff_vs_first": err,
                    "device": jax.devices()[0].device_kind}), flush=True)
            except Exception as e:  # noqa: BLE001 - a refused tiling is a result
                print(json.dumps({"n": n, "variant": name,
                                  "error": f"{type(e).__name__}: "
                                           f"{str(e)[:300]}"}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
