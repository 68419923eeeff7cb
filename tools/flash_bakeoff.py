#!/usr/bin/env python3
"""Training flash-attention bake-off on the chip (PR 46), at the shape
``qwen3-14b-qlora.sft-1k`` runs: bf16, batch 8 x 1,024, 40 query heads on
8 K/V heads of 128, causal.

    chiprun -- python tools/flash_bakeoff.py [--parent <tree>] [--lengths 768]

Each candidate takes ``(B, L, H, D)`` queries and ``(B, L, Hkv, D)`` keys
and values, as ``dot_product_attention`` hands them over, and is timed
forward alone and forward + backward (``jax.vjp``), whatever it needs
around its kernels (a K/V repeat, layout copies) included. The timed
program's arrays are the projections' ``(B, L, heads·D)``, split into heads
inside it and joined again behind the call, as a model's layer does:

- ``ops/flash_attention.py`` at its own tiles (``pick_blocks``), at each
  candidate tile, and with the block the diagonal crosses worked in bands
  of 128 / 256 / 512 queries or whole (``bands``; the module's ``_BAND``,
  set from here for the one call);
- ``jax.experimental.pallas.ops.tpu.flash_attention`` of the installed jax
  at its default block sizes (128) and at 512, K/V repeated to 40 heads and
  everything moved to its ``(B, H, L, D)``;
- with ``--parent <tree>``, that checkout's ``ops/flash_attention.py``
  behind the K/V repeat its ``dot_product_attention`` made (the kernel
  before this PR: ``git archive 1c4fac0 | tar -x -C <tree>``);
- ``dense_attention`` at every length, for ``_pick_impl``'s crossover
  (alone a call's float32 scores fit at 1,024; in the step they do not:
  docs/perf.md Finding 3).

Times are DEVICE times from a profiler trace (``benchmark/trace.py``): the
median execution of the candidate's program, and the part of it inside
Pallas custom calls. ``roofline_pct`` is the least time for the work the
algorithm needs (``benchmark/flops.py::flash_attention_cost``; forward 1 x,
dK/dV 2 x, dQ 1.5 x) over the program's time. Prints one JSON line a
candidate and writes them to ``chiprun_out/flash_bakeoff.json``. Refuses to
run without a TPU; ``--rehearse`` drives the wiring on the CPU at a toy
shape (interpret mode, no times).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

B, L, H, HK, D = 8, 1024, 40, 8, 128
TILES = ((128, 128), (256, 256), (256, 512), (512, 256), (512, 512),
         (512, 1024), (1024, 512), (1024, 1024))
BANDS = (128, 256, 512, 1024)
REPS = 5


def repeat_kv(fn):
    """``fn`` behind the K/V repeat an equal-heads kernel needs."""
    def call(q, k, v):
        g = q.shape[2] // k.shape[2]
        return fn(q, jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2))
    return call


def installed(block: int | None, scale: float):
    from jax.experimental.pallas.ops.tpu import flash_attention as jfa

    sizes = None if block is None else jfa.BlockSizes(
        block_q=block, block_k_major=block, block_k=block, block_b=1,
        block_q_major_dkv=block, block_k_major_dkv=block, block_k_dkv=block,
        block_q_dkv=block, block_k_major_dq=block, block_k_dq=block,
        block_q_dq=block)

    def call(q, k, v):
        heads_first = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
        return heads_first(jfa.flash_attention(
            heads_first(q), heads_first(k), heads_first(v), causal=True,
            sm_scale=scale, block_sizes=sizes))
    return repeat_kv(call)


def parent_kernel(tree: str):
    path = os.path.join(tree, "llm_in_practise_tpu", "ops",
                        "flash_attention.py")
    spec = importlib.util.spec_from_file_location("parent_flash", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return repeat_kv(mod.flash_attention)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", help="a checkout whose kernel is timed too")
    ap.add_argument("--lengths", type=int, nargs="*", default=[],
                    help="further lengths: the shipped tiles and dense")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmark import device, flops, trace
    from llm_in_practise_tpu.ops import flash_attention as fa
    from llm_in_practise_tpu.ops.attention import dense_attention

    if args.rehearse:
        b, length, h, hk, tiles = 1, 256, 4, 2, ((128, 128), (256, 256))
        peak_flops = peak_bw = None
    else:
        from llm_in_practise_tpu.core.mesh import require_tpu

        require_tpu()
        b, length, h, hk, tiles = B, L, H, HK, TILES
        peak_flops, peak_bw = device.peaks(jax.devices()[0].device_kind)
    scale = D ** -0.5
    lines = []

    def emit(**kw):
        lines.append(kw)
        print(json.dumps(kw), flush=True)

    def inputs(n):
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        rnd = lambda i, heads: jax.random.normal(  # noqa: E731
            keys[i], (b, n, heads * D)).astype(jnp.bfloat16)
        return rnd(0, h), rnd(1, hk), rnd(2, hk), rnd(3, h)

    def device_ms(fn, name, *xs):
        """Median device time of ``fn``'s program and of the Pallas custom
        calls in it, over ``REPS`` executions under one capture."""
        fn.__name__ = name
        run = jax.jit(fn)
        jax.block_until_ready(run(*xs))
        if args.rehearse:
            return None, None
        with tempfile.TemporaryDirectory() as log_dir:
            with trace.capture(log_dir):
                for _ in range(REPS):
                    jax.block_until_ready(run(*xs))
            seen = trace.reduce(trace.load(trace.newest_xplane(log_dir)))
        runs = seen["programs"][f"jit_{name}"]
        kernels = sum(s for op, s in seen["op_seconds"].items()
                      if op.endswith("custom-call"))
        return 1e3 * float(np.median(runs)), 1e3 * kernels / REPS

    def bake(what, attn, n, **tags):
        q, k, v, do = inputs(n)
        heads = lambda x: x.reshape(b, n, -1, D)  # noqa: E731

        def fwd(q, k, v):
            return attn(heads(q), heads(k), heads(v)).reshape(q.shape)

        def fwd_bwd(q, k, v, do):
            out, vjp = jax.vjp(fwd, q, k, v)
            return (out,) + vjp(do)

        cost, nbytes = flops.flash_attention_cost(
            b, n, n, h, hk, D, causal=True)
        row = dict(what=what, length=n, **tags)
        try:
            for key, fn, xs, work in (("fwd", fwd, (q, k, v), 1.0),
                                      ("fwd_bwd", fwd_bwd, (q, k, v, do),
                                       4.5)):
                ms, kernel_ms = device_ms(fn, f"{key}_{len(lines)}", *xs)
                if ms is None:
                    continue
                least = 1e3 * max(work * cost / peak_flops,
                                  nbytes / peak_bw)
                row.update({f"{key}_ms": ms, f"{key}_kernel_ms": kernel_ms,
                            f"{key}_roofline_pct": 100 * least / ms})
        except Exception as e:          # a tile the compiler refuses
            row["error"] = str(e)[:300]
        emit(**row)

    ours = lambda **kw: (lambda q, k, v: fa.flash_attention(  # noqa: E731
        q, k, v, scale=scale, **kw))
    for n in [length] + args.lengths:
        block_q, block_k = fa.pick_blocks(n, D, jnp.bfloat16)
        bake("shipped", ours(), n, block_q=block_q, block_k=block_k)
    for bq, bk in tiles:
        bake("tiles", ours(block_q=bq, block_k=bk), length,
             block_q=bq, block_k=bk)
    shipped_band, side = fa._BAND, max(bq for bq, _ in tiles)
    try:
        for band in BANDS if not args.rehearse else (128,):
            fa._BAND = band     # read when the kernel is traced
            bake("bands", ours(block_q=side, block_k=side), length,
                 block_q=side, block_k=side, band=band)
    finally:
        fa._BAND = shipped_band
    if not args.rehearse:   # the installed kernel has no interpret switch
        bake("installed_jax", installed(None, scale), length, block=128)
        bake("installed_jax", installed(512, scale), length, block=512)
    if args.parent:
        bake("parent", parent_kernel(args.parent), length,
             block_q=128, block_k=128)
    for n in [length] + args.lengths:
        bake("dense", lambda q, k, v: dense_attention(
            q, k, v, causal=True, scale=scale), n)

    if args.rehearse:
        return 0
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "flash_bakeoff.json"), "w",
              encoding="utf-8") as f:
        json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
