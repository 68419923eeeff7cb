#!/usr/bin/env python3
"""Bake-off on the chip (PR 48, PR 49): ONE reader of a paged layer whose
decode reads the pool's pages where they lie, at the shapes its cell runs:
16 slots of pages of 16 rows, a pool of (16 x pages a slot + 1) pages in
by-pages buffers, block tables scattered over the pool.

    chiprun -- python tools/paged_decode_bakeoff.py \
        [--geometry phi4 mimo trinity deepseek]

- ``phi4`` (``phi-4-mini-flash.grounded-reasoning``): the full layer or a
  cross layer, two softmaxes of 20 query pairs over their own key pages
  (``bf16[16385, 16, 640]`` twice), the value pages (``[.., 1280]``) read
  once; slots of 1,024 pages;
- ``mimo`` (``mimo-v2.5.agent-context``): a global layer, one softmax of 64
  heads over 4 K/V heads, keys of 192 over values of 128 (``bf16[32769, 16,
  768]`` and ``[.., 512]``); slots of 2,048 pages;
- ``trinity`` (``trinity-large.mixed-lengths``): a global layer, 48 heads
  over 8 K/V heads of 128 (``bf16[32769, 16, 1024]`` twice);
- ``deepseek`` (``deepseek-v3.long-doc-qa``, PR 54): a latent layer, the
  absorbed form of 128 heads over ONE pool whose 576-of-640-lane row is key
  and value at once (``bf16[16385, 16, 640]``, copied once a page); slots
  of 1,024 pages; the absorb and ``W^V`` einsums are in both paths' times.

For each:

- ``gathered``: what the decode program did before: the view's gather at the
  pow2 width that holds the longest row (``paged_kv.take_pages``, once a
  step) and ``swa.decode_attention`` / ``swa.paired_decode_attention`` /
  ``mla.decode_attention`` over it, each a program of its own;
- ``in_place``: ``swa.paged_decode_attention`` /
  ``swa.paged_paired_decode_attention`` / ``mla.paged_decode_attention`` at
  several numbers of pages a block,
  the flat work list's making inside the program (a step makes it once for
  all its readers).

Three sets of lengths: ``traffic`` (16 decoding rows as the cell's workload
file draws them: a prompt plus a uniform share of its answer), ``full``
(every row at the longest a request may be: 16,384; 24,576; 14,848) and
``quarter`` (4 of 16 rows live). Times are DEVICE times from a profiler trace
(``benchmark/trace.py``): the median execution of the candidate's program.
``floor_ms``: the attended rows' bytes (a row of every buffer) over the
chip's bandwidth. Prints one JSON line a candidate and writes them to
``chiprun_out/paged_decode_bakeoff.json``. Refuses to run without a TPU;
``--rehearse`` drives the wiring on the CPU at a toy shape (interpret mode,
no times) and holds the two paths to each other.
"""

from __future__ import annotations

import argparse
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

SLOTS, PAGE = 16, 16
REPS = 5
# name -> (cell, query heads a softmax, K/V heads, key and value widths a
# head, softmaxes a head, a slot's rows, the longest row, pages a block);
# 0 softmaxes: a LATENT pool, its one row the key (rank + rope wide) and,
# its first ``value width`` columns, the value, under absorbed queries
GEOMETRY = {
    "phi4": ("phi-4-mini-flash.grounded-reasoning", 20, 10, 64, 128, 2,
             16384, 16384, (8, 16, 32, 64)),
    "mimo": ("mimo-v2.5.agent-context", 64, 4, 192, 128, 1, 32768, 24576,
             (16, 32, 64)),
    "trinity": ("trinity-large.mixed-lengths", 48, 8, 128, 128, 1, 32768,
                24576, (16, 32, 64)),
    "deepseek": ("deepseek-v3.long-doc-qa", 128, 1, 576, 512, 0, 16384,
                 14848, (16, 32, 64)),
}


def traffic_lengths(cell: str, slots: int, cache: int, rng) -> np.ndarray:
    """Lengths of ``slots`` decoding rows as the cell's traffic gives
    them: a drawn prompt plus a uniform share of its drawn answer."""
    from benchmark import traffic

    with open(os.path.join(ROOT, "benchmark", "workloads", cell + ".json"),
              encoding="utf-8") as f:
        workload = json.load(f)
    prompts = traffic.draw_lengths(workload["prompt_tokens"], slots, rng)
    answers = traffic.draw_lengths(workload["output_tokens"], slots, rng)
    # a toy's cache is shorter than the cell's longest request
    scale = min(1.0, cache / int(workload["max_total_tokens"]))
    return np.clip((prompts + rng.uniform(size=slots) * answers) * scale,
                   1, cache).astype(np.int32)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--geometry", nargs="+", choices=sorted(GEOMETRY),
                    default=sorted(GEOMETRY))
    args = ap.parse_args()

    from benchmark import device, trace
    from llm_in_practise_tpu.ops import mla_attention as mla
    from llm_in_practise_tpu.ops import swa_attention as swa
    from llm_in_practise_tpu.serve import paged_kv

    peak_bw = None
    if not args.rehearse:
        from llm_in_practise_tpu.core.mesh import require_tpu

        require_tpu()
        peak_bw = device.peaks(jax.devices()[0].device_kind)[1]
    lines = []

    def emit(**kw):
        lines.append(kw)
        print(json.dumps(kw), flush=True)

    def device_ms(fn, name, *xs):
        def call(*xs):      # a program of its own name, whatever jit cached
            return fn(*xs)

        call.__name__ = name
        run = jax.jit(call)
        out = jax.block_until_ready(run(*xs))
        if args.rehearse:
            return None, out
        with tempfile.TemporaryDirectory() as log_dir:
            with trace.capture(log_dir):
                for _ in range(REPS):
                    jax.block_until_ready(run(*xs))
            seen = trace.reduce(trace.load(trace.newest_xplane(log_dir)))
        return 1e3 * float(np.median(seen["programs"][f"jit_{name}"])), out

    for geometry in args.geometry:
        cell, heads, kv_heads, dq, dv, n, cache, longest, blocks = (
            GEOMETRY[geometry])
        slots, latent = SLOTS, n == 0
        if args.rehearse:
            slots, heads, kv_heads, dq, dv, cache, longest, blocks = (
                4, 4, 2, 16, 32, 256, 256, (2, 4))
            if latent:
                kv_heads, dq, dv = 1, 24, 16
        scale = dq ** -0.5
        per_slot = cache // PAGE
        n_pages = slots * per_slot + 1
        widths = ((kv_heads * dq,) if latent
                  else (kv_heads * dq,) * n + (kv_heads * dv,))
        keys = jax.random.split(jax.random.PRNGKey(0),
                                2 * n + 1 if n else 4)
        pools = [jnp.pad(
            jax.random.normal(k, (n_pages, PAGE, w), jnp.bfloat16),
            ((0, 0), (0, 0), (0, paged_kv.lane_whole(w) - w)))
            for k, w in zip(keys, widths)]
        if latent:
            # q_nope, q_rope and W_kvb (rank, heads, nope + v), the heads'
            # nope and value widths a quarter of the rank as published
            dn, rope = dv // 4, dq - dv
            scale = (dn + rope) ** -0.5
            qs = [jax.random.normal(keys[1], (slots, 1, heads, dn),
                                    jnp.bfloat16),
                  jax.random.normal(keys[2], (slots, 1, heads, rope),
                                    jnp.bfloat16),
                  (jax.random.normal(keys[3], (dv, heads, 2 * dn),
                                     jnp.float32) * dv ** -0.5).astype(
                      jnp.bfloat16)]
        else:
            qs = [jax.random.normal(k, (slots, 1, heads, dq), jnp.bfloat16)
                  for k in keys[n + 1:]]
        nq = len(qs)
        rng = np.random.default_rng(48)
        # every slot's pages scattered over the pool, as a long run leaves
        # them
        table = jnp.asarray(rng.permutation(np.arange(1, n_pages)).reshape(
            slots, per_slot).astype(np.int32))

        def gather(*xs):
            *bufs, idx = xs
            return tuple(paged_kv.take_pages(buf, idx, w)
                         for buf, w in zip(bufs, widths))

        def reader(*xs):
            q, (*k, v, index) = xs[:nq], xs[nq:]
            if latent:
                return (mla.decode_attention(
                    q[0], q[1], v, index, q[2], rank=dv,
                    scale=scale),)
            if n == 1:
                return (swa.decode_attention(q[0], k[0], v, index,
                                             scale=scale),)
            return swa.paired_decode_attention(q, k, v, index, scale=scale)

        def walker(ppb):
            def in_place(*xs):
                q, (*k, v, table, lengths) = xs[:nq], xs[nq:]
                if latent:
                    return (mla.paged_decode_attention(
                        q[0], q[1], v, q[2], rank=dv, scale=scale,
                        table=table, lengths=lengths,
                        pages_per_block=ppb),)
                kw = dict(scale=scale, kv_heads=kv_heads,
                          pages_per_block=ppb)
                if n == 1:
                    return (swa.paged_decode_attention(
                        q[0], k[0], v, table, lengths, v_dim=dv, **kw),)
                return swa.paged_paired_decode_attention(
                    q, k, v, table, lengths, **kw)
            return in_place

        quarter = traffic_lengths(cell, slots, cache, rng)
        quarter[slots // 4:] = 0
        for what, lengths in (
                ("traffic", traffic_lengths(cell, slots, cache, rng)),
                ("full", np.full((slots,), longest, np.int32)),
                ("quarter", quarter)):
            rows = int(lengths.sum())
            tags = dict(geometry=geometry, lengths=what, rows_attended=rows,
                        mean_live=rows // max(int((lengths > 0).sum()), 1))
            if peak_bw is not None:
                tags["floor_ms"] = 1e3 * rows * sum(widths) * 2 / peak_bw
            width = PAGE
            while width < lengths.max():
                width *= 2
            ms, view = device_ms(gather, f"gather_{len(lines)}", *pools,
                                 table[:, :width // PAGE])
            emit(what="gathered", part="gather (once a step)", width=width,
                 ms=ms, **tags)
            # idle rows attend the trash they gathered, as before
            index = jnp.asarray(np.maximum(lengths - 1, 0))
            ms, want = device_ms(reader, f"reader_{len(lines)}", *qs, *view,
                                 index)
            emit(what="gathered", part="one reader", width=width, ms=ms,
                 **tags)
            for ppb in blocks:
                block = PAGE * ppb      # a row's length up to whole blocks
                row = dict(what="in_place", pages_per_block=ppb,
                           rows_read=int(
                               (-(-lengths // block) * block).sum()),
                           **tags)
                try:
                    ms, got = device_ms(
                        walker(ppb), f"in_place_{len(lines)}", *qs, *pools,
                        table, jnp.asarray(lengths))
                    live = lengths > 0
                    row.update(ms=ms, max_abs_diff=max(
                        float(jnp.max(jnp.abs(
                            a[live].astype(jnp.float32)
                            - b[live].astype(jnp.float32))))
                        for a, b in zip(got, want)))
                except Exception as e:      # a block the compiler refuses
                    row["error"] = str(e)[:300]
                emit(**row)
        del pools, view, want

    if args.rehearse:
        worst = max(r["max_abs_diff"] for r in lines if "max_abs_diff" in r)
        if not worst < 0.05:
            raise SystemExit(f"the two paths differ by {worst}")
        return 0
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "paged_decode_bakeoff.json"),
              "w", encoding="utf-8") as f:
        json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
