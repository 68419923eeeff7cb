#!/usr/bin/env python3
"""Bake-off on the chip (PR 48): ONE reader of Phi-4-mini-flash's paged
layer (the full layer or a cross layer: two softmaxes of 20 query pairs
over their own key pages, the value pages read once), at the shapes
``phi-4-mini-flash.grounded-reasoning`` runs: 16 slots of 1,024 pages of 16
rows, a pool of 16,385 pages in three by-pages buffers (``k1``, ``k2``
``bf16[16385, 16, 640]``, ``v`` ``bf16[16385, 16, 1280]``), block tables
scattered over the pool.

    chiprun -- python tools/paged_decode_bakeoff.py

- ``gathered``: what the decode program did before this PR: the view's
  gather at the pow2 width that holds the longest row
  (``paged_kv.take_pages``, once a step for eight readers) and
  ``swa.paired_decode_attention`` over it, each a program of its own;
- ``in_place``: ``swa.paged_paired_decode_attention`` at 8 / 16 / 32 / 64
  pages a block, the flat work list's making inside the program (a step
  makes it once for its eight readers).

Three sets of lengths: ``traffic`` (16 decoding rows as the cell's workload
file draws them: a prompt plus a uniform share of its answer), ``full``
(every row at 16,384) and ``quarter`` (4 of 16 rows live). Times are DEVICE
times from a profiler trace (``benchmark/trace.py``): the median execution
of the candidate's program. ``floor_ms``: the attended rows' bytes (5,120 a
row) over the chip's bandwidth. Prints one JSON line a candidate and writes
them to ``chiprun_out/paged_decode_bakeoff.json``. Refuses to run without a
TPU; ``--rehearse`` drives the wiring on the CPU at a toy shape (interpret
mode, no times) and holds the two paths to each other.
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

SLOTS, PAIRS, KV_PAIRS, HEAD, PAGE, CACHE = 16, 20, 10, 64, 16, 16384
BLOCKS = (8, 16, 32, 64)
REPS = 5
WORKLOAD = os.path.join(ROOT, "benchmark", "workloads",
                        "phi-4-mini-flash.grounded-reasoning.json")


def traffic_lengths(slots: int, cache: int, rng) -> np.ndarray:
    """Lengths of ``slots`` decoding rows as the cell's traffic gives
    them: a drawn prompt plus a uniform share of its drawn answer."""
    from benchmark import traffic

    with open(WORKLOAD, encoding="utf-8") as f:
        workload = json.load(f)
    prompts = traffic.draw_lengths(workload["prompt_tokens"], slots, rng)
    answers = traffic.draw_lengths(workload["output_tokens"], slots, rng)
    scale = cache / int(workload["max_total_tokens"])   # a toy's cache
    return np.clip((prompts + rng.uniform(size=slots) * answers) * scale,
                   1, cache).astype(np.int32)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmark import device, trace
    from llm_in_practise_tpu.ops import swa_attention as swa
    from llm_in_practise_tpu.serve import paged_kv

    if args.rehearse:
        slots, pairs, kv_pairs, hd, cache, blocks = 4, 4, 2, 16, 256, (2, 4)
        peak_bw = None
    else:
        from llm_in_practise_tpu.core.mesh import require_tpu

        require_tpu()
        slots, pairs, kv_pairs, hd, cache, blocks = (
            SLOTS, PAIRS, KV_PAIRS, HEAD, CACHE, BLOCKS)
        peak_bw = device.peaks(jax.devices()[0].device_kind)[1]
    scale = hd ** -0.5
    per_slot = cache // PAGE
    n_pages = slots * per_slot + 1
    widths = (kv_pairs * hd, kv_pairs * hd, kv_pairs * 2 * hd)
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    pools = [jnp.pad(jax.random.normal(k, (n_pages, PAGE, w), jnp.bfloat16),
                     ((0, 0), (0, 0), (0, paged_kv.lane_whole(w) - w)))
             for k, w in zip(keys, widths)]
    qs = [jax.random.normal(k, (slots, 1, pairs, hd), jnp.bfloat16)
          for k in keys[3:]]
    rng = np.random.default_rng(48)
    # every slot's pages scattered over the pool, as a long run leaves them
    table = jnp.asarray(rng.permutation(np.arange(1, n_pages)).reshape(
        slots, per_slot).astype(np.int32))
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

    def gather(k1, k2, v, idx):
        return tuple(paged_kv.take_pages(buf, idx, w)
                     for buf, w in zip((k1, k2, v), widths))

    def reader(q1, q2, k1, k2, v, index):
        return swa.paired_decode_attention((q1, q2), (k1, k2), v, index,
                                           scale=scale)

    full = np.full((slots,), cache, np.int32)
    quarter = traffic_lengths(slots, cache, rng)
    quarter[slots // 4:] = 0
    for what, lengths in (("traffic", traffic_lengths(slots, cache, rng)),
                          ("full", full), ("quarter", quarter)):
        rows = int(lengths.sum())
        tags = dict(lengths=what, rows_attended=rows,
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
        # idle rows attend the trash they gathered, as today
        index = jnp.asarray(np.maximum(lengths - 1, 0))
        ms, want = device_ms(reader, f"reader_{len(lines)}", *qs, *view,
                             index)
        emit(what="gathered", part="one reader", width=width, ms=ms, **tags)
        for ppb in blocks:
            def in_place(q1, q2, k1, k2, v, table, lengths, ppb=ppb):
                return swa.paged_paired_decode_attention(
                    (q1, q2), (k1, k2), v, table, lengths, scale=scale,
                    kv_heads=kv_pairs, pages_per_block=ppb)

            block = PAGE * ppb      # a row's length up to whole blocks
            row = dict(what="in_place", pages_per_block=ppb,
                       rows_read=int((-(-lengths // block) * block).sum()),
                       **tags)
            try:
                ms, got = device_ms(in_place, f"in_place_{len(lines)}", *qs,
                                    *pools, table, jnp.asarray(lengths))
                live = lengths > 0
                row.update(ms=ms, max_abs_diff=max(
                    float(jnp.max(jnp.abs(a[live] - b[live])))
                    for a, b in zip(got, want)))
            except Exception as e:      # a block the compiler refuses
                row["error"] = str(e)[:300]
            emit(**row)

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
