#!/usr/bin/env python3
"""Latent-attention bake-off on the chip (PR 34), at DeepSeek-V3's published
widths (128 heads, latent 512 + 64, nope 128, v 128), for the shapes
``deepseek-v3.long-doc-qa`` runs:

    chiprun -- python tools/mla_bakeoff.py

- the PREFILL form: one 2,048-query chunk against a row of 4k / 8k / 16k
  keys through ``ops/mla_attention.py::prefill_attention``, naive
  (decompress ``KEY_BLOCK`` keys at a time, flash kernel over them) against
  absorbed (the latent as the one key/value head, same kernel), and the
  naive form's kernel tiles;
- the DECODE form: 16 rows of one query against views 8,192 and 16,384
  wide (absorbed, XLA einsums);
- the held share of a routed layer (16 of 256 experts, width 2,048 over
  hidden 7,168, top-8) at 2,048 tokens (a chunk row) and 16 (a decode
  step): route + drop + grouped matmuls + scatter-add.

Prints one JSON line per variant (median milliseconds, and the share of
the form's own least time where that is meaningful) and writes them to
``chiprun_out/mla_bakeoff.json``. Refuses to run without a TPU.
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

H, DN, DR, DV, RANK = 128, 128, 64, 128, 512
HIDDEN, WIDTH, EXPERTS, HELD, TOP_K = 7168, 2048, 256, 16, 8


def timed(fn, *args, reps: int = 5) -> float:
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t)
    return 1e3 * float(np.median(out))


def main() -> int:
    from benchmark import device, flops_mla
    from llm_in_practise_tpu.core.mesh import require_tpu
    from llm_in_practise_tpu.ops import grouped_experts as ge
    from llm_in_practise_tpu.ops import mla_attention as mla

    require_tpu()
    peak_flops, peak_bw = device.peaks(jax.devices()[0].device_kind)
    bf = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 12)
    rnd = lambda i, shape, s=1.0: (s * jax.random.normal(  # noqa: E731
        keys[i], shape)).astype(bf)
    w_kvb = rnd(0, (RANK, H, DN + DV), 0.02)
    lines = []

    def emit(**kw):
        lines.append(kw)
        print(json.dumps(kw), flush=True)

    prefill = jax.jit(mla.prefill_attention, static_argnames=(
        "rank", "scale", "absorbed", "key_block", "block_q", "block_k"))
    decode = jax.jit(mla.decode_attention, static_argnames=("rank", "scale"))
    lq = 2048
    q_nope, q_rope = rnd(1, (1, lq, H, DN)), rnd(2, (1, lq, H, DR))
    for n_keys in (4096, 8192, 16384):
        latent = rnd(3, (1, 16384, RANK + DR))
        start = jnp.asarray([n_keys - lq], jnp.int32)
        pairs = flops_mla.chunk_pairs(n_keys - lq, lq)
        fl, by = flops_mla.prefill_cost(pairs, n_keys, 1, H, DN, DR, DV,
                                        RANK)
        least_ms = 1e3 * max(fl / peak_flops, by / peak_bw)
        kb = mla.KEY_BLOCK
        variants = [("naive", False, 1024, 512, kb),
                    ("naive", False, 512, 512, kb),
                    ("naive", False, 1024, 1024, kb),
                    ("naive", False, 512, 1024, kb),
                    ("naive", False, 512, 2048, kb),
                    ("naive", False, 1024, 2048, kb),
                    ("naive", False, 1024, 1024, kb // 2),
                    ("naive", False, 1024, 1024, kb * 2),
                    ("absorbed", True, 1024, 512, kb),
                    ("absorbed", True, 1024, 1024, kb)]
        for form, absorbed, bq, bk, key_block in variants:
            fn = lambda *a, _kw=dict(  # noqa: E731
                rank=RANK, scale=0.1, absorbed=absorbed, block_q=bq,
                block_k=bk, key_block=key_block): prefill(*a, **_kw)
            try:
                ms = timed(fn, q_nope, q_rope, latent, start, w_kvb)
                emit(what="prefill", form=form, keys=n_keys, block_q=bq,
                     block_k=bk, key_block=key_block, ms=ms,
                     naive_count_least_ms=least_ms,
                     roofline_pct=100 * least_ms / ms)
            except Exception as e:      # a tile the compiler refuses
                emit(what="prefill", form=form, keys=n_keys, block_q=bq,
                     block_k=bk, key_block=key_block, error=str(e)[:300])

    for width in (8192, 16384):
        latent = rnd(4, (16, width, RANK + DR))
        index = jnp.full((16,), width * 3 // 4, jnp.int32)
        qn, qr = rnd(5, (16, 1, H, DN)), rnd(6, (16, 1, H, DR))
        ms = timed(lambda *a: decode(*a, rank=RANK, scale=0.1), qn, qr,
                   latent, index, w_kvb)
        fl, by = flops_mla.decode_cost(16 * (width * 3 // 4 + 1), 1, H,
                                       RANK, DR)
        least_ms = 1e3 * max(fl / peak_flops, by / peak_bw)
        emit(what="decode", rows=16, view=width, attended=width * 3 // 4 + 1,
             ms=ms, least_ms=least_ms, roofline_pct=100 * least_ms / ms,
             view_read_ms=1e3 * 2 * latent.size * 2 / peak_bw)

    w_router = rnd(7, (HIDDEN, EXPERTS), 0.02)
    bias = rnd(8, (EXPERTS,), 0.01)
    w_gate = rnd(9, (HELD, HIDDEN, WIDTH), 0.02)
    w_up = rnd(10, (HELD, HIDDEN, WIDTH), 0.02)
    w_down = rnd(11, (HELD, WIDTH, HIDDEN), 0.02)
    stream_ms = 1e3 * 3 * HELD * HIDDEN * WIDTH * 2 / peak_bw

    # the weights are ARGUMENTS: closed over, they would be 1.4 GB of
    # constants in the program
    def route_only(x, router, b):
        return ge.route(x, router, TOP_K, scoring="sigmoid", bias=b,
                        n_group=8, topk_group=4, scale=2.5)

    def layer(x, router, b, gate, up, down):
        ids, w = route_only(x, router, b)
        return ge.grouped_expert_ffn(x, ids, w, gate, up, down,
                                     held=(0, HELD), n_experts=EXPERTS)

    layer, route_only = jax.jit(layer), jax.jit(route_only)
    for n in (2048, 16):
        x = rnd(1, (n, HIDDEN))
        emit(what="held_experts", tokens=n,
             ms=timed(layer, x, w_router, bias, w_gate, w_up, w_down),
             route_ms=timed(route_only, x, w_router, bias),
             all_held_weights_stream_ms=stream_ms)

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "mla_bakeoff.json"), "w",
              encoding="utf-8") as f:
        json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
