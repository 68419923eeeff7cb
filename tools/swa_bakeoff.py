#!/usr/bin/env python3
"""Window / global attention bake-off on the chip (PR 39), at MiMo-V2.5's
published widths (64 query heads, keys 192, values 128; 4 K/V heads in a
global layer, 8 and a window of 128 in a window layer), for the shapes
``mimo-v2.5.agent-context`` runs:

    chiprun -- python tools/swa_bakeoff.py

- the GLOBAL chunk: 2,048 queries at the end of a row of 4,096 / 16,384 /
  32,768 keys through ``ops/swa_attention.py::flash_partial``, over the
  kernel's tiles;
- the WINDOW chunk: 2,048 queries over their own 2,048 keys under the band,
  over the tiles, a q tile holding one head's positions or (``fold``) every
  query head of a K/V head, beside the same call WITHOUT the band (every
  block under the diagonal visited and masked: what skipping buys), and
  the whole ``prefill_attention`` of a window layer (kernel + ring corner +
  sink);
- DECODE: 16 rows of one query over views 8,192 / 32,768 wide of FLAT rows
  (a global layer: 768 and 512 wide, the heads split on the query's side)
  and over the ring (a window layer), XLA einsums.

``--geometry trinity`` (PR 44) runs :func:`long_band` instead, at
Trinity-Large's widths (48 query heads on 8 K/V heads, keys and values
128, a window of 4,096) for the shapes ``trinity-large.mixed-lengths``
runs: the global chunk over 4,096 / 16,384 / 32,768 keys; a window
layer's chunk of 2,048 queries under a band of 4,096 over 6,144 keys
(``[the ring ‖ its own keys]``) over the tiles, and the whole
``prefill_attention`` of such a layer (the ring put in order + the
kernel); decode over flat views against a view with a head axis, and
over 16 rings of 4,096 rows.

Prints one JSON line a variant (median milliseconds, the key blocks a
(batch, head) visits, the share of the form's own least time) and writes
them to ``chiprun_out/swa_bakeoff.json``. Refuses to run without a TPU.
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

H, HK_GLOBAL, HK_WINDOW, DQ, DV, WINDOW = 64, 4, 8, 192, 128, 128


def timed(fn, *args, reps: int = 5) -> float:
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t)
    return 1e3 * float(np.median(out))


def long_band(emit, peak_flops: float, peak_bw: float) -> None:
    """Trinity-Large's geometry (module docstring)."""
    from benchmark import flops_swa
    from llm_in_practise_tpu.ops import swa_attention as swa

    h, hk, d, window, lq = 48, 8, 128, 4096, 2048
    bf = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    rnd = lambda i, shape: jax.random.normal(  # noqa: E731
        keys[i], shape).astype(bf)
    scale = d ** -0.5

    def least_ms(pairs, rows):
        fl, by = flops_swa.attention_cost(pairs, rows, 1, h, hk, d, d)
        return 1e3 * max(fl / peak_flops, by / peak_bw)

    flash = jax.jit(swa.flash_partial, static_argnames=(
        "scale", "window", "block_q", "block_k", "fold", "name"))
    q = rnd(0, (1, h, lq, d))
    zero = jnp.zeros((1,), jnp.int32)
    for n_keys in (4096, 16384, 32768):
        k, v = rnd(1, (1, hk, n_keys, d)), rnd(2, (1, hk, n_keys, d))
        start = jnp.asarray([n_keys - lq], jnp.int32)
        least = least_ms(flops_swa.causal_pairs(n_keys - lq, lq), n_keys)
        for bq, bk in ((512, 512), (512, 1024), (1024, 512), (1024, 1024),
                       (2048, 512), (1024, 2048)):
            try:
                ms = timed(lambda *a, _kw=dict(  # noqa: E731
                    scale=scale, block_q=bq, block_k=bk): flash(*a, **_kw),
                    q, k, v, start, zero)
                emit(what="global_chunk", keys=n_keys, block_q=bq,
                     block_k=bk, ms=ms, least_ms=least,
                     roofline_pct=100 * least / ms)
            except Exception as e:      # a tile the compiler refuses
                emit(what="global_chunk", keys=n_keys, block_q=bq,
                     block_k=bk, error=str(e)[:300])

    # a chunk at 12,288: the ring holds 8,192 .. 12,287, 6,144 keys in all
    n_keys, at = window + lq, 12288
    k, v = rnd(3, (1, hk, n_keys, d)), rnd(4, (1, hk, n_keys, d))
    start, k0 = (jnp.asarray([p], jnp.int32) for p in (at, at - window))
    least = least_ms(flops_swa.band_pairs(at, lq, window),
                     flops_swa.band_keys(at, lq, window))
    for fold, tiles in (
            (True, ((128, 512), (128, 1024), (256, 256), (256, 512),
                    (256, 1024), (512, 512))),
            (False, ((512, 512), (512, 1024), (1024, 512), (1024, 1024)))):
        for bq, bk in tiles:
            try:
                ms = timed(lambda *a, _kw=dict(  # noqa: E731
                    scale=scale, window=window, block_q=bq, block_k=bk,
                    fold=fold, name=swa.WINDOW_RING_KERNEL): flash(
                        *a, **_kw), q, k, v, start, k0)
                emit(what="ring_chunk", fold=fold, block_q=bq, block_k=bk,
                     ms=ms, band_least_ms=least,
                     roofline_pct=100 * least / ms,
                     key_blocks=swa.key_blocks_visited(
                         at, at - window, lq, n_keys, window=window,
                         block_q=bq, block_k=bk))
            except Exception as e:
                emit(what="ring_chunk", fold=fold, block_q=bq, block_k=bk,
                     error=str(e)[:300])
    whole = jax.jit(lambda q, k, v, rk, rv, st: swa.prefill_attention(
        q, k, v, st, scale=scale, window=window, cached=(rk, rv)))
    order = jax.jit(lambda rk, k, st: swa.ring_stretch(rk, k, st)[0])
    own_k, own_v = rnd(5, (1, lq, hk, d)), rnd(6, (1, lq, hk, d))
    ring_k, ring_v = rnd(1, (1, window, hk, d)), rnd(2, (1, window, hk, d))
    emit(what="window_layer_chunk", ms=timed(
        whole, q.transpose(0, 2, 1, 3), own_k, own_v, ring_k, ring_v,
        start), ring_in_order_ms=timed(order, ring_k, own_k, start),
        band_least_ms=least, tiles=list(swa.window_blocks(window)))

    decode = jax.jit(swa.decode_attention, static_argnames=("scale",))
    ring = jax.jit(swa.ring_decode_attention,
                   static_argnames=("scale", "window"))

    @jax.jit
    def by_heads(q1, k, v, index):
        """The same query over a view WITH a head axis (B, W, Hk, 128):
        no widened query, the view re-laid out if the chip wants it."""
        b = q1.shape[0]
        qg = q1[:, 0].reshape(b, hk, h // hk, d)
        s = jnp.einsum("bgrd,bkgd->bgrk", qg, k,
                       preferred_element_type=jnp.float32) * scale
        live = jnp.arange(k.shape[1])[None, :] <= index[:, None]
        p = jax.nn.softmax(jnp.where(live[:, None, None], s, -1e30),
                           axis=-1).astype(v.dtype)
        return jnp.einsum("bgrk,bkgd->bgrd", p, v).reshape(b, 1, h, d)

    q1 = rnd(7, (16, 1, h, d))
    for width in (8192, 32768):
        k, v = rnd(1, (16, width, hk * d)), rnd(2, (16, width, hk * d))
        index = jnp.full((16,), width * 3 // 4, jnp.int32)
        rows = 16 * (width * 3 // 4 + 1)
        least = least_ms(rows, rows)
        for form, fn, args in (
                ("flat", lambda *a: decode(*a, scale=scale), (k, v)),
                ("heads", by_heads, (k.reshape(16, width, hk, d),
                                     v.reshape(16, width, hk, d)))):
            ms = timed(fn, q1, *args, index)
            emit(what="global_decode", form=form, rows=16, view=width,
                 ms=ms, least_ms=least, roofline_pct=100 * least / ms,
                 view_read_ms=1e3 * (k.size + v.size) * 2 / peak_bw)
    k, v = rnd(3, (16, window, hk, d)), rnd(4, (16, window, hk, d))
    ms = timed(lambda *a: ring(*a, scale=scale, window=window), q1, k, v,
               jnp.full((16,), 20000, jnp.int32))
    emit(what="window_decode", rows=16, ring_rows=window, ms=ms,
         ring_read_ms=1e3 * (k.size + v.size) * 2 / peak_bw,
         roofline_pct=100 * least_ms(16 * window, 16 * window) / ms)


def main() -> int:
    from benchmark import device, flops_swa
    from llm_in_practise_tpu.core.mesh import require_tpu
    from llm_in_practise_tpu.ops import swa_attention as swa

    require_tpu()
    peak_flops, peak_bw = device.peaks(jax.devices()[0].device_kind)
    lines = []

    def emit(**kw):
        lines.append(kw)
        print(json.dumps(kw), flush=True)

    def write(name: str) -> int:
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out", name), "w",
                  encoding="utf-8") as f:
            json.dump(lines, f, indent=1)
        return 0

    if sys.argv[1:] == ["--geometry", "trinity"]:
        long_band(emit, peak_flops, peak_bw)
        return write("swa_bakeoff_trinity.json")
    bf = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    rnd = lambda i, shape: jax.random.normal(  # noqa: E731
        keys[i], shape).astype(bf)
    scale = DQ ** -0.5

    def least_ms(pairs, rows, hk):
        fl, by = flops_swa.attention_cost(pairs, rows, 1, H, hk, DQ, DV)
        return 1e3 * max(fl / peak_flops, by / peak_bw)

    flash = jax.jit(swa.flash_partial, static_argnames=(
        "scale", "window", "block_q", "block_k", "fold"))
    lq = 2048
    q = rnd(0, (1, H, lq, DQ))
    for n_keys in (4096, 16384, 32768):
        k, v = rnd(1, (1, HK_GLOBAL, n_keys, DQ)), rnd(
            2, (1, HK_GLOBAL, n_keys, DV))
        start = jnp.asarray([n_keys - lq], jnp.int32)
        least = least_ms(flops_swa.causal_pairs(n_keys - lq, lq), n_keys,
                         HK_GLOBAL)
        for bq, bk in ((512, 512), (1024, 512), (512, 1024), (1024, 1024),
                       (2048, 512), (1024, 2048), (2048, 1024)):
            try:
                ms = timed(lambda *a, _kw=dict(  # noqa: E731
                    scale=scale, block_q=bq, block_k=bk): flash(*a, **_kw),
                    q, k, v, start, jnp.zeros((1,), jnp.int32))
                emit(what="global_chunk", keys=n_keys, block_q=bq,
                     block_k=bk, ms=ms, least_ms=least,
                     roofline_pct=100 * least / ms,
                     key_blocks=swa.key_blocks_visited(
                         n_keys - lq, 0, lq, n_keys, window=None,
                         block_q=bq, block_k=bk))
            except Exception as e:      # a tile the compiler refuses
                emit(what="global_chunk", keys=n_keys, block_q=bq,
                     block_k=bk, error=str(e)[:300])

    k, v = rnd(3, (1, HK_WINDOW, lq, DQ)), rnd(4, (1, HK_WINDOW, lq, DV))
    start = jnp.asarray([12288], jnp.int32)
    least = least_ms(flops_swa.band_pairs(12288, lq, WINDOW),
                     flops_swa.band_keys(12288, lq, WINDOW), HK_WINDOW)
    for window, fold, tiles in (
            (WINDOW, True, ((128, 128), (128, 256), (256, 128), (256, 256),
                            (512, 256), (512, 512))),
            (WINDOW, False, ((256, 256), (512, 256), (512, 512),
                             (1024, 512))),
            (None, False, ((256, 256), (512, 512), (1024, 512)))):
        for bq, bk in tiles:
            try:
                ms = timed(lambda *a, _kw=dict(  # noqa: E731
                    scale=scale, window=window, block_q=bq, block_k=bk,
                    fold=fold): flash(*a, **_kw), q, k, v, start, start)
                emit(what="window_chunk", band=window is not None,
                     fold=fold, block_q=bq, block_k=bk, ms=ms,
                     band_least_ms=least, roofline_pct=100 * least / ms,
                     key_blocks=swa.key_blocks_visited(
                         12288, 12288, lq, lq, window=window, block_q=bq,
                         block_k=bk))
            except Exception as e:
                emit(what="window_chunk", band=window is not None,
                     fold=fold, block_q=bq, block_k=bk,
                     error=str(e)[:300])
    whole = jax.jit(lambda q, k, v, rk, rv, st, sink: swa.prefill_attention(
        q, k, v, st, scale=scale, window=WINDOW, sink=sink,
        cached=(rk, rv)))
    emit(what="window_layer_chunk", ms=timed(
        whole, q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), rnd(5, (1, WINDOW, HK_WINDOW, DQ)),
        rnd(6, (1, WINDOW, HK_WINDOW, DV)), start,
        jnp.zeros((H,), jnp.float32)), band_least_ms=least,
        tiles=list(swa.WINDOW_BLOCKS))

    decode = jax.jit(swa.decode_attention, static_argnames=("scale",))
    ring = jax.jit(swa.ring_decode_attention,
                   static_argnames=("scale", "window"))
    q1 = rnd(7, (16, 1, H, DQ))
    for width in (8192, 32768):
        k, v = rnd(1, (16, width, HK_GLOBAL * DQ)), rnd(
            2, (16, width, HK_GLOBAL * DV))
        index = jnp.full((16,), width * 3 // 4, jnp.int32)
        ms = timed(lambda *a: decode(*a, scale=scale), q1, k, v, index)
        rows = 16 * (width * 3 // 4 + 1)
        least = least_ms(rows, rows, HK_GLOBAL)
        emit(what="global_decode", rows=16, view=width, ms=ms,
             least_ms=least, roofline_pct=100 * least / ms,
             view_read_ms=1e3 * (k.size + v.size) * 2 / peak_bw)
    k, v = rnd(3, (16, WINDOW, HK_WINDOW, DQ)), rnd(
        4, (16, WINDOW, HK_WINDOW, DV))
    emit(what="window_decode", rows=16, ms=timed(
        lambda *a: ring(*a, scale=scale, window=WINDOW), q1, k, v,
        jnp.full((16,), 20000, jnp.int32)),
        ring_read_ms=1e3 * (k.size + v.size) * 2 / peak_bw)

    return write("swa_bakeoff.json")


if __name__ == "__main__":
    sys.exit(main())
