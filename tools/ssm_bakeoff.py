#!/usr/bin/env python3
"""The chunk scan's bake-off on the chip (PR 47), at Phi-4-mini-flash's
widths (5,120 channels x 16 states, a 2,048-token chunk, a carried state):

    chiprun -- python tools/ssm_bakeoff.py

- ``ops/selective_scan.py::chunk_scan`` over its tiles (positions a grid
  step walks x channels a block holds x positions unrolled a loop trip),
  each checked against the sequential recurrence
  (``chunk_scan_reference``) on the same inputs;
- the recurrence itself as XLA runs it (a ``lax.scan`` of 2,048 steps): what
  the kernel replaces;
- the decode plane's one-position update over 16 rows (``state_update``).

Times are DEVICE times from a profiler trace (``benchmark/trace.py``), the
median program over ``REPS`` executions under one capture. Prints one JSON
line a variant (milliseconds, the share of the least time the bytes need,
the worst difference from the reference) and writes them to
``chiprun_out/ssm_bakeoff.json``. ``--rehearse`` drives the wiring on the
CPU at a toy size (interpret mode; no times). Refuses to run without a TPU
otherwise.
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

LENGTH, CHANNELS, STATES, ROWS, REPS = 2048, 5120, 16, 16, 5
TILES = ((128, 1024, 1), (256, 1024, 1), (256, 1024, 2), (256, 1024, 4),
         (256, 1024, 8), (128, 1024, 4), (64, 1024, 4),
         (512, 1024, 2), (256, 512, 2), (256, 2048, 2), (1024, 1024, 2))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmark import device, flops_ssm, trace
    from llm_in_practise_tpu.ops import selective_scan as ssm

    if args.rehearse:
        length, chan, tiles = 64, 256, ((16, 128, 2), (32, 256, 1))
        peak_bw = None
    else:
        from llm_in_practise_tpu.core.mesh import require_tpu

        require_tpu()
        length, chan, tiles = LENGTH, CHANNELS, TILES
        _, peak_bw = device.peaks(jax.devices()[0].device_kind)
    g = {"d_inner": chan, "d_state": STATES}
    lines = []

    def emit(**kw):
        lines.append(kw)
        print(json.dumps(kw), flush=True)

    def inputs(rows, n):
        ks = jax.random.split(jax.random.PRNGKey(0), 6)
        x = jax.random.normal(ks[0], (rows, n, chan))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (rows, n, chan)) - 4)
        b, c = (jax.random.normal(k, (rows, n, STATES)) for k in ks[2:4])
        a = -jnp.broadcast_to(jnp.arange(1, STATES + 1, dtype=jnp.float32)[
            :, None], (STATES, chan))
        return (x, dt, b, c, a, jnp.ones((chan,)),
                jax.random.normal(ks[4], (rows, STATES, chan)))

    def device_ms(fn, name, *xs):
        fn.__name__ = name
        run = jax.jit(fn)
        out = jax.block_until_ready(run(*xs))
        if args.rehearse:
            return None, out
        with tempfile.TemporaryDirectory() as log_dir:
            with trace.capture(log_dir):
                for _ in range(REPS):
                    jax.block_until_ready(run(*xs))
            seen = trace.reduce(trace.load(trace.newest_xplane(log_dir)))
        return 1e3 * float(np.median(seen["programs"][f"jit_{name}"])), out

    xs = inputs(1, length)
    want = jax.block_until_ready(jax.jit(ssm.chunk_scan_reference)(*xs))
    _, nbytes = flops_ssm.scan_cost(length, 1, g)
    least = None if peak_bw is None else 1e3 * nbytes / peak_bw

    def row(what, ms, out, **tags):
        err = max(float(jnp.max(jnp.abs(o - w))) for o, w in zip(out, want))
        emit(what=what, ms=ms, least_ms=least,
             roofline_pct=None if ms is None else 100 * least / ms,
             worst_abs_diff=err, **tags)

    for bt, bc, unroll in tiles:
        try:
            ms, out = device_ms(
                lambda *a, _kw=dict(block_t=bt, block_c=bc, unroll=unroll,
                                    interpret=args.rehearse):
                ssm.chunk_scan(*a, **_kw), f"scan_{len(lines)}", *xs)
            row("chunk_scan", ms, out, block_t=bt, block_c=bc,
                unroll=unroll)
        except Exception as e:      # a tile the compiler refuses
            emit(what="chunk_scan", block_t=bt, block_c=bc, unroll=unroll,
                 error=str(e)[:300])
    ms, out = device_ms(lambda *a: ssm.chunk_scan_reference(*a),
                        "lax_scan", *xs)
    row("lax_scan", ms, out)
    # the decode plane: one position, 16 rows
    one = inputs(ROWS, 1)
    ms, _ = device_ms(
        lambda x, dt, b, c, a, d, s: ssm.state_update(
            x[:, 0], dt[:, 0], b[:, 0], c[:, 0], a, d, s), "update", *one)
    emit(what="state_update", rows=ROWS, ms=ms)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "ssm_bakeoff.json"), "w",
              encoding="utf-8") as f:
        json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
