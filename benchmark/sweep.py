#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: ONE process, one warm
engine, ascending rates, a window each.

    python benchmark/sweep.py --workload qwen3-8b.chat-steady \\
        --rates 2,3,4,5,6,7,8 --seconds 30 --seed 1

The knee is the highest swept rate at which at least nine tenths of the
requests DUE met both of the workload's limits (TTFT and the mean gap
between tokens) and no more than the engine's slots were in flight when
the window ended. The workload file then holds 0.8 of it as a number: the
benchmark never searches for a rate. One JSON line per rate, then one
with the knee.

``--seeds a,b,c`` runs every rate once per seed. In an open loop the seed
is the PHASE at which the cycle of requests begins, so one rate with many
seeds shows how far a tail moves with the phase alone (PERF.md §6):

    python benchmark/sweep.py --workload qwen3-8b.chat-steady \
        --rates 0.84 --seconds 51 --seeds 11,12,13
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import device, serving, spec, traffic  # noqa: E402

SUSTAINED_SHARE = 0.9


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="ascending, comma-separated requests/s")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", help="comma-separated; every rate runs once "
                    "per seed (default: --seed alone)")
    opts = ap.parse_args()
    seeds = ([int(s) for s in opts.seeds.split(",")] if opts.seeds
             else [opts.seed])

    bench = spec.benchmark()
    cell = spec.cell(bench, opts.workload)
    workload = spec.workload_of(cell)
    devices = device.require_chips(cell["chips"])
    from llm_in_practise_tpu.core.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    sv = serving.build(spec.config_of(bench, cell), seeds[0])
    held = {}                     # rate -> sustained at every seed
    try:
        serving.warm(sv, workload, seeds[0])
        for rate, seed in [(float(r), s) for r in opts.rates.split(",")
                           for s in seeds]:
            w = dict(workload, arrivals=dict(workload["arrivals"],
                                             rate_per_s=rate))
            work = serving.write_prompts(
                sv, traffic.plan(w, opts.seconds, seed), seed)
            window = serving.run_window(sv, w, work, opts.seconds)
            metrics, notes = serving.end_to_end(window, w)
            sustained = (notes["met_both_limits_share"] >= SUSTAINED_SHARE
                         and notes["in_flight_at_window_end"]
                         <= sv.engine.max_slots)
            held[rate] = held.get(rate, True) and sustained
            print(json.dumps({
                "rate_per_s": rate, "seed": seed, "sustained": sustained,
                "met_both_limits_share": notes["met_both_limits_share"],
                "in_flight_at_window_end": notes["in_flight_at_window_end"],
                "attempted": notes["attempted"], "failed": notes["failed"],
                "ttft_ms": notes["ttft_ms"], "tpot_ms": notes["tpot_ms"],
                "tokens_per_s": metrics["serve_tokens_per_s"],
                "sender_lateness_ms": notes["sender_lateness_ms"]}),
                flush=True)
            while (sv.engine.pending.qsize()
                   or any(r is not None for r in sv.engine.slot_req)):
                time.sleep(0.2)       # drain before the next rate
    finally:
        sv.close()
    knee = max((r for r, ok in held.items() if ok), default=None)
    print(json.dumps({"knee_rate_per_s": knee,
                      "four_fifths": None if knee is None else 0.8 * knee,
                      "device": device.describe(devices)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
