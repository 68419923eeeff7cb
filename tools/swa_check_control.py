#!/usr/bin/env python3
"""The control behind the limits of ``benchmark/reference/mimo_v2.py``
(PR 39) and ``benchmark/reference/afmoe.py`` (PR 44): a window / global
cell's own ``check`` (the probes, the reference, the limits:
``benchmark/runners/serve_hybrid_cell.py`` for
``mimo-v2.5.agent-context``, ``serve_window_ring_cell.py`` for ``--cell
trinity-large.mixed-lengths``) on a server built as the cell builds it,
but for ONE serving argument: the K/V pages and the window rings are
stored in the nearest precision below the configuration's
(``--kv-cache-dtype fp8``, e4m3). Weights, activations, router and logits
are as the configuration has them.

    chiprun -- python tools/swa_check_control.py --seeds 3913000001 3913000002
    chiprun -- python tools/swa_check_control.py --cell trinity-large.mixed-lengths --seeds 4413000001

Prints one JSON line a seed: ``check``'s whole output (each reading beside
its limit) and ``limits_failed``, the limits that run broke. The control
has done its work when every run came out NOT ok: exit code 0 then, 1 if
a run at the lower precision passed (a limit sits too high), 2 if a run
never reached its comparison. ``--kv-cache-dtype bfloat16`` turns the same
code into a sound run (exit 1 unless ``--expect ok``). ``--rehearse``: the
toy size on the CPU (the wiring; a toy's readings set no limit).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# cell -> (its runner's module, what its ``check`` takes as probes: of the
# runner, the workload, whether this is a rehearsal)
CELLS = {
    "mimo-v2.5.agent-context": (
        "serve_hybrid_cell", lambda cell, workload, rehearse: workload),
    "trinity-large.mixed-lengths": (
        "serve_window_ring_cell", lambda cell, workload, rehearse:
        cell.REHEARSAL_PROBES if rehearse else cell.PROBES),
}


def limits_failed(out: dict) -> list[str]:
    """Which of ``check``'s limits a run broke, by the tolerances it
    printed beside its readings."""
    if "worst" not in out:
        return []
    tol, worst, routing = out["tolerances"], out["worst"], out["routing"]
    failed = [name for name, key in (("rms", "rms_over_std"),
                                     ("max", "max_over_std"),
                                     ("token_margin", "token_margin_over_std"))
              if worst[key] > tol[name]]
    if routing["outside_margin"]:
        failed.append("route_margin")
    if routing["flip_share"] > tol["route_flip_share"]:
        failed.append("route_flip_share")
    return failed


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", choices=sorted(CELLS),
                    default="mimo-v2.5.agent-context")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--kv-cache-dtype", default="fp8")
    ap.add_argument("--expect", choices=("not_ok", "ok"), default="not_ok")
    ap.add_argument("--rehearse", action="store_true")
    opts = ap.parse_args()

    import importlib

    from benchmark import device, spec

    runner, probes_of = CELLS[opts.cell]
    cell = importlib.import_module(f"benchmark.runners.{runner}")
    bench = spec.benchmark()
    entry = spec.cell(bench, opts.cell)
    config, workload = spec.config_of(bench, entry), spec.workload_of(entry)
    if opts.rehearse:
        from benchmark import rehearsal

        config, workload = rehearsal.shrink(config, workload)
        workload = dict(workload, **cell.REHEARSAL_WORKLOAD)
    else:
        device.require_chips(1)
    args = list(config["layout"]["serve_args"])
    args[args.index("--kv-cache-dtype") + 1] = opts.kv_cache_dtype
    config = dict(config, layout=dict(config["layout"], serve_args=args))

    as_expected, incomplete = True, False
    for seed in opts.seeds:
        sv = cell.build(config, seed, not opts.rehearse)
        try:
            out = cell.check(sv, probes_of(cell, workload, opts.rehearse),
                             seed)
        finally:
            sv.close()
        del sv
        gc.collect()        # the next seed's weights need the room
        print(json.dumps({"cell": opts.cell,
                          "kv_cache_dtype": opts.kv_cache_dtype,
                          "seed": seed, "limits_failed": limits_failed(out),
                          **out}), flush=True)
        incomplete |= "worst" not in out
        as_expected &= out["ok"] == (opts.expect == "ok")
    return 2 if incomplete else 0 if as_expected else 1


if __name__ == "__main__":
    raise SystemExit(main())
