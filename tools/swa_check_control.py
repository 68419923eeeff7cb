#!/usr/bin/env python3
"""The control behind the limits of ``benchmark/reference/mimo_v2.py``
(PR 39), ``benchmark/reference/afmoe.py`` (PR 44),
``benchmark/reference/phi4flash.py`` (PR 47) and
``benchmark/reference/lfm2_moe.py`` (PR 52): a cell's own
``check`` (the probes, the reference, the limits:
``benchmark/runners/serve_hybrid_cell.py`` for
``mimo-v2.5.agent-context``, ``serve_window_ring_cell.py`` for ``--cell
trinity-large.mixed-lengths``, ``serve_recurrent_cell.py`` for ``--cell
phi-4-mini-flash.grounded-reasoning``, ``serve_conv_moe_cell.py`` for
``--cell lfm2-24b-a2b.chat-concurrent``) on a server built as the cell builds
it, but for ONE store kept in the nearest precision below the
configuration's: the K/V pages and the window rings (``--kv-cache-dtype
fp8``, e4m3), or, for a cell with recurrent layers, their state
(``--kv-cache-dtype bfloat16 --ssm-state-dtype bfloat16``: the state is no
part of the K/V cache and has a dtype of its own). Weights, activations,
router and logits are as the configuration has them.

    chiprun -- python tools/swa_check_control.py --seeds 3913000001 3913000002
    chiprun -- python tools/swa_check_control.py --cell trinity-large.mixed-lengths --seeds 4413000001
    chiprun -- python tools/swa_check_control.py --cell phi-4-mini-flash.grounded-reasoning --ssm-state-dtype bfloat16 --kv-cache-dtype bfloat16 --seeds 4713000001
    chiprun -- python tools/swa_check_control.py --cell phi-4-mini-flash.grounded-reasoning --kv-cache-dtype bfloat16 --expect ok --seeds 4713100001 --probe-seeds 8 --faults
    chiprun -- python tools/swa_check_control.py --cell lfm2-24b-a2b.chat-concurrent --kv-cache-dtype bfloat16 --expect ok --seeds 5213100001 --probe-seeds 3 --faults

``--probe-seeds n`` (the cells with ``probe`` and ``judge``): n sets of
probes at seeds of their own on each server (one build, n comparisons).
For ``lfm2-24b-a2b.chat-concurrent`` the server is WARMED as the cell warms
it before the first probes (thirty-two requests at once on programs not yet
built did not come back in twenty minutes on the chip: PR 52), its lower
precision is the REFERENCE's (``faults()`` holds the K/V rows in e4m3 beside
the three planted faults: no second server to build and compile), every
set of probes is judged against every fault, and two readings that are held
to nothing follow each set: a bfloat16 router, and the reference left to
its own routed sets at the judged positions too (what the routing's flips
cost there). ``--faults`` (a SOUND server): after a seed's first comparison
the same observation is judged against the reference with ONE form left out
at a time (``benchmark/reference/phi4flash.py``'s forms as data: the learned
lambda, the sub-norm, the GMU's memory, the memory of the token before,
the D skip, the window, a convolution tail dropped at a chunk's boundary)
and with the two probes' slots crossed: each must come out NOT ok, and its
line says by which limits (exit 1 if one passed).

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
import time

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
    "phi-4-mini-flash.grounded-reasoning": (
        "serve_recurrent_cell", lambda cell, workload, rehearse:
        cell.REHEARSAL_PROBES if rehearse else cell.PROBES),
    "lfm2-24b-a2b.chat-concurrent": (
        "serve_conv_moe_cell", lambda cell, workload, rehearse:
        cell.REHEARSAL_PROBES if rehearse else cell.PROBES),
}


def limits_failed(out: dict) -> list[str]:
    """Which of ``check``'s limits a run broke, by the tolerances it
    printed beside its readings."""
    if "limits_failed" in out:      # the recurrent cell names them itself
        return out["limits_failed"]
    if "worst" not in out:
        return []
    tol, worst = out["tolerances"], out["worst"]
    routing = out.get("routing")    # a dense model routes nothing
    failed = [name for name, key in (("rms", "rms_over_std"),
                                     ("max", "max_over_std"),
                                     ("token_margin", "token_margin_over_std"))
              if name in tol and worst[key] > tol[name]]
    if routing and routing["outside_margin"]:
        failed.append("route_margin")
    if routing and routing["flip_share"] > tol["route_flip_share"]:
        failed.append("route_flip_share")
    return failed


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", choices=sorted(CELLS),
                    default="mimo-v2.5.agent-context")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--kv-cache-dtype", default="fp8")
    ap.add_argument("--ssm-state-dtype", default=None,
                    help="the recurrent state's dtype (a cell whose model "
                         "has one; default: the configuration's float32)")
    ap.add_argument("--expect", choices=("not_ok", "ok"), default="not_ok")
    ap.add_argument("--probe-seeds", type=int, default=1)
    ap.add_argument("--faults", action="store_true")
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

    conv_moe = runner == "serve_conv_moe_cell"
    recurrent = runner == "serve_recurrent_cell" or conv_moe
    if (opts.faults or opts.probe_seeds > 1) and not recurrent:
        ap.error("--faults / --probe-seeds: a cell with probe and judge")
    probes = probes_of(cell, workload, opts.rehearse)
    extra = ((cell.REHEARSAL_FILLERS,) if recurrent and opts.rehearse
             else ())
    slack = cell.REHEARSAL_SLACK if recurrent and opts.rehearse else 1.0
    as_expected, incomplete = True, False

    def report(seed, out, **more):
        nonlocal as_expected, incomplete
        print(json.dumps({"cell": opts.cell,
                          "kv_cache_dtype": opts.kv_cache_dtype,
                          "ssm_state_dtype": opts.ssm_state_dtype,
                          "seed": seed, **more,
                          "limits_failed": limits_failed(out), **out}),
              flush=True)
        incomplete |= "worst" not in out
        as_expected &= out["ok"] == (opts.expect == "ok"
                                     and "fault" not in more)

    for seed in opts.seeds:
        lower = ({} if opts.ssm_state_dtype is None
                 else {"ssm_state_dtype": opts.ssm_state_dtype})
        sv = cell.build(config, seed, not opts.rehearse, **lower)
        try:
            if conv_moe:
                from benchmark import serving

                print(json.dumps({"warm_up": serving.warm(
                    sv, workload, seed)}), flush=True)
            for i in range(opts.probe_seeds):
                at = seed + 1000 * i
                if not recurrent:
                    report(at, cell.check(sv, probes, at))
                    continue
                t0 = time.monotonic()
                seen = cell.probe(sv, probes, at, *extra)
                t1 = time.monotonic()
                out = cell.judge(sv, seen, slack=slack)
                report(at, out, probe_s=t1 - t0,
                       judge_s=time.monotonic() - t1)
                if not opts.faults or "why" in seen or (i and not conv_moe):
                    continue
                for form, value in cell.faults(sv).items():
                    report(at, cell.judge(
                        sv, seen, dict(sv.geom, **{form: value}),
                        slack=slack), fault=f"{form}={value}")
                report(at, cell.judge(sv, seen, crossed=True, slack=slack),
                       fault="slots_crossed")
                if not conv_moe:
                    continue
                # held to nothing (module docstring)
                for reading, out in (
                        *((f"{form}={value}", cell.judge(
                            sv, seen, dict(sv.geom, **{form: value}),
                            slack=slack))
                          for form, value in cell.READINGS.items()),
                        ("routes_free", cell.judge(
                            sv, seen, slack=slack, forced=False))):
                    print(json.dumps({"cell": opts.cell, "seed": at,
                                      "reading": reading, **out}),
                          flush=True)
        finally:
            sv.close()
        del sv
        gc.collect()        # the next seed's weights need the room
    return 2 if incomplete else 0 if as_expected else 1


if __name__ == "__main__":
    raise SystemExit(main())
