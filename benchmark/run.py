#!/usr/bin/env python3
"""Run ONE cell of BENCHMARK.json once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip(s) and starts no other. Without the
accelerator the cell asks for it exits non-zero and prints no result. The
LAST line of standard output is the result object; everything else worth
keeping is on earlier lines and in
``chiprun_out/benchmark/<cell>/seed<n>-trace<t>.json``.

``--rehearse`` is for the CPU sandbox: it runs the same code end to end at
a tiny size and prints NO result line, because a CPU run measures nothing
that is reported under a device metric's name.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import device, spec, stats, trace  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts XLA backend compilations (cache loads included: a program
    built inside the window either way) through ``jax.monitoring``, the
    benchmark's own listener rather than a counter of the program."""

    def __init__(self):
        import jax.monitoring

        self.at: list[float] = []
        self.in_window = None
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.at.append(time.monotonic())

    def window_open(self) -> None:
        print("[bench] set-up done; window opens", file=sys.stderr,
              flush=True)

    def window_close(self, t0: float, t1: float) -> None:
        self.in_window = sum(t0 <= t <= t1 for t in self.at)


def note(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    opts = ap.parse_args()

    bench = spec.benchmark()
    cell = spec.cell(bench, opts.workload)
    workload = spec.workload_of(cell)
    config = spec.config_of(bench, cell)
    if opts.rehearse:
        from benchmark import rehearsal

        config, workload = rehearsal.shrink(config, workload)

    import jax

    from llm_in_practise_tpu.core.compile_cache import (
        enable_compilation_cache,
    )

    if opts.rehearse:
        devices = jax.devices()[:cell["chips"]]
    else:
        devices = device.require_chips(cell["chips"])
    cache_dir = enable_compilation_cache()
    note(cell=cell["name"], seed=opts.seed, seconds=opts.seconds,
         trace=opts.trace, platform=devices[0].platform,
         device_kind=devices[0].device_kind, device_count=len(devices),
         jax=jax.__version__, compile_cache_dir=cache_dir,
         rehearsal=opts.rehearse)

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    compiles = CompileCounter()
    ctx = {
        "bench": bench, "cell": cell, "config": config,
        "workload": workload, "seed": opts.seed, "seconds": opts.seconds,
        "trace": bool(opts.trace), "trace_dir": trace_dir,
        "t_start": T_START, "devices": devices,
        "on_chip": not opts.rehearse, "compiles": compiles,
        "describe_devices": lambda: device.describe(devices),
        "peaks": lambda: device.peaks(devices[0].device_kind),
    }
    try:
        result = spec.runner(workload["runner"]).run(ctx)
        dev = result["device"]
        notes = result["notes"]
        notes["compilations_in_window"] = compiles.in_window
        correct = bool(result["correct"] and compiles.in_window == 0)
        obs = result["obs"]
        obs["trace"] = None
        obs["e2e"] = result["e2e"]
        breakdown = None
        if opts.trace:
            t_reduce = time.monotonic()
            reduced = trace.reduce(trace.load(
                trace.newest_xplane(trace_dir), rehearsal=opts.rehearse))
            notes["trace_reduce_s"] = time.monotonic() - t_reduce
            obs["trace"] = reduced
            dev["busy_s"] = reduced["busy_s"]
            dev["window_s"] = reduced["window_s"]
            breakdown = {
                "device_ops": trace.top(reduced["op_seconds"]),
                "idle_gaps": trace.charge_gaps(reduced, result["marks"],
                                               result["steps"]),
            }
            notes["trace"] = {
                "lines": reduced["lines"],
                "programs": {
                    k: {"runs": len(v), "median_ms": 1e3 * stats.median(v),
                        "total_s": sum(v)}
                    for k, v in reduced["programs"].items()},
                "idle_share": 1.0 - reduced["busy_s"] / reduced["window_s"],
            }
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)

    group = "per_layer" if opts.trace else "end_to_end"
    metrics = {}
    for m in spec.metrics_for(bench, cell["name"], group):
        if group == "end_to_end":
            value = result["e2e"].get(m["name"])
        else:
            mf = spec.metric_file(m["name"])
            value = spec.reader(mf["reader"]).read(obs, mf["params"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            # the driver refuses a line that lacks a metric the cell
            # lists: say which, where the next reader will look first
            notes.setdefault("metrics_missing", []).append(m["name"])
    notes["process_s"] = time.monotonic() - T_START
    note(notes=notes, end_to_end=result["e2e"])
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmark", cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"seed{opts.seed}-trace{opts.trace}"
                           f"{'-rehearsal' if opts.rehearse else ''}.json"),
              "w", encoding="utf-8") as f:
        # the program's step records and the client's view of every
        # request, in traced and untraced runs alike: a run that reads
        # far off can then be explained from its own file
        json.dump({"notes": notes, "end_to_end": result["e2e"],
                   "metrics": metrics, "device": dev,
                   "breakdown": breakdown,
                   "requests": result.get("requests", []),
                   "steps": result["steps"]}, f, indent=1, default=str)
    if opts.rehearse:
        print("REHEARSAL on", devices[0].platform, "- control flow only; "
              "every time, rate and share above is NOT MEASURED; correct =",
              correct)
        return 0 if correct else 1
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": dev}
    if breakdown is not None:
        line["breakdown"] = breakdown
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
