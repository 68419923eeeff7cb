"""One serving run: build, warm up, window (with an optional traced
slice), the device's memory peak, then the reference comparison. The
workload file says which loop: ``arrivals`` (open: requests DUE on a
schedule at a fixed rate, whatever the system does) or ``clients``
(closed: each caller sends its next request when its last completed)."""

from __future__ import annotations

import threading
import time

from benchmark import serving, trace, traffic


def run(ctx: dict) -> dict:
    workload, seed, seconds = ctx["workload"], ctx["seed"], ctx["seconds"]
    sv = serving.build(ctx["config"], seed)
    try:
        eng = sv.engine
        warmed = serving.warm(sv, workload, seed)
        work = serving.write_prompts(
            sv, traffic.plan(workload, seconds, seed), seed)
        sampler = serving.Sampler(eng) if ctx["trace"] else None
        marks, tracer = {}, None
        if ctx["trace"]:
            slice_s = min(float(workload["trace_slice_s"]), seconds)

            def traced_slice():
                time.sleep(0.25 + (seconds - slice_s) / 2)
                with trace.capture(ctx["trace_dir"]) as m:
                    time.sleep(slice_s)
                marks.update(m)

            tracer = threading.Thread(target=traced_slice, daemon=True)
        step0 = eng.steptrace.snapshot()
        preempt0 = eng.preemptions
        ctx["compiles"].window_open()
        setup_s = time.monotonic() - ctx["t_start"]
        if tracer is not None:
            tracer.start()
        window = serving.run_window(sv, workload, work, seconds, sampler)
        ctx["compiles"].window_close(window.t0, window.t0 + seconds)
        if tracer is not None:
            tracer.join(timeout=120)
        grace_s = time.monotonic() - (window.t0 + seconds)
        step1 = eng.steptrace.snapshot()
        device = ctx["describe_devices"]()
        e2e, notes = serving.end_to_end(window, workload)
        notes["warm_up"] = warmed
        notes["grace_and_trace_stop_s"] = grace_s
        notes["preemptions"] = eng.preemptions - preempt0
        notes["engine_compile_events_total"] = eng.compile_meter.compile_events
        wall = step1["step_wall_seconds_total"] - step0["step_wall_seconds_total"]
        dev = step1["device_seconds_total"] - step0["device_seconds_total"]
        counters = {"step_wall_s": wall, "step_device_s": dev,
                    "step_host_s": wall - dev}
        obs = {"requests": [], "counters": counters}
        if sampler is not None:
            s = window.samples
            obs["requests"] = s["finished_cp"]
            counters["pool_pages_peak"] = max(s["pool_pages_used"])
            counters["pool_pages"] = s["pool_pages"]
        steps = eng.steptrace.records(limit=eng.steptrace.capacity)
        built = eng.compile_meter.compile_events
        t_check = time.monotonic()
        checked = serving.check(sv, workload, seed)
        notes["check"] = checked
        notes["check_s"] = time.monotonic() - t_check
        # engine programs the probes met that neither the warm-up nor the
        # window had built: time after the window, and a hole in the plan
        notes["check_engine_compiles"] = (eng.compile_meter.compile_events
                                          - built)
    finally:
        sv.close()
    e2e["setup_s"] = setup_s
    requests = [{"index": o.index, "prompt_tokens": o.prompt_tokens,
                 "tokens": o.tokens, "due_s": o.t_due - window.t0,
                 "ttft_s": o.ttft_s(), "tpot_s": o.tpot_s(),
                 "done_s": None if o.t_done is None else o.t_done - window.t0,
                 "finish_reason": o.finish_reason, "error": o.error}
                for o in window.outcomes]
    return {"e2e": e2e, "notes": notes, "correct": checked["ok"],
            "attempted": notes["attempted"], "failed": notes["failed"],
            "device": device, "obs": obs, "marks": marks, "steps": steps,
            "requests": requests}
