"""Serving load harness: concurrency ladder with TTFT/TPOT/OutputTPS.

TPU-native counterpart of the reference's benchmark layer — the `vllm
bench serve` ShareGPT ladder with JSON aggregation
(``LLM_on_Kubernetes/Inference_Platfrom/README.md:1345-1520``, results
table ``:1504-1512``) and the Locust tokens/s harness
(``Deployment/Ray/scripts/locustfile-TPS.py``). Drives any
OpenAI-compatible endpoint (ours or vLLM's) over streaming SSE so TTFT
(first token) and TPOT (inter-token) are measured where they happen.

Prints one JSON line per concurrency level plus a summary table:
OutputTPS, p50/p99 TTFT, p50/p99 TPOT, success rate — the reference's
result schema. SLA check: p99 TTFT < 2s, p99 TPOT < 100ms
(``README.md:1517``).
"""

from __future__ import annotations

import argparse
import json
import random
import threading
import time
import urllib.request


PROMPTS = [
    "Explain how a systolic array multiplies matrices.",
    "What is ring attention and when is it useful?",
    "Summarize the difference between data and tensor parallelism.",
    "Who are you?",
    "Write a haiku about compilers.",
    "What does ZeRO stage 3 shard?",
]


def _quantile(xs, q):
    """Linear-interpolated quantile — a floor index would hide the worst
    observation and could flip the SLA gate."""
    if not xs:
        return 0.0
    xs = sorted(xs)
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def one_request(url, model, prompt, max_tokens, timeout):
    """Returns (ok, ttft_s, tpot_list, n_tokens, failure_reason)."""
    body = json.dumps({
        "model": model,
        "messages": [{"role": "user", "content": prompt}],
        "max_tokens": max_tokens,
        "stream": True,
    }).encode()
    req = urllib.request.Request(
        f"{url}/v1/chat/completions", data=body,
        headers={"Content-Type": "application/json"},
    )
    t0 = time.perf_counter()
    ttft = None
    stamps = []
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            while True:
                # SSE is newline-delimited; readline blocks exactly until
                # the next event without per-byte Python overhead
                line = r.readline()
                if not line:
                    break
                line = line.strip()
                if not line.startswith(b"data:"):
                    continue
                data = line[5:].strip()
                if data == b"[DONE]":
                    continue
                try:
                    delta = json.loads(data)["choices"][0].get(
                        "delta", {}).get("content")
                except (ValueError, KeyError, IndexError):
                    continue
                if delta:
                    now = time.perf_counter()
                    if ttft is None:
                        ttft = now - t0
                    stamps.append(now)
    except OSError as e:
        # record WHY — a lost request is a bug until shown otherwise; the
        # artifact must carry the reason, not just a success-rate dip
        return False, None, [], 0, f"{type(e).__name__}: {e}"
    if ttft is None:
        return False, None, [], 0, "stream_closed_without_tokens"
    # Per-request mean inter-token time, (last - first)/(n - 1) — the
    # `vllm bench serve` TPOT definition. Raw per-gap sampling breaks
    # under burst delivery (multi-step decode / speculative bursts emit
    # several SSE events back-to-back: most gaps read ~0 and one gap
    # reads a whole block, so per-gap percentiles are meaningless).
    tpot = ([(stamps[-1] - stamps[0]) / (len(stamps) - 1)]
            if len(stamps) > 1 else [])
    return True, ttft, tpot, len(stamps), None


def _aggregate(concurrency, n_requests, n_ok, failures, ttfts, tpots,
               total_tokens, wall):
    """Shared row schema for both ladders — one place to add a metric."""
    return {
        "concurrency": concurrency,
        "requests": n_requests,
        "success_rate": n_ok / max(n_requests, 1),
        "failures": failures,
        "output_tps": total_tokens / wall if wall else 0.0,
        "ttft_p50_ms": _quantile(ttfts, 0.5) * 1e3,
        "ttft_p99_ms": _quantile(ttfts, 0.99) * 1e3,
        "tpot_p50_ms": _quantile(tpots, 0.5) * 1e3,
        "tpot_p99_ms": _quantile(tpots, 0.99) * 1e3,
        "wall_s": wall,
    }


def run_level(url, model, concurrency, n_requests, max_tokens, timeout):
    results = []
    lock = threading.Lock()
    queue = list(range(n_requests))
    rng = random.Random(0)
    prompts = [rng.choice(PROMPTS) for _ in range(n_requests)]

    def worker():
        while True:
            with lock:
                if not queue:
                    return
                i = queue.pop()
            r = one_request(url, model, prompts[i], max_tokens, timeout)
            with lock:
                results.append(r)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker) for _ in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0

    oks = [r for r in results if r[0]]
    failures: dict[str, int] = {}
    for r in results:
        if not r[0]:
            failures[r[4]] = failures.get(r[4], 0) + 1
    return _aggregate(
        concurrency, n_requests, len(oks), failures,
        [r[1] for r in oks], [x for r in oks for x in r[2]],
        sum(r[3] for r in oks), wall)


def run_level_inprocess(engine, prompt_ids_list, concurrency, n_requests,
                        max_tokens, timeout=600.0):
    """Closed-loop ladder directly against ``InferenceEngine.submit`` — no
    HTTP, no SSE. TTFT/TPOT come from the engine's own per-request stamps
    (``Request.ttft_s`` / ``tpot_s``), so this row is
    **engine-attributable**: it isolates scheduler + device time from the
    HTTP ladder's transport. The engine's background thread must be
    running (``engine.start()``). Like the HTTP client, every failure
    carries a reason and a dead engine thread surfaces as per-request
    timeouts instead of a hang.
    """
    done = []          # (request | None, failure_reason | None)
    lock = threading.Lock()
    queue = list(range(n_requests))
    rng = random.Random(0)
    picks = [rng.randrange(len(prompt_ids_list)) for _ in range(n_requests)]

    def worker():
        while True:
            with lock:
                if not queue:
                    return
                i = queue.pop()
            row = _submit_and_drain(engine, prompt_ids_list[picks[i]],
                                    max_tokens, timeout)
            with lock:
                done.append(row)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker) for _ in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return {"mode": "inprocess",
            **_engine_rows_aggregate(done, concurrency, n_requests, wall)}


def _submit_and_drain(engine, ids, max_tokens, timeout, constraint=None):
    """Submit one engine request (as a traced root — without this the
    direct-engine path records no spans and the artifact's obs_snapshot
    trace summary would be structurally empty) and drain its stream
    with a bounded per-token wait. Returns ``(request, None)`` or
    ``(None, failure_reason)`` — the ONE drain/reason convention both
    the closed ladder and the trace replay book through."""
    import queue as queue_mod

    from llm_in_practise_tpu.obs.trace import new_context
    from llm_in_practise_tpu.serve import engine as engine_mod
    from llm_in_practise_tpu.serve.engine import SamplingParams

    try:
        req = engine.submit(ids,
                            SamplingParams(greedy=True,
                                           max_tokens=max_tokens,
                                           constraint=constraint),
                            trace=new_context())
        while True:  # drain the stream; bounded wait per token
            item = req.tokens.get(timeout=timeout)
            if item is engine_mod._FINISH:
                break
        return req, None
    except queue_mod.Empty:
        return None, f"token_timeout>{timeout:g}s"
    except Exception as e:  # noqa: BLE001 — a bench row must say why
        return None, f"{type(e).__name__}: {e}"


def _engine_rows_aggregate(done, concurrency, n_requests, wall):
    """Success/failure accounting over ``(request, reason)`` rows —
    shared by the closed ladder and the trace replay. Requests the
    engine SHED (admission control: finish_reason "queue_full", zero
    tokens) are failures for success-rate purposes — the SLA
    percentiles describe served requests only, with the shed fraction
    reported alongside so a config can't "pass" by serving almost
    nothing."""
    oks = [r for r, err in done
           if err is None and r.finish_time is not None
           and r.finish_reason != "queue_full"]
    failures: dict[str, int] = {}
    for r, err in done:
        reason = err or (
            "queue_full" if r.finish_reason == "queue_full"
            else ("no_finish_time" if r.finish_time is None else None))
        if reason:
            failures[reason] = failures.get(reason, 0) + 1
    return _aggregate(
        concurrency, n_requests, len(oks), failures,
        [r.ttft_s for r in oks if r.ttft_s is not None],
        [r.tpot_s for r in oks if r.tpot_s is not None],
        sum(r.n_generated for r in oks), wall)


def run_trace_inprocess(engine, prompt_ids_list, schedule, *,
                        timeout=600.0, workers=32, constraint=None):
    """Open-loop TRACE-REPLAY against ``InferenceEngine.submit``
    (ISSUE 12 satellite / ROADMAP item 2b first slice): requests fire
    at a seeded bursty schedule's instants (serve/arrivals.py) with the
    schedule's mixed prompt/output lengths, instead of the closed
    ladder's back-to-back uniform load. Row shape matches the ladder
    rows (mode "trace_replay"), with the realized schedule statistics
    attached — including arrival LATENESS: workers drain their streams,
    so in-flight requests are bounded at ``workers`` and arrivals past
    that fire late (the open-loop promise degrades); the row states how
    late, instead of silently reporting the scheduled load as applied."""
    from llm_in_practise_tpu.serve import arrivals as arrivals_mod

    rng = random.Random(0)
    picks = [rng.randrange(len(prompt_ids_list)) for _ in schedule]

    def submit(arrival):
        ids = list(prompt_ids_list[picks.pop()])
        ids = (ids * (arrival.prompt_tokens // max(len(ids), 1) + 1)
               )[:arrival.prompt_tokens]
        return _submit_and_drain(engine, ids, arrival.max_tokens,
                                 timeout, constraint=constraint)

    t0 = time.perf_counter()
    late: list = []
    done = arrivals_mod.replay(schedule, submit, workers=workers,
                               lateness=late)
    wall = time.perf_counter() - t0
    return {"mode": "trace_replay",
            "arrivals": {**arrivals_mod.describe(schedule),
                         **arrivals_mod.lateness_stats(late)},
            **_engine_rows_aggregate(done, workers, len(schedule), wall)}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--url", default="http://127.0.0.1:8000")
    p.add_argument("--model", default=None)
    p.add_argument("--concurrency", default="1,4,8,16",
                   help="comma-separated ladder")
    p.add_argument("--requests", type=int, default=32, help="per level")
    p.add_argument("--max_tokens", type=int, default=64)
    p.add_argument("--timeout", type=float, default=120.0)
    p.add_argument("--sla_ttft_ms", type=float, default=2000.0)
    p.add_argument("--sla_tpot_ms", type=float, default=100.0)
    args = p.parse_args()

    if args.model is None:
        with urllib.request.urlopen(f"{args.url}/v1/models", timeout=10) as r:
            args.model = json.loads(r.read())["data"][0]["id"]

    rows = []
    for level in (int(c) for c in args.concurrency.split(",")):
        row = run_level(args.url, args.model, level, args.requests,
                        args.max_tokens, args.timeout)
        rows.append(row)
        print(json.dumps(row))

    print(f"\n{'conc':>5} {'OutTPS':>8} {'p50TTFT':>9} {'p99TTFT':>9} "
          f"{'p50TPOT':>9} {'p99TPOT':>9} {'ok%':>5}")
    for r in rows:
        print(f"{r['concurrency']:>5} {r['output_tps']:>8.1f} "
              f"{r['ttft_p50_ms']:>8.0f}m {r['ttft_p99_ms']:>8.0f}m "
              f"{r['tpot_p50_ms']:>8.1f}m {r['tpot_p99_ms']:>8.1f}m "
              f"{r['success_rate'] * 100:>4.0f}%")
    worst = rows[-1]
    ok = (worst["ttft_p99_ms"] < args.sla_ttft_ms
          and worst["tpot_p99_ms"] < args.sla_tpot_ms)
    print(f"SLA (p99 TTFT<{args.sla_ttft_ms:.0f}ms, "
          f"p99 TPOT<{args.sla_tpot_ms:.0f}ms): {'PASS' if ok else 'FAIL'}")


if __name__ == "__main__":
    main()
