"""Host-gap baseline bench — BENCH_HOST_GAP artifact producer (CPU).

Measures the per-step engine-loop timeline (obs/steptrace.py) under
closed-loop load on every CPU-reproducible engine path — contiguous,
paged, and paged + fused ngram speculation — and writes the baseline
host-gap block ROADMAP item 3's async host/device-overlap refactor must
drive toward zero. Each leg:

- drives the engine through the FULL server path (OpenAIServer over
  HTTP is stood up; load is closed-loop against ``engine.submit`` so
  the numbers are engine-attributable),
- embeds the steptrace snapshot (per-activity host seconds, device-busy
  and host-gap fractions) and GATES on coverage: attributed host
  activities + device dispatch time must explain >= 95 % of engine-loop
  wall time (``tests/test_steptrace.py`` re-asserts the artifact),
- scrapes ``llm_host_gap_fraction`` LIVE from ``/metrics`` over HTTP,
- writes a Perfetto dual-lane Chrome-JSONL file and verifies BOTH lanes
  (engine host lane + dispatch window lane) carry events.

Run: ``JAX_PLATFORMS=cpu python tools/host_gap_bench.py``
Writes ``BENCH_HOST_GAP_r09.json`` at the repo root. The tier-1 smoke
runs ``main(quick=True)`` against a temp dir.

CPU caveat: absolute fractions are CPU-backend numbers (device dispatch
here is host-threaded XLA); the attribution machinery is what this
artifact pins — on a real chip run the same legs via
``tools/tpu_serve_bench.py`` (its artifact embeds the same block).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

OUT = os.path.join(REPO, "BENCH_HOST_GAP_r09.json")
COVERAGE_GATE = 0.95


def _build(kv_layout: str, spec: bool, tracer):
    import jax
    import jax.numpy as jnp

    from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
    from llm_in_practise_tpu.serve.engine import InferenceEngine

    cfg = GPTConfig(vocab_size=64, seq_len=256, n_layer=2, n_head=2,
                    embed_dim=64, dropout=0.0, pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    return InferenceEngine(
        model, params, max_slots=8, cache_len=256,
        cache_dtype=jnp.float32, chunked_prefill=32,
        prefix_cache=True, kv_layout=kv_layout,
        speculative_k=4 if spec else None, tracer=tracer)


def _prompts():
    # self-similar prompts so the ngram proposer actually drafts (the
    # spec leg must exercise draft_propose + the fused verify path)
    base = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5]
    return [
        (base * 4)[:30],
        [(i * 7 + 3) % 64 for i in range(48)],
        base * 2,
        [(i * 5 + 1) % 64 for i in range(20)] * 2,
    ]


def _drive(engine, *, concurrency: int, n_requests: int,
           max_tokens: int) -> None:
    from llm_in_practise_tpu.serve.engine import SamplingParams

    prompts = _prompts()
    lock = threading.Lock()
    left = [n_requests]

    def worker(i):
        while True:
            with lock:
                if left[0] <= 0:
                    return
                left[0] -= 1
                k = left[0]
            req = engine.submit(prompts[k % len(prompts)],
                                SamplingParams(greedy=True,
                                               max_tokens=max_tokens))
            req.result()

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def _perfetto_lanes(path: str) -> dict:
    from llm_in_practise_tpu.obs.steptrace import (
        HOST_LANE_TID,
        WINDOW_LANE_TID,
    )

    host = window = 0
    with open(path, encoding="utf-8") as f:
        for line in f:
            try:
                ev = json.loads(line)
            except json.JSONDecodeError:
                continue
            if ev.get("ph") != "X" or ev.get("cat") != "steptrace":
                continue
            if ev.get("tid") == HOST_LANE_TID:
                host += 1
            elif ev.get("tid") == WINDOW_LANE_TID:
                window += 1
    return {"host_events": host, "window_events": window}


def run_leg(name: str, *, kv_layout: str, spec: bool, workdir: str,
            quick: bool) -> dict:
    from bench import host_gap_snapshot
    from llm_in_practise_tpu.obs.trace import Tracer
    from llm_in_practise_tpu.serve.api import OpenAIServer

    trace_path = os.path.join(workdir, f"host_gap_{name}.trace.jsonl")
    tracer = Tracer(trace_file=trace_path)
    engine = _build(kv_layout, spec, tracer)

    class _Tok:
        def encode(self, text):
            return [b % 64 for b in text.encode("utf-8", "replace")[:64]]

        def decode(self, ids):
            return " ".join(str(int(i)) for i in ids)

    srv = OpenAIServer(engine, _Tok(), model_name=f"host-gap-{name}",
                       tracer=tracer)
    port = srv.serve(host="127.0.0.1", port=0, background=True)
    try:
        # warmup (compiles), then reset nothing: the recorder's totals
        # are lifetime, and compile stalls are real host/device time —
        # a separate measured pass would hide first-use cliffs the
        # recorder exists to show; quick mode keeps everything tiny
        _drive(engine, concurrency=4 if quick else 8,
               n_requests=8 if quick else 24, max_tokens=8)
        _drive(engine, concurrency=4 if quick else 8,
               n_requests=8 if quick else 48,
               max_tokens=8 if quick else 32)
        block = host_gap_snapshot(engine)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
            metrics = resp.read().decode()
        live = [ln for ln in metrics.splitlines()
                if ln.startswith("llm_host_gap_fraction")]
        if not live:
            raise SystemExit(
                f"leg {name}: llm_host_gap_fraction absent from the "
                "live /metrics exposition")
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/requests",
                timeout=30) as resp:
            debug_requests = json.loads(resp.read().decode())
    finally:
        srv.shutdown()
    tracer.set_trace_file(None)   # flush + close the JSONL sink
    lanes = _perfetto_lanes(trace_path)
    if not (lanes["host_events"] and lanes["window_events"]):
        raise SystemExit(
            f"leg {name}: Perfetto file {trace_path} is missing a lane "
            f"({lanes})")
    if block["coverage"] < COVERAGE_GATE:
        raise SystemExit(
            f"leg {name}: steptrace coverage {block['coverage']:.4f} "
            f"below the {COVERAGE_GATE} gate — host activities are "
            "leaking into `other`")
    sample = (debug_requests["finished"][-1]
              if debug_requests["finished"] else None)
    return {
        "leg": name,
        "kv_layout": kv_layout,
        "speculation": "ngram" if spec else "off",
        "host_gap": block,
        "live_host_gap_fraction": float(live[0].split()[-1]),
        "spec_rounds": engine.spec_rounds,
        "perfetto": {"file": os.path.basename(trace_path), **lanes},
        "debug_requests_sample": sample,
        "critical_path_seconds_total":
            debug_requests["critical_path_seconds_total"],
    }


def main(quick: bool = False, out: str | None = None,
         workdir: str | None = None) -> dict:
    workdir = workdir or REPO
    legs = [
        ("contiguous", dict(kv_layout="contiguous", spec=False)),
        ("paged", dict(kv_layout="paged", spec=False)),
        ("paged_spec", dict(kv_layout="paged", spec=True)),
    ]
    # quick mode shrinks each leg's load, not the leg list — the
    # coverage gate must hold on every engine path either way
    results = []
    for name, kw in legs:
        t0 = time.perf_counter()
        leg = run_leg(name, workdir=workdir, quick=quick, **kw)
        leg["leg_seconds"] = round(time.perf_counter() - t0, 1)
        results.append(leg)
        print(json.dumps({"leg": name,
                          "host_gap_fraction":
                              leg["host_gap"]["host_gap_fraction"],
                          "coverage": leg["host_gap"]["coverage"]}),
              flush=True)
    artifact = {
        "metric": "host_gap_fraction_per_engine_path",
        "coverage_gate": COVERAGE_GATE,
        "legs": results,
        "environment_caveat": (
            "CPU backend: device-busy time is host-threaded XLA "
            "compute, so fractions are not chip numbers — the pinned "
            "quantity is the ATTRIBUTION (coverage >= 0.95 on every "
            "path) and the baseline shape; real-chip legs ride "
            "tools/tpu_serve_bench.py's observability.host_gap block"),
    }
    path = out or OUT
    with open(path, "w") as f:
        json.dump(artifact, f, indent=2)
    print("wrote", path)
    return artifact


if __name__ == "__main__":
    main(quick=os.environ.get("HOST_GAP_QUICK", "") == "1")
