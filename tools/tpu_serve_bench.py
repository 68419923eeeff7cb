"""Serving benchmark on the real TPU chip — BENCH_SERVE artifact producer.

Stands up the full serving stack in-process (continuous-batching engine +
OpenAI server with SSE streaming) on one chip and drives TWO concurrency
ladders:

1. **In-process** (``run_level_inprocess``): closed-loop workers against
   ``engine.submit`` directly — no HTTP, no SSE. TTFT/TPOT come from the
   engine's own request stamps, so these rows are **engine-attributable**.
2. **HTTP/SSE** (``run_level``): the reference's ``vllm bench serve``
   ShareGPT-style ladder (``LLM_on_Kubernetes/Inference_Platfrom/
   README.md:1345-1520``) through the full server path, now with
   per-failure reasons recorded — a lost request is a bug until the
   artifact says why.

**Model-size caveat, stated up front:** the served model is the GPTLike
6L/512d architecture (~36M params, bf16) — the reference's from-scratch
teaching model — NOT an 8B. Absolute tok/s are not comparable to
BASELINE.md's table; the comparable quantities are the shapes: TTFT/TPOT
percentiles vs concurrency, saturation behavior, and the SLA gates
(p99 TTFT < 2 s, p99 TPOT < 100 ms). The per-chip 8B-class number lives
in bench.py's QLoRA/MFU metrics instead.

Run on the TPU host (default env): ``python tools/tpu_serve_bench.py``
Writes ``BENCH_SERVE_r03.json`` at the repo root.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp

from deploy.benchmark.bench_serve import PROMPTS, run_level, run_level_inprocess
from llm_in_practise_tpu.models.gpt import GPT, gptlike_config
from llm_in_practise_tpu.serve.api import OpenAIServer
from llm_in_practise_tpu.serve.engine import InferenceEngine

OUT = os.path.join(REPO, "BENCH_SERVE_r03.json")
LADDER = (8, 16, 32, 64, 128, 256)   # reference ladder tops out at 256
MAX_TOKENS = 64
MAX_SLOTS = 64
SLA = {"ttft_p99_ms": 2000.0, "tpot_p99_ms": 100.0}


def _requests_for(conc: int) -> int:
    return max(64, 2 * conc)


class ByteTokenizer:
    def encode(self, text: str):
        return list(text.encode("utf-8", errors="replace")[:256])

    def decode(self, ids):
        return bytes(int(i) % 256 for i in ids).decode("utf-8",
                                                       errors="replace")


def main() -> None:
    from llm_in_practise_tpu.core.mesh import require_tpu

    require_tpu()
    cfg = gptlike_config(32768, seq_len=1024, dropout=0.0,
                         compute_dtype="bfloat16")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    mixed_step = os.environ.get("SERVE_MIXED_STEP", "1") != "0"
    # --kv-layout A/B leg (docs/paged-kv.md): SERVE_KV_LAYOUT=paged
    # serves the ladder off the block-table page pool (optionally
    # SERVE_KV_POOL_TOKENS sized below max_slots*cache_len to run the
    # concurrency ladder past a contiguous ceiling — the dedicated
    # same-bytes A/B is tools/kv_layout_bench.py)
    kv_layout = os.environ.get("SERVE_KV_LAYOUT", "contiguous")
    kv_pool_tokens = os.environ.get("SERVE_KV_POOL_TOKENS")
    # speculation leg (ISSUE 9 / ROADMAP item 4): SERVE_SPEC=ngram runs
    # the prompt-lookup proposer, SERVE_SPEC=draft a SELF-speculative
    # draft — the target's first SERVE_SPEC_DRAFT_LAYERS blocks sharing
    # the stem/head. Either way the fused spec round verifies the k
    # drafts in one dispatch; the dedicated
    # cross-leg A/B artifact is tools/spec_ladder_bench.py
    # (BENCH_SPEC_LADDER_r07.json).
    spec_mode = os.environ.get("SERVE_SPEC", "off")
    if spec_mode not in ("off", "ngram", "draft"):
        raise SystemExit(f"SERVE_SPEC must be off|ngram|draft, "
                         f"got {spec_mode!r}")
    # tensor-parallel leg (ISSUE 10 / ROADMAP item 1): SERVE_TP=N
    # shards the model + KV cache over the first N devices — decode is
    # bandwidth-bound (perf.md Findings 13/14), so each layer shard
    # streams from its own HBM controller and the per-token weight-read
    # floor divides by N. On the real 8-chip host this is the
    # production decode-replica shape (docs/serving-tp.md); the
    # CPU-reproducible correctness ladder is tools/tp_ladder_bench.py.
    serve_tp = int(os.environ.get("SERVE_TP", "1"))
    mesh = None
    if serve_tp > 1:
        if serve_tp > len(jax.devices()):
            raise SystemExit(f"SERVE_TP={serve_tp} but only "
                             f"{len(jax.devices())} devices attached")
        from llm_in_practise_tpu.parallel import strategy as S

        _strat = S.tensor_parallel(model=serve_tp, data=1)
        mesh = _strat.build_mesh(jax.devices()[:serve_tp])
    spec_k = (None if spec_mode == "off"
              else int(os.environ.get("SERVE_SPEC_K", "4")))
    draft_model = draft_params = None
    if spec_mode == "draft":
        D = int(os.environ.get("SERVE_SPEC_DRAFT_LAYERS", "2"))
        draft_params = {k: v for k, v in params.items()
                        if not k.startswith("block_")
                        or int(k.rsplit("_", 1)[1]) < D}
        draft_model = GPT(cfg.replace(n_layer=D))
    if mesh is not None:
        from llm_in_practise_tpu.serve.engine import (
            shard_params_for_serving,
        )

        params = shard_params_for_serving(params, _strat, mesh)
    engine = InferenceEngine(
        model, params, max_slots=MAX_SLOTS, cache_len=1024,
        chunked_prefill=256, speculative_k=spec_k,
        draft_model=draft_model, draft_params=draft_params,
        mixed_step=mixed_step,
        kv_layout=kv_layout, mesh=mesh,
        kv_pool_tokens=(int(kv_pool_tokens) if kv_pool_tokens else None),
    )
    engine.start()
    tok = ByteTokenizer()
    prompt_ids = [tok.encode(p) for p in PROMPTS]
    print(f"device {jax.devices()[0].device_kind} | slots {MAX_SLOTS} | "
          f"mixed_step {mixed_step} | "
          f"spec {spec_mode} | tp {serve_tp}",
          flush=True)

    # warmup: compile prefill buckets (incl. the pow2 batched-admission
    # sizes up to max_slots), decode, and the capped block variants before
    # timing anything — a saturating burst, then one mini-pass per ladder
    # level so no first-use compile lands inside a timed level
    t0 = time.perf_counter()
    run_level_inprocess(engine, prompt_ids, concurrency=2 * MAX_SLOTS,
                        n_requests=3 * MAX_SLOTS, max_tokens=8)
    for conc in LADDER:
        run_level_inprocess(engine, prompt_ids, concurrency=conc,
                            n_requests=max(8, conc), max_tokens=4)
    print(f"warmup/compile {time.perf_counter()-t0:.0f}s", flush=True)

    # SLO goodput accounting from here on (post-warmup, so first-use
    # compiles don't count as violations): the artifact's device-plane
    # block then splits output tokens into slo=ok vs slo=violated
    engine.stats.goodput.configure(SLA["ttft_p99_ms"] / 1e3,
                                   SLA["tpot_p99_ms"] / 1e3)

    inproc_levels = []
    for conc in LADDER:
        r = run_level_inprocess(engine, prompt_ids, concurrency=conc,
                                n_requests=_requests_for(conc),
                                max_tokens=MAX_TOKENS)
        r["sla_ok"] = (r["ttft_p99_ms"] < SLA["ttft_p99_ms"]
                       and r["tpot_p99_ms"] < SLA["tpot_p99_ms"])
        inproc_levels.append(r)
        print(json.dumps(r), flush=True)

    # trace-replay row (ISSUE 12 satellite / ROADMAP 2b first slice):
    # the SAME engine under a seeded bursty open-loop schedule with
    # mixed prompt/output lengths — realistic-load numbers next to the
    # uniform ladder, same row shape (serve/arrivals.py)
    from deploy.benchmark.bench_serve import run_trace_inprocess
    from llm_in_practise_tpu.serve import arrivals

    sched = arrivals.synthesize(
        seed=42, n_requests=_requests_for(64), mean_iat_s=0.05, cv=2.0,
        prompt_tokens=(16, 192), max_tokens=(16, MAX_TOKENS))
    r = run_trace_inprocess(engine, prompt_ids, sched)
    # success_rate guards the gate: with zero served requests the
    # percentiles are vacuous 0.0 and must not read as an SLA pass
    r["sla_ok"] = (r["success_rate"] > 0.5
                   and r["ttft_p99_ms"] < SLA["ttft_p99_ms"]
                   and r["tpot_p99_ms"] < SLA["tpot_p99_ms"])
    inproc_levels.append(r)
    print(json.dumps(r), flush=True)

    srv = OpenAIServer(engine, tok, model_name="gptlike-tpu")
    port = srv.serve(host="127.0.0.1", port=0, background=True)
    url = f"http://127.0.0.1:{port}"
    print(f"server on {url}", flush=True)

    # HTTP-side warmup: the chat prompt builder wraps prompts in ChatML,
    # landing them in LONGER prefill buckets than the raw in-process
    # prompt ids — without this, those buckets compile inside the first
    # timed HTTP level and read as 20 s+ TTFT outliers. Deterministic
    # coverage: hit EVERY prompt once (run_level samples randomly and
    # can miss one), then a concurrent pass for the batched variants.
    from deploy.benchmark.bench_serve import one_request

    t0 = time.perf_counter()
    for p in PROMPTS:
        one_request(url, "gptlike-tpu", p, max_tokens=4, timeout=600)
    run_level(url, "gptlike-tpu", concurrency=8,
              n_requests=2 * len(PROMPTS), max_tokens=4, timeout=600)
    print(f"http warmup {time.perf_counter()-t0:.0f}s", flush=True)

    http_levels = []
    for conc in LADDER:
        r = run_level(url, "gptlike-tpu", concurrency=conc,
                      n_requests=_requests_for(conc),
                      max_tokens=MAX_TOKENS, timeout=600)
        r["mode"] = "http_sse"
        r["sla_ok"] = (r["ttft_p99_ms"] < SLA["ttft_p99_ms"]
                       and r["tpot_p99_ms"] < SLA["tpot_p99_ms"])
        http_levels.append(r)
        print(json.dumps(r), flush=True)

    # observability snapshot BEFORE shutdown: the /metrics exposition
    # (dispatch accounting, TTFT/TPOT histograms), the trace-ring
    # summary, AND the device plane (per-phase MFU / HBM-bandwidth
    # utilization, peak HBM, compile seconds, SLO goodput) ride in the
    # artifact, so a perf regression in these rows arrives with its
    # per-phase breakdown attached (bench.obs_snapshot)
    from bench import obs_snapshot

    observability = obs_snapshot(server=srv, engine=engine)

    srv.shutdown()  # also stops the engine thread it owns
    artifact = {
        "observability": observability,
        "device": jax.devices()[0].device_kind,
        "model": "GPTLike 6L/512d bf16 (~36M params) — NOT 8B; see header",
        "engine": {"max_slots": MAX_SLOTS, "cache_len": 1024,
                   "chunked_prefill": 256,
                   "mixed_step": mixed_step,
                   "speculation": {
                       "mode": spec_mode, "k": spec_k,
                       "proposed": engine.spec_proposed,
                       "accepted": engine.spec_accepted,
                       "spec_rounds": engine.spec_rounds,
                       "tokens_per_spec_dispatch": (
                           round(engine.spec_round_tokens
                                 / engine.spec_rounds, 3)
                           if engine.spec_rounds else None)},
                   "kv_layout": kv_layout,
                   "tensor_parallel": {
                       "tp": serve_tp,
                       "collective_bytes_total":
                           round(engine.collective_bytes_total, 1),
                       "collective_seconds_total":
                           round(engine.collective_seconds_total, 6)},
                   "debug_kv": engine.debug_kv(),
                   # host-gap dial (obs/steptrace.py; full block incl.
                   # per-activity totals rides in observability.host_gap)
                   "host_gap_fraction": round(
                       engine.steptrace.snapshot()["host_gap_fraction"],
                       4),
                   "mixed_blocks": engine.mixed_blocks,
                   "dispatches_per_step":
                       round(engine.dispatch_meter.mean_per_step, 3),
                   "batched_prefill_admission": True,
                   "block_cap_under_queueing": True},
        "max_tokens": MAX_TOKENS,
        "sla": SLA,
        "levels_inprocess": inproc_levels,
        "levels_http_sse": http_levels,
        "reference_baseline": "BASELINE.md ladder (RTX 3090, Qwen3-8B, "
                              "vLLM): 368.3→3808.1 tok/s @ conc 8→256 — "
                              "different model scale, compare shapes not "
                              "absolutes",
        "ladders": (
            "the in-process rows exclude the HTTP/SSE transport and "
            "time requests at the engine, so they are the "
            "engine-attributable numbers; the http_sse rows measure the "
            "full server path"
        ),
    }
    with open(OUT, "w") as f:
        json.dump(artifact, f, indent=2)
    print("wrote", OUT)


if __name__ == "__main__":
    main()
