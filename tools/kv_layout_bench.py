"""Paged-vs-contiguous KV layout A/B — the ROADMAP item 2 acceptance
artifact.

Both legs get the SAME persistent KV pool bytes. The contiguous layout
must spend them as worst-case ``max_slots x cache_len`` reservations,
so the pool caps it at ``pool_tokens // cache_len`` slots; the paged
layout spends pages on ACTUAL context, so the same bytes serve 4x the
slots for short/medium requests — the concurrency ladder runs PAST the
contiguous slot ceiling and records what each layout actually
sustains (peak concurrently-active slots, throughput, latency
percentiles, shed fraction).

What "same pool bytes" means here (stated in the artifact): the
persistent KV allocation. The paged programs additionally gather a
transient contiguous view per dispatch (width = the pow2 bucket of the
longest LIVE context, freed by XLA between dispatches) — the artifact
reports that workspace bound; a fused paged-attention kernel that
reads pages in place is the follow-up that removes it
(docs/paged-kv.md "Limitations").

CPU-runnable (tiny GPT, greedy) so the A/B is reproducible anywhere:
``python tools/kv_layout_bench.py``. Writes ``BENCH_KV_LAYOUT_r06.json``
at the repo root with a mid-load ``/debug/kv`` snapshot embedded per
paged level.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp

from deploy.benchmark.bench_serve import run_level_inprocess
from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
from llm_in_practise_tpu.serve.engine import InferenceEngine

OUT = os.environ.get("KV_LAYOUT_BENCH_OUT",
                     os.path.join(REPO, "BENCH_KV_LAYOUT_r06.json"))

CACHE_LEN = 256
POOL_TOKENS = 2048            # the shared KV budget: 8 contiguous slots
PAGED_SLOTS = 32              # paged serves 4x the slots on those bytes
PAGE_SIZE = 16
LADDER = (4, 8, 16, 24, 32)   # past the contiguous ceiling of 8
MAX_TOKENS = 24


def build_model():
    cfg = GPTConfig(vocab_size=256, seq_len=CACHE_LEN, n_layer=4,
                    n_head=4, embed_dim=64, dropout=0.0,
                    pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    row_bytes = 2 * cfg.n_head * (cfg.embed_dim // cfg.n_head) * 4  # k+v f32
    return model, params, cfg.n_layer * row_bytes


def prompts():
    out = []
    for j in range(16):
        n = 8 + (j * 5) % 25                  # 8..32 tokens
        out.append([(j * 31 + i * 7 + 1) % 255 + 1 for i in range(n)])
    return out


def run_leg(layout: str, model, params, prompt_ids, token_bytes):
    kw = dict(cache_len=CACHE_LEN, cache_dtype=jnp.float32,
              chunked_prefill=64)
    if layout == "paged":
        eng = InferenceEngine(model, params, max_slots=PAGED_SLOTS,
                              kv_layout="paged", kv_page_size=PAGE_SIZE,
                              kv_pool_tokens=POOL_TOKENS, **kw)
    else:
        eng = InferenceEngine(model, params,
                              max_slots=POOL_TOKENS // CACHE_LEN, **kw)
    eng.start()
    # warmup: compile every ladder level's shapes (view-width buckets,
    # batched-admission sizes, block variants) before timing — a
    # first-seen compile inside a timed level reads as a TTFT cliff
    # full-depth generations: the paged view-width buckets (and the
    # contiguous block variants) are reached only as contexts GROW, so
    # short warmup tokens would leave a compile inside a timed level
    run_level_inprocess(eng, prompt_ids, concurrency=max(LADDER),
                        n_requests=2 * max(LADDER),
                        max_tokens=MAX_TOKENS)
    for conc in LADDER:
        run_level_inprocess(eng, prompt_ids, concurrency=conc,
                            n_requests=max(8, conc),
                            max_tokens=MAX_TOKENS)
    levels = []
    for conc in LADDER:
        peak = {"active": 0, "kv": None}
        stop = threading.Event()

        def sample():
            while not stop.is_set():
                active = eng.stats.active_slots
                if active >= peak["active"]:
                    peak["active"] = active
                    peak["kv"] = eng.debug_kv()
                time.sleep(0.02)

        sampler = threading.Thread(target=sample, daemon=True)
        sampler.start()
        row = run_level_inprocess(eng, prompt_ids, concurrency=conc,
                                  n_requests=max(48, 2 * conc),
                                  max_tokens=MAX_TOKENS)
        stop.set()
        sampler.join(timeout=2)
        row["peak_active_slots"] = peak["active"]
        row["debug_kv_at_peak"] = peak["kv"]
        levels.append(row)
        print(json.dumps({k: row[k] for k in
                          ("concurrency", "success_rate", "output_tps",
                           "ttft_p99_ms", "peak_active_slots")
                          if k in row} | {"layout": layout}), flush=True)
    eng.stop()
    max_sustained = max(lv["peak_active_slots"] for lv in levels)
    return {
        "layout": layout,
        "max_slots": eng.max_slots,
        "kv_pool_tokens": POOL_TOKENS,
        "kv_pool_bytes": POOL_TOKENS * token_bytes,
        "page_size": PAGE_SIZE if layout == "paged" else None,
        "transient_view_bound_bytes": (
            eng.max_slots * CACHE_LEN * token_bytes
            if layout == "paged" else 0),
        "max_sustained_concurrency": max_sustained,
        "preemptions": getattr(eng, "preemptions", 0),
        "final_debug_kv": eng.debug_kv(),
        "levels": levels,
    }


def main() -> None:
    model, params, token_bytes = build_model()
    prompt_ids = prompts()
    print(f"pool budget: {POOL_TOKENS} KV tokens "
          f"({POOL_TOKENS * token_bytes} bytes) | device "
          f"{jax.devices()[0].device_kind}", flush=True)
    legs = {}
    for layout in ("contiguous", "paged"):
        t0 = time.perf_counter()
        legs[layout] = run_leg(layout, model, params, prompt_ids,
                               token_bytes)
        legs[layout]["leg_seconds"] = round(time.perf_counter() - t0, 1)
    paged, contig = legs["paged"], legs["contiguous"]
    artifact = {
        "bench": "kv_layout_ab",
        "ladder": list(LADDER),
        "max_tokens": MAX_TOKENS,
        "note": ("both legs hold the same persistent KV pool bytes; "
                 "the paged leg additionally uses a transient per-"
                 "dispatch gather view bounded by "
                 "transient_view_bound_bytes (freed between "
                 "dispatches) — see docs/paged-kv.md"),
        "legs": legs,
        "paged_sustains_higher_concurrency": (
            paged["max_sustained_concurrency"]
            > contig["max_sustained_concurrency"]),
    }
    with open(OUT, "w") as f:
        json.dump(artifact, f, indent=1)
    print(f"wrote {OUT}: paged {paged['max_sustained_concurrency']} vs "
          f"contiguous {contig['max_sustained_concurrency']} "
          f"sustained slots on {POOL_TOKENS} pool tokens", flush=True)
    if not artifact["paged_sustains_higher_concurrency"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
