"""Multi-billion-param serving ladder on the real TPU chip.

Round 2's serving artifact measured a 36M GPTLike — fine for engine
mechanics, useless for comparing against BASELINE.md's ladder, which
serves Qwen3-8B. This tool serves a **Qwen3-architecture model with
distinct-per-layer NF4 weights through the W4A16 fused-kernel path**
(``serve/quantized.py``) on one chip, driving the engine directly
(in-process — engine-attributable, no HTTP transport in the timings)
across a concurrency ladder.

Reference counterpart: the vLLM W4A16 serving of quantized exports
(``Quantization/LLM-Compressor/GPTQ/eval_qwen3_4b_gptq.py:11-21``) and
the benchmark ladder methodology
(``LLM_on_Kubernetes/Inference_Platfrom/README.md:1345-1520``).

Knobs (env):

- ``QWEN3_SERVE_GEOM``: ``small`` (d2048/L28 ≈ 1.72B, default), ``8b``
  (d4096/L36 GQA 32:8 — the real Qwen3-8B geometry, NF4 ≈ 4.4 GiB), or
  ``14b`` (d5120/L40 — the 14B training rung's serving twin; pair with
  ``QWEN3_SERVE_SLOTS=8`` and NF4, the int8 tree leaves no KV room).
- ``QWEN3_SERVE_SCAN`` (default 1): serve in the scan-layers layout —
  stacked params AND stacked KV cache, every engine program compiling
  ONE block regardless of depth; the packed NF4 components ride the
  decode scan as sideband inputs (models/layers.py scan_sideband). This
  keeps the 36-layer model's engine compile flat in depth.
- ``QWEN3_SERVE_LAYERS``: override layer count within the geometry.
- ``QWEN3_SERVE_LONG`` (default 0): long-context mode — 8K cache,
  synthetic ~6K-token prompts through chunked prefill, fewer slots;
  measures the serving-side long-context story (the reference's is
  vLLM ``max_model_len``/chunked prefill —
  ``Deployment/Ray/serve_run_examples/deepseek.py:32-35``). Writes
  the ``_LONG`` artifact instead.
- ``QWEN3_SERVE_FMT`` (default ``nf4``): weight format. ``int8`` serves
  the W8A16 per-channel path (2x NF4's bytes, decode at memory speed —
  NF4 decode is dequant-BOUND at 8B, ``docs/perf.md`` Finding 9); its
  artifact gets an ``_INT8`` suffix. ``mixed`` is the 14B SLA split
  (int8 MLP + NF4 attention — ``peft/qlora.py::mixed_serve_fmt``): the
  MLP's 81% of layer bytes decode at int8 rate while the tree stays
  ~11 GiB; artifact suffix ``_MIXED``.

Writes ``BENCH_SERVE_QWEN3[_8B|_14B][_INT8|_MIXED][_LONG]_r05.json`` —
every non-default geometry/format gets its own artifact path (the
r03/r04 names were earlier rounds' runs).
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

from bench import _distinct_base_stacked, _distinct_nf4_base, _hbm_stats
from deploy.benchmark.bench_serve import PROMPTS, run_level_inprocess
from llm_in_practise_tpu.models.qwen3 import Qwen3, Qwen3Config
from llm_in_practise_tpu.quant.nf4 import tree_nbytes
from llm_in_practise_tpu.serve.engine import InferenceEngine
from llm_in_practise_tpu.serve.quantized import QuantizedModel

LONG_MODE = os.environ.get("QWEN3_SERVE_LONG", "0") != "0"
FMT = os.environ.get("QWEN3_SERVE_FMT", "nf4")
if FMT not in ("nf4", "int8", "mixed"):
    raise SystemExit(
        f"QWEN3_SERVE_FMT={FMT!r}: must be 'nf4', 'int8', or 'mixed'")
GEOM_NAME = os.environ.get("QWEN3_SERVE_GEOM", "small")
# every non-default geometry gets its own artifact path — a same-named
# rerun under a different geometry once clobbered a committed artifact
OUT = os.path.join(
    REPO, "BENCH_SERVE_QWEN3"
    + {"small": "", "8b": "_8B", "14b": "_14B"}[GEOM_NAME]
    + {"nf4": "", "int8": "_INT8", "mixed": "_MIXED"}[FMT]
    + ("_LONG" if LONG_MODE else "") + "_r05.json")
LADDER = (1, 2, 4) if LONG_MODE else (4, 8, 16, 32)
MAX_TOKENS = 32 if LONG_MODE else 64
CACHE_LEN = 8192 if LONG_MODE else 1024
PROMPT_LEN = 6144 if LONG_MODE else None  # None -> short text prompts
# Chunked-prefill span scales with prompt length (VERDICT r4 Weak #1):
# 256 is tuned for short-prompt TTFT fairness, but a 6144-token prompt
# at chunk 256 pays 24 serialized chunk dispatches before its first
# token — the r4 long ladder's 22-98 s TTFT was mostly this. 1024 cuts it to 6 dispatches while a chunk's compute
# still interleaves with decode.
CHUNK = int(os.environ.get("SERVE_CHUNK", "1024" if LONG_MODE else "256"))
# Dequant-bound decode (DECODE_AB_8B.json) amortizes per-token cost over
# live slots, so slots are the throughput lever; fp8 KV halves cache HBM
# to make room for more (vLLM --kv-cache-dtype fp8 parity).
MAX_SLOTS = int(os.environ.get("QWEN3_SERVE_SLOTS",
                               "4" if LONG_MODE else "16"))
KV_DTYPE = os.environ.get("QWEN3_SERVE_KV_DTYPE", "bfloat16")
if KV_DTYPE not in ("bfloat16", "fp8"):
    raise SystemExit(
        f"QWEN3_SERVE_KV_DTYPE={KV_DTYPE!r}: must be 'bfloat16' or "
        "'fp8' (fail fast — quantization takes minutes)")
SLA = {"ttft_p99_ms": 2000.0, "tpot_p99_ms": 100.0}
# Admission control (engine-level, round 5): shed requests whose queue
# wait already blew the TTFT SLA instead of serving them seconds late —
# over-capacity ladder levels then report a bounded served-TTFT plus a
# shed fraction (failures.queue_full), the reference's backpressure
# shape. 0 disables (pre-r5 semantics: infinite patience).
QUEUE_TIMEOUT_S = float(os.environ.get("SERVE_QUEUE_TIMEOUT_S", "1.5"))
MAX_QUEUE = int(os.environ.get("SERVE_MAX_QUEUE", "0")) or None
if QUEUE_TIMEOUT_S < 0 or (MAX_QUEUE is not None and MAX_QUEUE < 0):
    # fail at env parse: a negative timeout assigned post-warmup would
    # bypass the engine constructor's validation and shed EVERY request
    # after the multi-minute quantize+warmup
    raise SystemExit(
        f"SERVE_QUEUE_TIMEOUT_S={QUEUE_TIMEOUT_S} / "
        f"SERVE_MAX_QUEUE={MAX_QUEUE}: must be >= 0")


class ByteTokenizer:
    def encode(self, text: str):
        return list(text.encode("utf-8", errors="replace")[:256])

    def decode(self, ids):
        return bytes(int(i) % 256 for i in ids).decode(
            "utf-8", errors="replace")


from bench import G8B, G14B  # one geometry definition — no drift

GEOMS = {
    "small": dict(hidden_size=2048, intermediate_size=6144, n_layer=28,
                  n_head=16, n_kv_head=8, head_dim=128),
    "8b": dict(n_layer=36, **G8B),
    # the 14B training rung's serving twin (NF4 ~7.8 GiB; int8 would
    # not leave KV room on 16 GiB) — run with QWEN3_SERVE_SLOTS=8
    "14b": dict(n_layer=40, **G14B),
}

# Fail fast on configurations whose memory arithmetic cannot close —
# quantize + warmup cost ~5 min before the doomed compile would surface
# (same rationale as the KV_DTYPE check above).
def _check_14b_memory(n_layer: int) -> None:
    """Fail fast on configurations whose memory arithmetic cannot close
    — full arithmetic, not a slots rule of thumb: base bytes (measured
    r4/r5 trees, incl. the 1.45 GiB bf16 embedding) + KV for THIS
    cache_len/dtype must leave transient headroom on the 15.75 GiB
    chip. The LONG path's 8K cache makes a per-slot KV 8x the 1K one —
    a slots<=8 check alone would wave through an 18 GiB config and
    waste the ~5 min quantize before the OOM surfaced. Layer-count
    aware so a QWEN3_SERVE_LAYERS debug run isn't falsely blocked.
    """
    if GEOM_NAME != "14b":
        return
    # full-depth trees: nf4 6.8 GiB packed + 1.45 embed (r4 artifact);
    # mixed 9.96 int8 MLP + 1.22 NF4 attn + 1.45 embed; int8 ~13 GiB
    # (never fits at L40 with KV, but a reduced-layer debug run does) —
    # layer-proportional part scales with n_layer, the embedding does not
    layers_gib = {"nf4": 6.85, "mixed": 11.18, "int8": 13.0}[FMT] \
        * (n_layer / 40)
    base_gib = layers_gib + 1.45
    kv_bytes = 2 if KV_DTYPE == "bfloat16" else 1
    kv_gib = (n_layer * 2 * 8 * 128 * CACHE_LEN * kv_bytes
              * MAX_SLOTS) / 2**30
    if base_gib + kv_gib > 14.8:
        raise SystemExit(
            f"14b {FMT} L{n_layer}: base ~{base_gib:.1f} GiB + KV "
            f"{kv_gib:.1f} GiB ({MAX_SLOTS} slots x {CACHE_LEN} "
            f"{KV_DTYPE}) exceeds the ~14.8 GiB budget (15.75 limit - "
            "transients) — reduce slots/cache or use fp8 KV")


def main() -> None:
    from llm_in_practise_tpu.core.mesh import require_tpu

    require_tpu()
    # Persistent compile cache BEFORE the first jitted program (the
    # quantizer's): the engine warmup's 4.5-14 min of compiles become
    # cache loads on every rerun (core/compile_cache.py; the engine
    # enables it too, but by then quantization has already compiled).
    from llm_in_practise_tpu.core.compile_cache import (
        enable_compilation_cache,
    )

    cache_dir = enable_compilation_cache()
    print(f"compile cache: {cache_dir}", flush=True)
    geom = dict(GEOMS[GEOM_NAME])
    if "QWEN3_SERVE_LAYERS" in os.environ:
        geom["n_layer"] = int(os.environ["QWEN3_SERVE_LAYERS"])
    use_scan = os.environ.get("QWEN3_SERVE_SCAN", "1") != "0"
    n_layer = geom["n_layer"]
    _check_14b_memory(n_layer)
    cfg = Qwen3Config(
        vocab_size=151936, max_seq_len=CACHE_LEN, rope_theta=1e6,
        tie_word_embeddings=True, remat=False, compute_dtype="bfloat16",
        **geom,
    )
    print(f"quantizing distinct {FMT} base (d{cfg.hidden_size}/L{n_layer}, "
          f"scan={use_scan})...", flush=True)
    serve_cfg = cfg
    if use_scan:
        # straight into the stacked layout — peak = packed tree + one
        # layer's f32 seed (an int8 8B cannot afford unrolled+stacked)
        qparams, quant_s = _distinct_base_stacked(cfg, Qwen3, fmt=FMT)
        serve_cfg = cfg.replace(scan_layers=True)
    else:
        qparams, quant_s = _distinct_nf4_base(cfg, Qwen3, fmt=FMT)
    from llm_in_practise_tpu.peft.fused import _is_quant
    from llm_in_practise_tpu.quant.int8 import Int8Tensor

    def _leaf_params(l):
        if isinstance(l, Int8Tensor):
            return l.q.size
        return l.packed.size * 2 if _is_quant(l) else l.size

    packed_bytes = sum(
        l.nbytes for l in jax.tree.leaves(qparams, is_leaf=_is_quant)
        if _is_quant(l)) or tree_nbytes(qparams)
    n_params = sum(
        _leaf_params(l)
        for l in jax.tree.leaves(qparams, is_leaf=_is_quant))
    print(f"{FMT} base {packed_bytes/2**30:.2f} GiB in {quant_s:.0f}s | "
          f"{_hbm_stats()}", flush=True)

    decode_steps = int(os.environ.get("SERVE_DECODE_STEPS", "8"))
    mixed_step = os.environ.get("SERVE_MIXED_STEP", "1") != "0"
    engine = InferenceEngine(
        QuantizedModel(Qwen3(serve_cfg)), qparams, max_slots=MAX_SLOTS,
        cache_len=CACHE_LEN, chunked_prefill=CHUNK, speculative_k=None,
        cache_dtype={"bfloat16": jnp.bfloat16,
                     "fp8": jnp.float8_e4m3fn}[KV_DTYPE],
        decode_steps=decode_steps, mixed_step=mixed_step,
        # admission knobs OFF during warmup: first-run compiles hold the
        # queue for minutes and a 1.5 s timeout would shed every warmup
        # request before it compiled its program; enabled post-warmup
    )
    engine.start()
    tok = ByteTokenizer()
    if PROMPT_LEN:
        import numpy as _np
        _rng = _np.random.default_rng(0)
        prompt_ids = [list(map(int, _rng.integers(0, 151936, PROMPT_LEN)))
                      for _ in range(8)]
    else:
        prompt_ids = [tok.encode(p) for p in PROMPTS]
    print(f"device {jax.devices()[0].device_kind} | slots {MAX_SLOTS} | "
          f"decode_steps {decode_steps} | mixed_step {mixed_step}",
          flush=True)

    # Warmup compiles every program the timed ladder will hit: the
    # saturating burst covers decode/chunked variants, then one mini-pass
    # per ladder level covers each level's batched-admission sizes (pow2
    # insert_batch programs) — without this, a first-use compile lands
    # inside a timed level and reads as a 40 s TTFT outlier.
    t0 = time.perf_counter()
    run_level_inprocess(engine, prompt_ids, concurrency=2 * MAX_SLOTS,
                        n_requests=2 * MAX_SLOTS, max_tokens=8)
    # odd budget under queue pressure: drives the budget-capped decode
    # blocks through their pow2 variants (1/2/4) so none first-compiles
    # inside a timed level
    run_level_inprocess(engine, prompt_ids, concurrency=2 * MAX_SLOTS,
                        n_requests=2 * MAX_SLOTS, max_tokens=7)
    for conc in LADDER:
        # mirror the timed levels' request count: the burst pattern
        # decides which batched-admission (insert_batch) program sizes
        # get compiled, and a size first seen inside a timed level once
        # read as a 20 s TTFT outlier at conc 16
        run_level_inprocess(engine, prompt_ids, concurrency=conc,
                            n_requests=max(32, 2 * conc), max_tokens=4)
    warmup_s = time.perf_counter() - t0
    print(f"warmup/compile {warmup_s:.0f}s | {_hbm_stats()}", flush=True)

    # Cold-vs-warm prefix TTFT pair (long mode): the reference platform's
    # headline is warm TTFT 50-200 ms vs cold 800-1500 ms via vLLM APC /
    # LMCache (Inference_Platfrom/README.md:1336-1341). Attach the L1
    # prefix cache, submit one long prompt cold (full chunked prefill),
    # then the SAME prompt again (full-prefix hit -> rows insert, no
    # prefill), and record both TTFTs. A throwaway pair runs first so
    # the insert/store programs compile outside the measured pair; the
    # cache detaches afterwards so ladder rows stay prefix-cold.
    cold_warm = None
    if LONG_MODE:
        from llm_in_practise_tpu.serve.engine import SamplingParams
        from llm_in_practise_tpu.serve.prefix_cache import PrefixCache

        engine.prefix_cache = PrefixCache(max_tokens=32768)

        def _ttft(ids):
            from llm_in_practise_tpu.obs.trace import new_context

            req = engine.submit(
                ids, SamplingParams(greedy=True, max_tokens=4),
                trace=new_context())
            req.result()
            if req.ttft_s is None:  # shed/failed probe: fail loudly now,
                raise RuntimeError(  # not as a TypeError after the run
                    f"cold/warm probe got no first token "
                    f"(finish_reason={req.finish_reason!r})")
            return req.ttft_s * 1000.0

        import numpy as _np
        _cw = _np.random.default_rng(7)
        warm_ids = [list(map(int, _cw.integers(0, 151936, PROMPT_LEN)))
                    for _ in range(2)]
        _ttft(warm_ids[0]); _ttft(warm_ids[0])      # compile insert/store
        cold_ms = _ttft(warm_ids[1])
        warm_ms = _ttft(warm_ids[1])
        engine.prefix_cache = None                  # ladder stays cold
        cold_warm = {
            "prompt_tokens": PROMPT_LEN,
            "cold_ttft_ms": round(cold_ms, 1),
            "warm_prefix_hit_ttft_ms": round(warm_ms, 1),
            "speedup": round(cold_ms / max(warm_ms, 1e-9), 1),
            "reference": "Inference_Platfrom/README.md:1336-1341 "
                         "(cold 800-1500 ms -> warm 50-200 ms)",
        }
        print(f"cold/warm prefix TTFT: {cold_ms:.0f} -> {warm_ms:.0f} ms",
              flush=True)

    engine.queue_timeout_s = QUEUE_TIMEOUT_S or None
    engine.max_queue = MAX_QUEUE
    # SLO goodput from here on (post-warmup/post-cold-warm probes): the
    # artifact's device-plane block splits served tokens by SLO outcome
    engine.stats.goodput.configure(SLA["ttft_p99_ms"] / 1e3,
                                   SLA["tpot_p99_ms"] / 1e3)
    levels = []
    for conc in LADDER:
        r = run_level_inprocess(engine, prompt_ids, concurrency=conc,
                                n_requests=max(32, 2 * conc),
                                max_tokens=MAX_TOKENS)
        # honesty split under admission control: served_sla_ok says the
        # SERVED subset met the gates (the bounded-degradation story);
        # sla_ok additionally requires ~everything to have been served —
        # an over-capacity level must not "pass" by shedding its tail,
        # and a fully-shed level (empty percentiles = 0.0) must not pass
        # vacuously.
        served = r["success_rate"] > 0
        r["served_sla_ok"] = bool(
            served and r["ttft_p99_ms"] < SLA["ttft_p99_ms"]
            and r["tpot_p99_ms"] < SLA["tpot_p99_ms"])
        r["sla_ok"] = bool(r["served_sla_ok"]
                           and r["success_rate"] >= 0.99)
        levels.append(r)
        print(json.dumps(r), flush=True)

    from bench import obs_snapshot

    engine.stop()
    artifact = {
        # trace-ring summary (per-phase span counts/seconds) + device
        # plane (per-phase MFU / HBM-bandwidth utilization, peak HBM,
        # compile seconds, goodput): the breakdown that turns a
        # regressed row into a diagnosis
        "observability": obs_snapshot(engine=engine),
        "device": jax.devices()[0].device_kind,
        "model": f"Qwen3-arch d{cfg.hidden_size}/L{n_layer}, vocab "
                 f"151936, distinct-per-layer {FMT.upper()}, "
                 + {"int8": "W8A16 XLA-fused dequant matmuls (measured "
                            "faster than the Pallas int8 kernel — "
                            "INT8_TILE_PROBE.json)",
                    "mixed": "int8 MLP (XLA dequant matmul) + NF4 "
                             "attention (fused W4A16 Pallas kernels) — "
                             "peft/qlora.py::mixed_serve_fmt",
                    "nf4": "fused W4A16 Pallas kernels"}[FMT],
        "layout": "scan (stacked params+KV, O(1)-depth compile)"
                  if use_scan else "unrolled",
        "weight_fmt": FMT,
        "packed_base_bytes": int(packed_bytes),
        "approx_params": int(n_params),
        "quantize_s": round(quant_s, 1),
        "warmup_compile_s": round(warmup_s, 1),
        "engine": {"max_slots": MAX_SLOTS, "cache_len": CACHE_LEN,
                   "chunked_prefill": CHUNK, "decode_steps": decode_steps,
                   "mixed_step": mixed_step,
                   "mixed_blocks": engine.mixed_blocks,
                   "dispatches_per_step":
                       round(engine.dispatch_meter.mean_per_step, 3),
                   "kv_dtype": KV_DTYPE,
                   "admission": {
                       "queue_timeout_s": QUEUE_TIMEOUT_S or None,
                       "max_queue": MAX_QUEUE,
                       "policy": "requests waiting past queue_timeout_s "
                                 "shed with finish_reason=queue_full "
                                 "(HTTP 429); SLA percentiles cover "
                                 "served requests, failures.queue_full "
                                 "counts the shed fraction"},
                   "path": "serve/quantized.py "
                           + {"int8": "int8 -> XLA dequant matmul (the "
                                      "measured-faster path)",
                              "mixed": "per-leaf dispatch: Int8 -> XLA "
                                       "dequant, NF4 -> Pallas kernel",
                              "nf4": "fused NF4 Pallas kernels"}[FMT]},
        "prompt_len": PROMPT_LEN or "short text prompts",
        "max_tokens": MAX_TOKENS,
        "sla": SLA,
        **({"cold_warm_prefix_ttft": cold_warm} if cold_warm else {}),
        "levels_inprocess": levels,
        **_hbm_stats(),
        "reference_baseline": (
            "BASELINE.md ladder (RTX 3090, Qwen3-8B W16, vLLM): 368.3 "
            f"tok/s @ conc 8 — this run is a "
            f"{n_params/1e9:.1f}B-class {FMT.upper()} model on one "
            "16 GB v5e; W4 decode at this scale is dequant-bound "
            "(DECODE_AB_8B.json; int8 exists to remove that tax), so "
            "compare shapes and SLA behavior, not absolutes"),
    }
    with open(OUT, "w") as f:
        json.dump(artifact, f, indent=2)
    print("wrote", OUT)


if __name__ == "__main__":
    main()
