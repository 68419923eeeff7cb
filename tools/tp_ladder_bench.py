"""Tensor-parallel ladder — the ISSUE 10 / ROADMAP item 1 acceptance artifact.

Three legs, tp ∈ {1, 2, 4}, on the SAME trained gptlike pair the spec
ladder uses (``tools/spec_ladder_bench._train_gpt`` — a memorized
corpus so ngram speculation has real acceptance), each leg the full
decode-replica composition: paged KV pool sharded over the mesh,
ngram speculation, greedy traffic.

What the artifact pins per leg:

- **golden parity** (the gate): every leg's outputs are byte-identical
  to the smallest-tp leg that ran (tp=1 in the default config) —
  sharding is placement, never semantics; fewer than 2 legs fails the
  gate rather than passing vacuously;
- per-leg tok/s at each concurrency (post-warmup counters only);
- the collective plane: ``llm_collective_{bytes,seconds}_total`` after
  the timed rows (the analytic per-chip ICI attribution), plus
  dispatches/step (the 1-dispatch invariant under TP);
- a full ``/metrics`` snapshot per leg (the acceptance criterion).

**CPU caveat, stated up front:** the tp legs run on VIRTUAL CPU
devices (``--xla_force_host_platform_device_count=8``) sharing the
same host cores — tp>1 CANNOT be faster here and usually reads slower
(collectives are pure overhead when there is no extra silicon). This
artifact is the CORRECTNESS-and-counters half; the speed half is the
real-chip ``SERVE_TP=N tools/tpu_serve_bench.py`` leg, where each
shard gets its own HBM controller (docs/serving-tp.md states the
expected bandwidth multiplication).

Run: ``python tools/tp_ladder_bench.py``. Writes
``BENCH_TP_LADDER_r08.json`` at the repo root. Env knobs:
``TP_BENCH_TRAIN_STEPS``, ``TP_BENCH_REQUESTS``, ``TP_BENCH_LEGS``
(default "1,2,4"). The CLI runs an int8-quantized-collective sub-leg at the
largest tp by DEFAULT (it is part of the published artifact);
``TP_BENCH_QUANTIZED_COLLECTIVES=0`` drops it. (Library callers —
the tier-1 smoke — get ``quantized_leg=False`` unless they ask.)
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# the tp legs need virtual devices BEFORE jax initializes — keep the
# recipe self-contained so `python tools/tp_ladder_bench.py` works on a
# bare CPU box (under pytest the conftest already set it)
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp

OUT = os.environ.get("TP_LADDER_OUT",
                     os.path.join(REPO, "BENCH_TP_LADDER_r08.json"))


class _Tok:
    def encode(self, t):
        return list(t.encode()[:32])

    def decode(self, ids):
        return bytes(int(i) % 256 for i in ids).decode("utf-8", "replace")


def run_ladder(*, train_steps: int = 300, n_requests: int = 24,
               max_tokens: int = 48, spec_k: int = 4, legs=(1, 2, 4),
               concurrencies=(1, 4), quantized_leg: bool = False,
               out_path: str | None = None) -> dict:
    """Build the trained gptlike target, run one engine per tp leg,
    return (and optionally write) the artifact. The tier-1 smoke calls
    this with reduced sizes."""
    from deploy.benchmark.bench_serve import run_level_inprocess
    from llm_in_practise_tpu.parallel import strategy as S
    from llm_in_practise_tpu.serve.api import OpenAIServer
    from llm_in_practise_tpu.serve.engine import (
        InferenceEngine,
        shard_params_for_serving,
    )
    from tools.spec_ladder_bench import _prompts, _train_gpt, CACHE_LEN

    n_dev = len(jax.devices())
    legs = tuple(tp for tp in legs if tp <= n_dev)
    t0 = time.perf_counter()
    model, params = _train_gpt(3, 4, 64, train_steps, seed=0)
    train_s = time.perf_counter() - t0
    prompt_ids = _prompts()

    base_kw = dict(max_slots=4, cache_len=CACHE_LEN,
                   cache_dtype=jnp.float32, chunked_prefill=64,
                   kv_layout="paged",
                   speculative_k=spec_k)

    def build(tp: int, quantized_collectives: bool = False):
        if tp <= 1:
            return InferenceEngine(model, params, **base_kw)
        strat = S.tensor_parallel(model=tp, data=1)
        mesh = strat.build_mesh(jax.devices()[:tp])
        sharded = shard_params_for_serving(params, strat, mesh)
        m = model
        if quantized_collectives:
            from llm_in_practise_tpu.parallel.collectives import (
                maybe_quantized_collectives,
            )

            m, _ = maybe_quantized_collectives(model, mesh, sharded)
        return InferenceEngine(m, sharded, mesh=mesh, **base_kw)

    leg_specs = [(f"tp{tp}", tp, False) for tp in legs]
    if quantized_leg and legs and legs[-1] > 1:
        leg_specs.append((f"tp{legs[-1]}_int8_collectives", legs[-1],
                          True))
    leg_rows = {}
    golden = {}
    for name, tp, qc in leg_specs:
        eng = build(tp, qc)
        eng.start()
        # warmup compiles every view-width/block/verify variant before
        # anything is timed; post-warmup counters only (the spec-ladder
        # convention)
        run_level_inprocess(eng, prompt_ids,
                            concurrency=max(concurrencies),
                            n_requests=max(8, 2 * max(concurrencies)),
                            max_tokens=max_tokens)
        w_bytes = eng.collective_bytes_total
        w_secs = eng.collective_seconds_total
        levels = []
        for conc in concurrencies:
            row = run_level_inprocess(eng, prompt_ids, concurrency=conc,
                                      n_requests=max(n_requests, 2 * conc),
                                      max_tokens=max_tokens)
            levels.append(row)
            print(json.dumps({"leg": name, "concurrency": conc,
                              "output_tps": row["output_tps"],
                              "tpot_p50_ms": row["tpot_p50_ms"]}),
                  flush=True)
        # snapshot the collective counters BEFORE the golden probe so
        # the published per-leg numbers cover exactly the timed rows
        t_bytes = eng.collective_bytes_total
        t_secs = eng.collective_seconds_total
        # golden-parity probe AFTER the timed rows (its tokens are the
        # gate, its latency irrelevant)
        from llm_in_practise_tpu.serve.engine import SamplingParams

        probe = eng.submit(prompt_ids[0],
                           SamplingParams(greedy=True, max_tokens=32))
        golden[name] = probe.result()
        srv = OpenAIServer(eng, _Tok(), model_name=name)
        metrics = srv.metrics_text()
        eng.stop()
        leg_rows[name] = {
            "tp": tp,
            "quantized_collectives": qc and eng.tp_quantized_collectives,
            "levels": levels,
            "dispatches_per_step":
                round(eng.dispatch_meter.mean_per_step, 3),
            "collective_bytes_timed": round(t_bytes - w_bytes, 1),
            "collective_seconds_timed": round(t_secs - w_secs, 9),
            "spec_rounds": eng.spec_rounds,
            "device_plane": eng.dispatch_meter.phase_snapshot(),
            "metrics_snapshot": metrics,
        }
    # the gate is never vacuous: fewer than 2 legs (a filtered
    # TP_BENCH_LEGS on a small box) means no parity CLAIM is possible,
    # so the artifact says False and main() exits 1 rather than
    # rubber-stamping an empty comparison. The anchor is the FIRST
    # (smallest-tp) leg that actually ran.
    parity = (len(golden) >= 2
              and all(v == golden[leg_specs[0][0]]
                      for v in golden.values()))
    artifact = {
        "bench": "tp_ladder",
        "model": f"GPT 3L/64d trained {train_steps} steps on a "
                 "repeating corpus (the spec-ladder target) — ngram "
                 "speculation has real acceptance on every leg",
        "train_seconds": round(train_s, 1),
        "engine": {**{k: v for k, v in base_kw.items()
                      if k != "cache_dtype"}},
        "devices": f"{n_dev}x virtual CPU "
                   "(--xla_force_host_platform_device_count)",
        "concurrencies": list(concurrencies),
        "max_tokens": max_tokens,
        "legs": leg_rows,
        "golden_parity_across_legs": parity,
        "cpu_caveat": (
            "virtual CPU devices share the same host cores: tp>1 "
            "CANNOT be faster here — this artifact pins correctness "
            "(byte-identical outputs), the 1-dispatch invariant, and "
            "the collective counters; the real-chip speed leg is "
            "SERVE_TP=N tools/tpu_serve_bench.py (docs/serving-tp.md)"),
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"wrote {out_path}: parity={parity}, legs="
              f"{sorted(leg_rows)}", flush=True)
    return artifact


def main() -> None:
    legs = tuple(int(x) for x in os.environ.get(
        "TP_BENCH_LEGS", "1,2,4").split(","))
    artifact = run_ladder(
        train_steps=int(os.environ.get("TP_BENCH_TRAIN_STEPS", "300")),
        n_requests=int(os.environ.get("TP_BENCH_REQUESTS", "24")),
        legs=legs,
        quantized_leg=os.environ.get(
            "TP_BENCH_QUANTIZED_COLLECTIVES", "1") != "0",
        out_path=OUT,
    )
    if not artifact["golden_parity_across_legs"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
