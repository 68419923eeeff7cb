"""On-TPU speculative-decoding acceptance check (VERDICT weak #5).

The CPU f32 suite asserts spec==greedy exactly; on TPU bf16, reduction
order can flip near-tie argmaxes, so exactness is checked *statistically*
here, on the real chip, together with the acceptance rate and the
measured wall-clock speedup — the three numbers that back the engine's
"lossless ~2-3x" speculative-decoding claim (vLLM-parity contract,
reference serves via vLLM whose spec decode makes the same promise).

Run on the TPU host (default env): ``python tools/tpu_spec_decode_check.py``
Writes ``SPEC_DECODE_TPU.json`` at the repo root.

Pass criteria (asserted):
- every spec-vs-plain divergence is a genuine bf16 near-tie: at each
  prompt's FIRST divergence (later positions differ only because the
  prefix already did — cascade, not error), the two chosen tokens'
  logits under the shared prefix must be within a bf16-rounding-sized
  gap. A real correctness bug picks tokens with a large gap.
- acceptance rate > 30% on repetitive text (prompt-lookup drafting's
  home turf) — the regime where the speedup claim applies;
- spec decode is faster than plain decode on repetitive text.
Positional token agreement is reported as context, not gated: with
near-uniform (random-weight) logits a single tie flip rewrites the rest
of the sequence, so the positional number understates losslessness.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from llm_in_practise_tpu.models.gpt import GPT, gptlike_config
from llm_in_practise_tpu.serve.engine import InferenceEngine, SamplingParams

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "SPEC_DECODE_TPU.json")


def main() -> None:
    from llm_in_practise_tpu.core.mesh import require_tpu

    require_tpu()
    # A real-ish model: GPTLike 6L/512d bf16 (the reference's from-scratch
    # architecture), random weights — acceptance depends on output
    # self-similarity, which repetitive prompts provide regardless of
    # training state.
    cfg = gptlike_config(2048, seq_len=512, dropout=0.0,
                         compute_dtype="bfloat16")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]

    rng = np.random.default_rng(0)
    prompts = (
        [list(rng.integers(0, 2048, 24)) for _ in range(4)]        # random
        + [list(np.tile(rng.integers(0, 2048, p), 8)[:40])         # periodic
           for p in (3, 5, 7, 4)]
    )
    MAX_TOKENS = 48
    sp = SamplingParams(greedy=True, max_tokens=MAX_TOKENS)

    def run(engine, label):
        outs, t0 = [], time.perf_counter()
        for p in prompts:
            outs.append(engine.generate(p, sp))
        dt = time.perf_counter() - t0
        n_tok = sum(len(o) for o in outs)
        print(f"{label}: {n_tok} tokens in {dt:.2f}s "
              f"({n_tok/dt:.1f} tok/s)", flush=True)
        return outs, dt, n_tok

    plain = InferenceEngine(model, params, max_slots=1, cache_len=512)
    plain_outs, _, _ = run(plain, "warmup(compile) plain")
    plain_outs, plain_dt, plain_n = run(plain, "plain")

    spec = InferenceEngine(model, params, max_slots=1, cache_len=512,
                           speculative_k=4)
    spec_outs, _, _ = run(spec, "warmup(compile) spec")
    spec.spec_proposed = spec.spec_accepted = 0
    spec_outs, spec_dt, spec_n = run(spec, "spec")

    agree = sum(
        sum(a == b for a, b in zip(po, so)) for po, so in
        zip(plain_outs, spec_outs)
    )
    total = sum(min(len(a), len(b)) for a, b in zip(plain_outs, spec_outs))
    agreement = agree / max(total, 1)
    acceptance = spec.spec_accepted / max(spec.spec_proposed, 1)

    # near-tie audit at each first divergence: one dense forward over the
    # shared prefix; the two candidates' logits must be bf16-tie close
    fwd = jax.jit(lambda p, x: model.apply({"params": p}, x,
                                           deterministic=True))
    gaps = []
    for prompt, po, so in zip(prompts, plain_outs, spec_outs):
        div = next((i for i, (a, b) in enumerate(zip(po, so)) if a != b),
                   None)
        if div is None:
            continue
        prefix = jnp.asarray([prompt + po[:div]], jnp.int32)
        logits = np.asarray(fwd(params, prefix))[0, -1].astype(np.float64)
        scale = float(np.abs(logits).max())
        gap = abs(float(logits[po[div]]) - float(logits[so[div]]))
        gaps.append({"pos": div, "gap": round(gap, 5),
                     "rel": round(gap / max(scale, 1e-9), 6)})
    max_rel_gap = max((g["rel"] for g in gaps), default=0.0)
    speedup = (plain_n / plain_dt) / (spec_n / spec_dt) if spec_dt else 0.0
    speedup = 1.0 / speedup if speedup else 0.0  # spec tok/s over plain

    result = {
        "device": jax.devices()[0].device_kind,
        "model": "GPTLike 6L/512d bf16 (random weights)",
        "prompts": len(prompts),
        "max_tokens": MAX_TOKENS,
        "token_agreement_vs_onetoken_greedy": round(agreement, 4),
        "first_divergence_near_tie_audit": gaps,
        "max_divergence_rel_logit_gap": round(max_rel_gap, 6),
        "draft_acceptance_rate": round(acceptance, 4),
        "drafts_proposed": int(spec.spec_proposed),
        "drafts_accepted": int(spec.spec_accepted),
        "plain_tok_s": round(plain_n / plain_dt, 1),
        "spec_tok_s": round(spec_n / spec_dt, 1),
        "spec_speedup": round(speedup, 3),
    }
    print(json.dumps(result, indent=2))
    with open(OUT, "w") as f:
        json.dump(result, f, indent=2)

    # bf16 keeps ~8 mantissa bits, and the logit is a 512-term dot of
    # bf16-rounded inputs — input rounding amplifies past a single ulp
    # (measured gaps here run 0.1-1% of scale). 2% of scale bounds that
    # noise while still catching a wrong-token bug, which on any confident
    # model shows an order-of-magnitude larger gap (and the CPU f32 suite
    # pins exact equality for logic errors).
    assert max_rel_gap < 0.02, (
        f"divergence with relative logit gap {max_rel_gap:.4f} — beyond "
        f"bf16 rounding noise; audit: {gaps}")
    assert acceptance > 0.30, (
        f"acceptance {acceptance:.1%} too low on repetitive prompts")
    assert result["spec_tok_s"] > result["plain_tok_s"], (
        "speculative decode must beat plain decode on repetitive text")
    print("SPEC DECODE TPU CHECK OK ->", OUT)


if __name__ == "__main__":
    main()
