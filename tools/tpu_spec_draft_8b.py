"""Draft-MODEL speculative decoding at 8B scale (VERDICT r4 #10).

`SPEC_DECODE_8B.json` measured the ngram (prompt-lookup) speculator:
1.57x, 47% acceptance on self-similar text — and ~0 acceptance on text
with no repeats, because an n-gram matcher has nothing to match. A
draft MODEL proposes from actual next-token prediction instead. With no
trained 8B checkpoint in-tree, the draft here is **self-speculative**:
the target's own first ``DRAFT_LAYERS`` layers, sliced from the SAME
stacked int8 tree (zero extra quantize; +8/36 of the tree in HBM) with
the shared embedding/head — the LayerSkip / Draft&Verify early-exit
family, which is also the memory-right choice on one chip.

Honest caveat, stated in the artifact too: the target's weights are
random-init (no trained 8B exists here), so ACCEPTANCE numbers
characterize the random-weight regime, not language; the engine
mechanics (draft-roll cost, verify cost, lossless commit) and the
throughput accounting are what this artifact certifies at scale. The
trained-pair behavior is pinned on CPU by
``tests/test_draft_model_spec.py`` (>50% acceptance, exact greedy).

Writes ``SPEC_DRAFT_8B.json``.
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
import numpy as np

OUT = os.path.join(REPO, "SPEC_DRAFT_8B.json")
NEW_TOKENS = 48
CACHE_LEN = 512
DRAFT_LAYERS = int(os.environ.get("SPEC_DRAFT_LAYERS", "8"))


def main() -> None:
    from llm_in_practise_tpu.core.mesh import require_tpu

    require_tpu()
    from llm_in_practise_tpu.core.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    from bench import G8B, _distinct_base_stacked
    from llm_in_practise_tpu.models.qwen3 import Qwen3, Qwen3Config
    from llm_in_practise_tpu.serve.engine import (
        InferenceEngine, SamplingParams,
    )
    from llm_in_practise_tpu.serve.quantized import QuantizedModel

    cfg = Qwen3Config(
        vocab_size=151936, max_seq_len=CACHE_LEN, rope_theta=1e6,
        tie_word_embeddings=True, remat=False, compute_dtype="bfloat16",
        scan_layers=True, **G8B, n_layer=36,
    )
    print("quantizing int8...", flush=True)
    qparams, q_sec = _distinct_base_stacked(cfg, Qwen3, fmt="int8")
    qmodel = QuantizedModel(Qwen3(cfg))

    # self-speculative draft: first DRAFT_LAYERS blocks of the SAME
    # tree (leading layer axis slice — Int8Tensor components slice
    # through the pytree), shared stem/head
    blocks = jax.tree.map(lambda x: x[:DRAFT_LAYERS], qparams["blocks"])
    draft_params = {**{k: v for k, v in qparams.items() if k != "blocks"},
                    "blocks": blocks}
    draft_model = QuantizedModel(Qwen3(cfg.replace(n_layer=DRAFT_LAYERS)))

    rng = np.random.default_rng(0)
    rep = [list(map(int, rng.integers(0, 151936, 6))) * 4
           for _ in range(2)]                      # ngram-friendly
    rand = [list(map(int, rng.integers(0, 151936, 24)))
            for _ in range(2)]                     # no repeats at all
    prompts = rep + rand
    sp = SamplingParams(greedy=True, max_tokens=NEW_TOKENS)

    def run(label, **kw):
        eng = InferenceEngine(qmodel, qparams, max_slots=1,
                              cache_len=CACHE_LEN,
                              cache_dtype=jnp.bfloat16, **kw)
        eng.generate(prompts[0], SamplingParams(greedy=True, max_tokens=4))
        t0 = time.perf_counter()
        outs = [eng.generate(p, sp) for p in prompts]
        dt = time.perf_counter() - t0
        n_tok = sum(len(o) for o in outs)
        acc = (eng.spec_accepted / eng.spec_proposed
               if eng.spec_proposed else None)
        print(f"{label}: {n_tok/dt:.2f} tok/s"
              + (f" | acceptance {acc:.3f}" if acc is not None else ""),
              flush=True)
        return outs, n_tok / dt, acc

    plain_out, plain_tps, _ = run("plain")
    ngram_out, ngram_tps, ngram_acc = run("ngram_spec", speculative_k=4)
    draft_out, draft_tps, draft_acc = run(
        "draft_model_spec", speculative_k=4,
        draft_model=draft_model, draft_params=draft_params)

    def agree(a, b):
        return float(np.mean([
            np.mean([x == y for x, y in zip(p, q)])
            for p, q in zip(a, b)]))

    result = {
        "model": "Qwen3-arch 7.57B int8 (d4096/L36, vocab 151936), "
                 "random-init weights (see caveat)",
        "draft": f"self-speculative: target's first {DRAFT_LAYERS} "
                 "layers, same int8 tree sliced on the layer axis, "
                 "shared embed/head (LayerSkip/Draft&Verify family)",
        "quantize_s": round(q_sec, 1),
        "single_stream": True,
        "new_tokens_per_prompt": NEW_TOKENS,
        "prompts": "2 ngram-friendly (6-token pattern x4) + 2 pure-random",
        "plain_tok_s": round(plain_tps, 2),
        "ngram": {"tok_s": round(ngram_tps, 2),
                  "speedup": round(ngram_tps / plain_tps, 2),
                  "acceptance": round(ngram_acc, 3)
                  if ngram_acc is not None else None},
        "draft_model": {"tok_s": round(draft_tps, 2),
                        "speedup": round(draft_tps / plain_tps, 2),
                        "acceptance": round(draft_acc, 3)
                        if draft_acc is not None else None},
        "positional_agreement_vs_plain": {
            "ngram": round(agree(plain_out, ngram_out), 3),
            "draft_model": round(agree(plain_out, draft_out), 3)},
        "caveat": (
            "random-init target: acceptance characterizes the random-"
            "weight regime (layers near-identity at init can make the "
            "truncated draft AGREE unusually often), not language; the "
            "trained-pair acceptance/losslessness contract is the CPU "
            "suite's tests/test_draft_model_spec.py"),
        "dispatches_per_round": (
            "a draft round costs 1 catch-up+roll dispatch (small "
            "model) + 1 wide verify (full model)"),
    }
    print(json.dumps(result, indent=2), flush=True)
    with open(OUT, "w") as f:
        json.dump(result, f, indent=2)
    print("wrote", OUT)


if __name__ == "__main__":
    main()
