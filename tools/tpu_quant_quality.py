"""Serving-format quantization quality at real scale, on the chip.

VERDICT r4 #9: the PPL acceptance gate (`quant/ppl.py`, reference
semantics 8.19 -> <9.0) and the golden e2e tests prove format quality at
fixture scale only; nothing measured the SERVING formats against bf16
on a multi-billion-param model on the TPU. This probe does, on the
reference's own eval model size — Qwen3-4B geometry, the model
`Quantization/LLM-Compressor/GPTQ/eval_qwen3_4b_gptq.py` evaluates —
because its bf16 tree (~8 GiB) genuinely fits the 16 GiB chip next to
each packed tree, so the reference arm is exact, not estimated.

Method: build the distinct-per-layer bf16 tree (seeded — every rebuild
is bit-identical), record its logits over N positions, then for each
serving format (int8, nf4, mixed) rebuild the SAME weights, quantize,
run the SAME forward through the serving dispatch path
(`fused_quant_apply`, kernels on), and compare per-position:

- top-1 agreement (the greedy-decode observable),
- mean / p99 |Δlogit| over the full 151936-vocab rows,
- mean KL(bf16 || quant).

Inputs are uniform random token ids (no held-out corpus exists at this
scale in-tree) — that measures FORMAT error propagation through real
weights, the same role the PPL gate's fixture corpus plays; agreement
numbers are comparable across formats, not across papers.

Writes ``QUANT_QUALITY.json``. Runtime: ~4 builds of a 4B tree +
4 forwards; the compile cache keeps reruns cheap.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp
import numpy as np

OUT = os.path.join(REPO, "QUANT_QUALITY.json")
BATCH, SEQ = 2, 512          # 1024 scored positions
FORMATS = ("int8", "nf4", "mixed")

# the literal Qwen3-4B geometry (reference eval model)
G4B = dict(hidden_size=2560, intermediate_size=9728, n_head=32,
           n_kv_head=8, head_dim=128)


def main() -> None:
    from llm_in_practise_tpu.core.mesh import require_tpu

    require_tpu()
    from llm_in_practise_tpu.core.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    from bench import _distinct_base_stacked, _hbm_stats
    from llm_in_practise_tpu.models.qwen3 import Qwen3, Qwen3Config
    from llm_in_practise_tpu.peft.fused import fused_quant_apply

    cfg = Qwen3Config(
        vocab_size=151936, max_seq_len=SEQ, rope_theta=1e6,
        tie_word_embeddings=True, remat=False, compute_dtype="bfloat16",
        n_layer=36, **G4B)
    serve_cfg = cfg.replace(scan_layers=True)
    model = Qwen3(serve_cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (BATCH, SEQ)),
                         jnp.int32)

    @jax.jit
    def fwd_plain(params, ids):
        return model.apply({"params": params}, ids, deterministic=True)

    @jax.jit
    def fwd_quant(qtree, ids):
        # jitted with the packed tree as an ARGUMENT (Finding 6: closure
        # constants are fatal through the remote compile path) — one
        # program per format, not per-op eager dispatch
        return fused_quant_apply(model, qtree, ids, deterministic=True,
                                 use_kernels=True,
                                 compute_dtype=jnp.bfloat16)

    # metrics against the resident reference logits, all on device —
    # only scalars come back to the host
    @jax.jit
    def metrics(ref, got):
        ref = ref.reshape(-1, ref.shape[-1]).astype(jnp.float32)
        got = got.reshape(-1, got.shape[-1]).astype(jnp.float32)
        top1 = jnp.mean(
            (jnp.argmax(ref, -1) == jnp.argmax(got, -1)).astype(jnp.float32))
        ad = jnp.abs(ref - got)
        logp_ref = jax.nn.log_softmax(ref)
        logp_got = jax.nn.log_softmax(got)
        kl = jnp.sum(jnp.exp(logp_ref) * (logp_ref - logp_got), -1)
        return {
            "top1_agreement": top1,
            "mean_abs_dlogit": jnp.mean(ad),
            "p99_abs_dlogit": jnp.quantile(
                jnp.max(ad, axis=-1), 0.99),
            "mean_kl": jnp.mean(kl),
        }

    report: dict = {
        "model": f"Qwen3-4B geometry (d{cfg.hidden_size}/L{cfg.n_layer}, "
                 f"GQA {cfg.n_head}:{cfg.n_kv_head}, vocab "
                 f"{cfg.vocab_size}) — the reference's GPTQ eval model "
                 "(eval_qwen3_4b_gptq.py)",
        "positions": BATCH * SEQ,
        "inputs": "uniform random token ids, seed 0 (format-error "
                  "measure; see module docstring)",
        "path": "serving dispatch (fused_quant_apply, kernels on: NF4 "
                "Pallas / int8 XLA)",
        "device": jax.devices()[0].device_kind,
        "formats": {},
    }

    print("building bf16 reference arm...", flush=True)
    t0 = time.perf_counter()
    params, secs = _distinct_base_stacked(cfg, Qwen3, fmt="bf16")
    ref_logits = fwd_plain(params, tokens)
    ref_logits = jax.block_until_ready(ref_logits).astype(jnp.bfloat16)
    print(f"bf16 arm in {time.perf_counter()-t0:.0f}s | {_hbm_stats()}",
          flush=True)
    del params
    gc.collect()

    for fmt in FORMATS:
        t0 = time.perf_counter()
        qtree, qsecs = _distinct_base_stacked(cfg, Qwen3, fmt=fmt)
        got = fwd_quant(qtree, tokens)
        m = {k: float(v) for k, v in
             jax.device_get(metrics(ref_logits, got)).items()}
        m["build_and_forward_s"] = round(time.perf_counter() - t0, 1)
        report["formats"][fmt] = m
        print(fmt, json.dumps(m), flush=True)
        del qtree, got
        gc.collect()

    with open(OUT, "w") as f:
        json.dump(report, f, indent=2)
    print("wrote", OUT)


if __name__ == "__main__":
    main()
