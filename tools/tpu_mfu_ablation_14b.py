"""MFU attribution at the 14B geometry (VERDICT r4 #4).

Round 3's ablation (`tpu_mfu_ablation.py`) exonerated every suspect at
d2048 on the MATERIALIZED-dequant path and stopped; the bench's 14B
rung runs a different machine — the training scan with inline dequant
(`bench._fused_scale_proof`) — whose remat/scan/CE/dequant tradeoffs
were never measured at d5120/L40. This tool ablates THE step the bench
ships, one knob at a time, all variants sharing one resident stacked
NF4 base (built once, 33 s):

- ``full``          — the shipped step (remat, scan_unroll=1, fused CE
                      chunk 2048 / vocab_chunk 8192, XLA inline dequant)
- ``fwd_only``      — loss value only, no grad: the executed-efficiency
                      ceiling split (Finding 7's 44%-forward method)
- ``ce_chunk_8192`` / ``ce_novchunk`` — coarser CE chunking
- ``scan_unroll_2`` — two blocks per scan iteration
- ``no_remat``      — gradient checkpointing off (if it fits)
- ``kernels_on``    — fused NF4 Pallas matmuls instead of XLA dequant
                      (Finding 4 measured XLA +77% at training scale —
                      re-checked at 14B)
- ``batch_4``       — half batch (dequant amortization check)

Writes ``MFU_ABLATION_14B.json`` (the r3 artifact stays — different
machine, both cited by docs/perf.md).
"""

from __future__ import annotations

import json
import os
import sys
import time
from functools import partial

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp
import numpy as np
import optax

SEQ = 1024
BATCH = 8
VOCAB = 151936


def main() -> None:
    from llm_in_practise_tpu.core.mesh import require_tpu

    require_tpu()
    from llm_in_practise_tpu.core.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    import bench
    from bench import G14B, _distinct_base_stacked
    # the ONE FLOP/peak model — imported from its home (obs/cost.py),
    # not re-derived: the r4 era's hand-copied variant of the per-token
    # formula is exactly the drift this import kills
    from llm_in_practise_tpu.obs.cost import (
        chip_peak,
        flops_per_token,
        hbm_stats as _hbm_stats,
        matmul_param_count,
    )
    from llm_in_practise_tpu.models.qwen3 import Qwen3, Qwen3Config
    from llm_in_practise_tpu.peft import lora as lora_lib
    from llm_in_practise_tpu.peft.fused import make_fused_qlora_loss_fn_args
    from llm_in_practise_tpu.train.losses import fused_linear_cross_entropy

    kind, peak = chip_peak()
    print(f"device {kind}", flush=True)

    base_cfg = Qwen3Config(
        vocab_size=VOCAB, max_seq_len=SEQ, rope_theta=1e6,
        tie_word_embeddings=True, remat=True, compute_dtype="bfloat16",
        scan_layers=True, n_layer=40, **G14B)

    print("building stacked NF4 base (shared across variants)...",
          flush=True)
    qparams, quant_s = _distinct_base_stacked(base_cfg, Qwen3)
    print(f"base in {quant_s:.0f}s | {_hbm_stats()}", flush=True)

    abstract = jax.eval_shape(
        lambda r: Qwen3(base_cfg).init(
            r, jnp.ones((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    m = matmul_param_count(abstract, tied_head=True)
    f_tok = flops_per_token(m, base_cfg.n_layer, SEQ,
                            base_cfg.n_head * base_cfg.head_dim,
                            train_full=False)
    lcfg = lora_lib.LoRAConfig(r=8, alpha=16.0,
                               target_patterns=("q_proj", "v_proj"))

    rngnp = np.random.default_rng(0)

    def run_variant(name, *, cfg=None, ce_chunk=2048, ce_vchunk=8192,
                    use_kernels=False, batch=BATCH, fwd_only=False):
        cfg = cfg or base_cfg
        t0 = time.perf_counter()
        try:
            model = Qwen3(cfg)
            lora = jax.jit(lambda: lora_lib.init_lora(
                abstract, lcfg, jax.random.PRNGKey(1)))()

            def base_loss(apply_out, qp, b, rng):
                x, y = b
                hidden = apply_out(x, deterministic=True,
                                   return_hidden=True)
                loss, _ = fused_linear_cross_entropy(
                    hidden, qp["tok_embed"]["embedding"], y,
                    transpose_weight=True, chunk=ce_chunk,
                    vocab_chunk=ce_vchunk)
                return loss

            loss_fn = make_fused_qlora_loss_fn_args(
                model, lcfg, base_loss, use_kernels=use_kernels)
            tx = optax.adamw(1e-4)
            opt = tx.init(lora)

            if fwd_only:
                @jax.jit
                def step(lora, opt, qp, b, rng):
                    return lora, opt, loss_fn(lora, qp, b, rng)
            else:
                @partial(jax.jit, donate_argnums=(0, 1))
                def step(lora, opt, qp, b, rng):
                    loss, g = jax.value_and_grad(loss_fn)(
                        lora, qp, b, rng)
                    up, opt = tx.update(g, opt, lora)
                    return optax.apply_updates(lora, up), opt, loss

            x = jnp.asarray(rngnp.integers(0, VOCAB, (batch, SEQ)),
                            jnp.int32)
            b = (x, jnp.roll(x, -1, axis=1))
            key = jax.random.PRNGKey(2)
            state = {"l": lora, "o": opt}

            def one():
                state["l"], state["o"], loss = step(
                    state["l"], state["o"], qparams, b, key)
                return loss

            jax.block_until_ready(one())
            jax.block_until_ready(one())
            dt = bench.timed_window(one, n_iters=4, n_windows=2)
            tokens = batch * SEQ
            row = {
                "variant": name,
                "step_ms": round(dt * 1e3, 1),
                "tok_s": round(tokens / dt, 1),
                "mfu": round(f_tok * tokens / dt / peak, 4),
                "build_s": round(time.perf_counter() - t0, 1),
            }
        except Exception as e:
            row = {"variant": name,
                   "error": f"{type(e).__name__}: {str(e)[:300]}"}
        print(json.dumps(row), flush=True)
        return row

    rows = [
        run_variant("full"),
        run_variant("fwd_only", fwd_only=True),
        run_variant("ce_chunk_8192", ce_chunk=8192),
        run_variant("ce_novchunk", ce_vchunk=None),
        run_variant("scan_unroll_2",
                    cfg=base_cfg.replace(scan_unroll=2)),
        run_variant("no_remat", cfg=base_cfg.replace(remat=False)),
        run_variant("kernels_on", use_kernels=True),
        run_variant("batch_4", batch=4),
    ]
    full = next((r for r in rows
                 if r["variant"] == "full" and "step_ms" in r), None)
    if full:
        for r in rows:
            if "step_ms" in r:
                r["delta_ms_vs_full"] = round(
                    r["step_ms"] - full["step_ms"], 1)

    out = os.path.join(REPO, "MFU_ABLATION_14B.json")
    with open(out, "w") as f:
        json.dump({
            "device": kind, "peak_bf16_flops": peak, "batch": BATCH,
            "seq": SEQ,
            "shape": dict(n_layer=40, vocab=VOCAB, **G14B),
            "mode": "train_step_scan_inline_dequant (the shipped 14B "
                    "bench step); one resident NF4 base shared by all "
                    "variants",
            "flop_model": "useful FLOPs only (2x fwd for the frozen "
                          "base, LoRA excluded) — same convention as "
                          "BENCH_r*.json mfu",
            "variants": rows,
        }, f, indent=2)
    print("wrote", out)


if __name__ == "__main__":
    main()
