"""MFU attribution for the QLoRA step (VERDICT r3 item 5).

Round 2's QLoRA leg plateaued at ~40% MFU. This tool attributes the
missing fraction by timing ABLATED variants of the same step — each
removes or swaps exactly one suspect — rather than eyeballing a trace:

- ``full``        — the bench step as shipped (NF4 dequant + LoRA +
                    auto-picked attention + fused tied-head CE + remat)
- ``no_nf4``      — bf16 base weights, LoRA still applied → the cost of
                    the in-step NF4 dequant
- ``attn_dense``  — force the XLA dense-softmax attention path
- ``attn_flash``  — force the Pallas FA-2 kernel
- ``no_ce``       — loss = mean(hidden^2), no vocab head → the cost of
                    the fused CE (matmul is ~2*V*D/token of the FLOP model,
                    so its *time* share should match its FLOP share if
                    it runs at par)
- ``no_remat``    — rematerialization off (if it fits) → recompute cost

Each prints tok/s + step ms + delta vs full. A final ``profile_trace``
of the full step is captured for the record. Writes MFU_ABLATION.json.

Run on the TPU host (default env): python tools/tpu_mfu_ablation.py
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
import optax

import bench
# the ONE FLOP/peak model (obs/cost.py) — bench re-exports it, but the
# tools import the source of truth directly so a bench refactor can't
# silently fork the accounting again
from llm_in_practise_tpu.obs.cost import (
    chip_peak,
    flops_per_token,
    matmul_param_count,
)
from llm_in_practise_tpu.models.qwen3 import Qwen3, Qwen3Config
from llm_in_practise_tpu.peft import lora as lora_lib
from llm_in_practise_tpu.peft.qlora import make_qlora_loss_fn_args
from llm_in_practise_tpu.train.losses import fused_linear_cross_entropy

SEQ = 1024
BATCH = 8
SHAPE = dict(hidden_size=2048, intermediate_size=6144, n_layer=28,
             n_head=16, n_kv_head=8, head_dim=128)


def build_step(*, quantized: bool, attn_impl: str = "auto",
               use_ce: bool = True, remat: bool = True):
    cfg = Qwen3Config(
        vocab_size=32768, max_seq_len=SEQ, rope_theta=1e6,
        tie_word_embeddings=True, remat=remat, compute_dtype="bfloat16",
        attn_impl=attn_impl, **SHAPE,
    )
    model = Qwen3(cfg)
    # same distinct-per-layer builder as the bench; quantize=False gives
    # the bf16 no-dequant control
    base, _ = bench._distinct_nf4_base(cfg, Qwen3, quantize=quantized)
    abstract = jax.eval_shape(
        lambda r: model.init(r, jnp.ones((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    lcfg = lora_lib.LoRAConfig(r=8, alpha=16.0,
                               target_patterns=("q_proj", "v_proj"))
    lora = jax.jit(lambda: lora_lib.init_lora(
        abstract, lcfg, jax.random.PRNGKey(1)))()

    def base_loss(p, batch, rng):
        x, y = batch
        hidden = model.apply({"params": p}, x, deterministic=True,
                             return_hidden=True)
        if not use_ce:
            return jnp.mean(hidden.astype(jnp.float32) ** 2)
        loss, _ = fused_linear_cross_entropy(
            hidden, p["tok_embed"]["embedding"], y,
            transpose_weight=True, chunk=2048)
        return loss

    loss_fn = make_qlora_loss_fn_args(lcfg, base_loss)
    tx = optax.adamw(1e-4)
    opt_state = tx.init(lora)

    @jax.jit
    def step4(lora, opt_state, qp, batch, rng):
        loss, grads = jax.value_and_grad(loss_fn)(lora, qp, batch, rng)
        updates, opt_state = tx.update(grads, opt_state, lora)
        return optax.apply_updates(lora, updates), opt_state, loss

    def qstep(lora, opt_state, batch, rng):
        return step4(lora, opt_state, base, batch, rng)

    m = matmul_param_count(abstract, tied_head=True)
    f_tok = flops_per_token(m, cfg.n_layer, SEQ,
                            cfg.n_head * cfg.head_dim,
                            train_full=False)
    return qstep, lora, opt_state, f_tok


def time_variant(name: str, peak: float, **kw) -> dict:
    t0 = time.perf_counter()
    try:
        qstep, lora, opt_state, f_tok = build_step(**kw)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.integers(0, 32768, (BATCH, SEQ)), jnp.int32)
        batch = (x, jnp.roll(x, -1, axis=1))
        key = jax.random.PRNGKey(2)
        state = {"lora": lora, "opt": opt_state}

        def one():
            state["lora"], state["opt"], loss = qstep(
                state["lora"], state["opt"], batch, key)
            return loss

        for _ in range(2):
            one()
        dt = bench.timed_window(one, n_iters=8, n_windows=2)
        tokens = BATCH * SEQ
        row = {
            "variant": name,
            "step_ms": round(dt * 1e3, 1),
            "tok_s": round(tokens / dt, 1),
            "mfu_vs_full_flop_model": round(f_tok * tokens / dt / peak, 4),
            "build_s": round(time.perf_counter() - t0, 1),
        }
    except Exception as e:
        row = {"variant": name, "error": f"{type(e).__name__}: {str(e)[:300]}"}
    print(json.dumps(row), flush=True)
    return row


def main() -> None:
    from llm_in_practise_tpu.core.mesh import require_tpu

    require_tpu()
    kind, peak = chip_peak()
    print(f"device {kind}", flush=True)
    rows = [
        time_variant("full", peak, quantized=True),
        time_variant("no_nf4", peak, quantized=False),
        time_variant("attn_dense", peak, quantized=True, attn_impl="dense"),
        time_variant("attn_flash", peak, quantized=True, attn_impl="flash"),
        time_variant("no_ce", peak, quantized=True, use_ce=False),
        time_variant("no_remat", peak, quantized=True, remat=False),
    ]
    full = next((r for r in rows if r["variant"] == "full" and "step_ms" in r),
                None)
    if full:
        for r in rows:
            if "step_ms" in r:
                r["delta_ms_vs_full"] = round(r["step_ms"] - full["step_ms"], 1)

    # capture a trace of the full step for the record
    trace_dir = os.path.join(REPO, "traces", "qlora_full")
    try:
        from llm_in_practise_tpu.obs.meter import profile_trace

        qstep, lora, opt_state, _ = build_step(quantized=True)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.integers(0, 32768, (BATCH, SEQ)), jnp.int32)
        batch = (x, jnp.roll(x, -1, axis=1))
        key = jax.random.PRNGKey(2)
        lora, opt_state, _ = qstep(lora, opt_state, batch, key)  # compiled
        with profile_trace(trace_dir):
            for _ in range(3):
                lora, opt_state, loss = qstep(lora, opt_state, batch, key)
            float(loss)
    except Exception as e:
        trace_dir = f"trace failed: {type(e).__name__}: {str(e)[:200]}"

    out = os.path.join(REPO, "MFU_ABLATION.json")
    with open(out, "w") as f:
        json.dump({"device": kind, "peak_bf16_flops": peak, "batch": BATCH,
                   "seq": SEQ, "shape": SHAPE, "variants": rows,
                   "trace": trace_dir}, f, indent=2)
    print("wrote", out)


if __name__ == "__main__":
    main()
