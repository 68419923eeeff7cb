"""151936-vocab compile-stall root-cause probe (VERDICT r3 item 4).

Round 2 measured that the real Qwen3 vocab (151936) makes EVERY QLoRA
step variant un-compilable on this chip's AOT compile service (>25 min;
32768 compiles in ~4 min), and that vocab-axis CE tiling did not rescue
it. This probe isolates the cause by compiling minimal 1-layer programs
that differ in exactly one dimension, each in its own subprocess with a
hard timeout. Timing is compile-only (``jit(...).lower(args).compile()``).

**Round-3 verdict (VOCAB_PROBE.json):** the vocab math was never the
problem — a bare 151936x2048 gather, the flax embed forward, and the full
1-layer init each compile in seconds. The stall is the frozen QLoRA base
captured as a jit CLOSURE CONSTANT: the tree is serialized into the HLO
module uploaded to the remote compile service (311 MB embedding at the
full vocab; the ``_const`` probes stall or die with HTTP 413 "length
limit exceeded" — the service's request cap). Passing the frozen tree as
a jit ARGUMENT (``make_qlora_loss_fn_args``) compiles the identical
program in <10 s at either vocab — the ``_arg`` probes below. A 1187-tile
width-128 CE variant was also tried once and died at HTTP 413 from
program size alone; it is omitted from the default set.

Probe naming: ``{head}_{vocab}_{const|arg}`` where const/arg is how the
frozen base reaches the step. ``ce_tiled`` uses the streaming vocab-tiled
CE (requested tile 8192; 151936 = 2^7 x 1187 with 1187 prime, so the
actual tile the divisor search lands on is 4748 — see
``train/losses.py``); ``ce_untiled`` is the single-dot head;
``embed_only`` drops the CE head entirely (loss on mean hidden).

Re-running merges with an existing VOCAB_PROBE.json (probes already
recorded are skipped); delete the file to re-measure everything.

Run on the TPU host (default env): python tools/tpu_vocab_probe.py
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SEQ = 1024
TIMEOUT_S = int(os.environ.get("VOCAB_PROBE_TIMEOUT", "720"))
OUT = os.path.join(REPO, "VOCAB_PROBE.json")

# name: (vocab, vocab_chunk, use_head, base_mode)
PROBES = {
    "control_32k": (32768, None, True, "const"),
    "ce_full_untiled": (151936, None, True, "const"),
    "ce_full_tiled": (151936, 8192, True, "const"),
    "ce_padded_aligned": (152064, 4608, True, "const"),
    "embed_only": (151936, None, False, "const"),
    "control_32k_arg": (32768, None, True, "arg"),
    "ce_full_untiled_arg": (151936, None, True, "arg"),
    "ce_full_tiled_arg": (151936, 8192, True, "arg"),
    "embed_only_arg": (151936, None, False, "arg"),
}


def run_probe(vocab: int, vocab_chunk: int | None, use_head: bool,
              base_mode: str) -> dict:
    from llm_in_practise_tpu.core.mesh import require_tpu

    require_tpu()
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from llm_in_practise_tpu.models.qwen3 import Qwen3, Qwen3Config
    from llm_in_practise_tpu.peft import lora as lora_lib
    from llm_in_practise_tpu.peft.qlora import (
        make_qlora_loss_fn, make_qlora_loss_fn_args, quantize_base_lowmem,
    )
    from llm_in_practise_tpu.train.losses import fused_linear_cross_entropy

    cfg = Qwen3Config(
        vocab_size=vocab, max_seq_len=SEQ, rope_theta=1e6,
        tie_word_embeddings=True, remat=True, compute_dtype="bfloat16",
        hidden_size=2048, intermediate_size=6144, n_layer=1,
        n_head=16, n_kv_head=8, head_dim=128,
    )
    model = Qwen3(cfg)
    params = jax.jit(
        lambda r: model.init(r, jnp.ones((1, 8), jnp.int32))["params"]
    )(jax.random.PRNGKey(0))
    qparams = quantize_base_lowmem(params)
    del params
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
        if not use_head:
            return jnp.mean(hidden.astype(jnp.float32) ** 2)
        loss, _ = fused_linear_cross_entropy(
            hidden, p["tok_embed"]["embedding"], y,
            transpose_weight=True, chunk=2048, vocab_chunk=vocab_chunk)
        return loss

    tx = optax.adamw(1e-4)
    opt_state = tx.init(lora)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, min(vocab, 151936), (8, SEQ)), jnp.int32)
    batch = (x, jnp.roll(x, -1, axis=1))

    t0 = time.perf_counter()
    if base_mode == "const":
        loss_fn = make_qlora_loss_fn(qparams, lcfg, base_loss)

        def qstep(lora, opt_state, batch, rng):
            loss, grads = jax.value_and_grad(loss_fn)(lora, batch, rng)
            updates, opt_state = tx.update(grads, opt_state, lora)
            return optax.apply_updates(lora, updates), opt_state, loss

        lowered = jax.jit(qstep).lower(lora, opt_state, batch,
                                       jax.random.PRNGKey(2))
    else:
        loss_fn = make_qlora_loss_fn_args(lcfg, base_loss)

        def qstep(lora, opt_state, qp, batch, rng):
            loss, grads = jax.value_and_grad(loss_fn)(lora, qp, batch, rng)
            updates, opt_state = tx.update(grads, opt_state, lora)
            return optax.apply_updates(lora, updates), opt_state, loss

        lowered = jax.jit(qstep).lower(lora, opt_state, qparams, batch,
                                       jax.random.PRNGKey(2))
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    lowered.compile()
    t_compile = time.perf_counter() - t0
    return {"lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1)}


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--probe", default=None)
    args = p.parse_args()

    if args.probe:  # child mode: one probe, result on stdout
        spec = PROBES[args.probe]
        print(json.dumps({"probe": args.probe, **run_probe(*spec)}))
        return

    existing: dict[str, dict] = {}
    if os.path.exists(OUT):
        with open(OUT) as f:
            existing = {r["probe"]: r for r in json.load(f).get("probes", [])}

    results = []
    for name in PROBES:
        if name in existing:
            results.append(existing[name])
            continue
        t0 = time.perf_counter()
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--probe", name],
                capture_output=True, text=True, timeout=TIMEOUT_S,
            )
            line = (proc.stdout.strip().splitlines()[-1]
                    if proc.stdout.strip() else "")
            row = (json.loads(line) if line.startswith("{")
                   else {"probe": name, "error": proc.stdout[-500:] +
                         proc.stderr[-500:]})
        except subprocess.TimeoutExpired:
            row = {"probe": name, "timeout_s": TIMEOUT_S,
                   "verdict": "STALLED (killed)"}
        row["wall_s"] = round(time.perf_counter() - t0, 1)
        results.append(row)
        print(json.dumps(row), flush=True)

    # keep historical one-off rows (e.g. the width-128 HTTP-413 evidence)
    results += [r for name, r in existing.items() if name not in PROBES]

    with open(OUT, "w") as f:
        json.dump({"timeout_s": TIMEOUT_S, "seq": SEQ, "probes": results},
                  f, indent=2)
    print("wrote", OUT)


if __name__ == "__main__":
    main()
