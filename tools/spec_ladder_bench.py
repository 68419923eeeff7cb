"""Speculation ladder A/B — the ROADMAP item 4 acceptance artifact.

Three legs on the SAME engine config (a decode replica's production
setup: paged KV, greedy traffic):

- **off**   — plain decode (the baseline the fused spec round must
  beat);
- **ngram** — prompt-lookup speculation (no extra weights);
- **draft** — draft-MODEL speculation (a smaller trained model
  proposes).

The thing under test is the **fused spec round**
(``serve/mixed_step.spec_verify_block``): the engine verifies the k
drafted tokens AND decodes the rest of the planned block inside ONE
jitted dispatch, so a spec round commits ``accepted + 1 + (block-1)``
tokens where the plain leg's block commits ``block`` — per-dispatch
economics the artifact reports as ``tokens_per_spec_dispatch``.

CPU-reproducible (the kv_layout_bench pattern): target and draft are
tiny GPTs TRAINED on a repeating corpus, so ngram/draft acceptance is
real — an untrained model generates noise, and a noise ladder says
nothing about the spec bet. A smoke variant runs inside tier-1
(``tests/test_spec_fused.py::test_spec_ladder_smoke``).

Gates (exit 1, like kv_layout_bench): every spec leg must commit > 1
token per spec dispatch, and the best spec leg's conc-1 TPOT must be
STRICTLY below the plain leg's. Golden-token equality (spec ≡ plain)
is pinned separately in ``tests/test_spec_fused.py`` for both KV
layouts — this artifact is the perf half.

Run: ``python tools/spec_ladder_bench.py``. Writes
``BENCH_SPEC_LADDER_r07.json`` at the repo root. Env knobs:
``SPEC_BENCH_KV_LAYOUT`` (default paged), ``SPEC_BENCH_TRAIN_STEPS``,
``SPEC_BENCH_REQUESTS``.
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

OUT = os.environ.get("SPEC_LADDER_OUT",
                     os.path.join(REPO, "BENCH_SPEC_LADDER_r07.json"))

CACHE_LEN = 256
VOCAB = 96
# the shared corpus both models memorize — self-similar text is the
# regime speculation exists for; the artifact states it
TEXT = ("the quick brown fox jumps over the lazy dog and then "
        "the quick brown fox jumps over the lazy dog again ") * 4


def _train_gpt(n_layer: int, n_head: int, embed_dim: int, steps: int,
               seed: int):
    """Memorize TEXT (the tests/test_draft_model_spec.py recipe) so
    generated text has the structure drafts can hit."""
    import optax

    from llm_in_practise_tpu.models.gpt import GPT, GPTConfig

    ids = np.frombuffer(TEXT.encode(), np.uint8).astype(np.int32) % VOCAB
    cfg = GPTConfig(vocab_size=VOCAB, seq_len=CACHE_LEN, n_layer=n_layer,
                    n_head=n_head, embed_dim=embed_dim, dropout=0.0,
                    pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.ones((1, 8), jnp.int32))["params"]
    tx = optax.adamw(3e-3)
    opt = tx.init(params)

    @jax.jit
    def step(params, opt, x, y):
        def loss_fn(p):
            logits = model.apply({"params": p}, x, deterministic=True)
            lp = jax.nn.log_softmax(logits.astype(jnp.float32))
            return -jnp.take_along_axis(lp, y[..., None], -1).mean()

        loss, g = jax.value_and_grad(loss_fn)(params)
        up, opt = tx.update(g, opt, params)
        return optax.apply_updates(params, up), opt, loss

    rng = np.random.default_rng(seed)
    for _ in range(steps):
        i = rng.integers(0, len(ids) - 33, (8,))
        x = jnp.asarray(np.stack([ids[j: j + 32] for j in i]))
        y = jnp.asarray(np.stack([ids[j + 1: j + 33] for j in i]))
        params, opt, _ = step(params, opt, x, y)
    return model, params


def _prompts(n: int = 8):
    ids = [int(b) % VOCAB for b in TEXT.encode()]
    return [ids[j * 9: j * 9 + 24 + (j % 3) * 8] for j in range(n)]


def run_ladder(*, train_steps: int = 300, n_requests: int = 24,
               max_tokens: int = 48, kv_layout: str = "paged",
               spec_k: int = 4,
               concurrencies=(1, 4), out_path: str | None = None) -> dict:
    """Build the trained pair, run the three legs, return (and
    optionally write) the artifact dict. The smoke test calls this
    with reduced sizes."""
    from deploy.benchmark.bench_serve import run_level_inprocess
    from llm_in_practise_tpu.serve.engine import InferenceEngine

    t0 = time.perf_counter()
    target_model, target_params = _train_gpt(3, 4, 64, train_steps, seed=0)
    draft_model, draft_params = _train_gpt(
        2, 2, 48, train_steps + train_steps // 3, seed=1)
    train_s = time.perf_counter() - t0
    prompt_ids = _prompts()

    base_kw = dict(max_slots=4, cache_len=CACHE_LEN,
                   cache_dtype=jnp.float32, chunked_prefill=64,
                   kv_layout=kv_layout)
    legs = {}
    for leg in ("off", "ngram", "draft"):
        kw = dict(base_kw)
        if leg != "off":
            kw["speculative_k"] = spec_k
        if leg == "draft":
            kw["draft_model"] = draft_model
            kw["draft_params"] = draft_params
        eng = InferenceEngine(target_model, target_params, **kw)
        eng.start()
        # warmup compiles every block/verify/view-width variant the
        # ladder will hit, so no first-use compile lands in a timed row
        run_level_inprocess(eng, prompt_ids,
                            concurrency=max(concurrencies),
                            n_requests=max(8, 2 * max(concurrencies)),
                            max_tokens=max_tokens)
        # baseline the lifetime spec counters here so the published
        # acceptance / tokens-per-dispatch cover TIMED rounds only —
        # warmup rounds (and their compile-stall dispatches) must not
        # leak into the artifact's per-leg numbers. (The device_plane
        # and dispatches_per_step blocks are 50-sample rolling means,
        # dominated by the timed rows by construction.)
        w = {a: getattr(eng, a) for a in
             ("spec_proposed", "spec_accepted", "spec_rounds",
              "spec_round_tokens")}
        levels = []
        for conc in concurrencies:
            row = run_level_inprocess(eng, prompt_ids, concurrency=conc,
                                      n_requests=max(n_requests, 2 * conc),
                                      max_tokens=max_tokens)
            levels.append(row)
            print(json.dumps({"leg": leg, "concurrency": conc,
                              "output_tps": row["output_tps"],
                              "tpot_p50_ms": row["tpot_p50_ms"]}),
                  flush=True)
        eng.stop()
        proposed = eng.spec_proposed - w["spec_proposed"]
        accepted = eng.spec_accepted - w["spec_accepted"]
        rounds = eng.spec_rounds - w["spec_rounds"]
        round_tokens = eng.spec_round_tokens - w["spec_round_tokens"]
        legs[leg] = {
            "speculative_k": kw.get("speculative_k"),
            "proposed": proposed,
            "accepted": accepted,
            "acceptance": (round(accepted / proposed, 4)
                           if proposed else None),
            "spec_rounds": rounds,
            "tokens_per_spec_dispatch": (
                round(round_tokens / rounds, 3) if rounds else None),
            "dispatches_per_step":
                round(eng.dispatch_meter.mean_per_step, 3),
            "device_plane": eng.dispatch_meter.phase_snapshot(),
            "levels": levels,
        }

    def conc1_tpot(leg):
        return legs[leg]["levels"][0]["tpot_p50_ms"]

    best_spec = min(("ngram", "draft"), key=conc1_tpot)
    artifact = {
        "bench": "spec_ladder",
        "model": f"GPT 3L/64d trained {train_steps} steps on a "
                 "repeating corpus (draft: 2L/48d, same corpus) — "
                 "self-similar text is the regime speculation exists "
                 "for; random text degrades toward the off leg "
                 "(acceptance -> 0), never below losslessness",
        "train_seconds": round(train_s, 1),
        "engine": {**{k: v for k, v in base_kw.items()
                      if k != "cache_dtype"},
                   "fused_spec_round": True},
        "concurrencies": list(concurrencies),
        "max_tokens": max_tokens,
        "legs": legs,
        "conc1_tpot_p50_ms": {leg: conc1_tpot(leg) for leg in legs},
        "best_spec_leg": best_spec,
        "spec_beats_plain_conc1": conc1_tpot(best_spec) < conc1_tpot("off"),
        "note": ("one fused dispatch per spec round: verify k drafts + "
                 "the block's remaining steps (serve/mixed_step."
                 "spec_verify_block); golden-token equality spec-on == "
                 "spec-off is pinned in tests/test_spec_fused.py for "
                 "both KV layouts"),
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"wrote {out_path}: conc-1 TPOT p50 off "
              f"{conc1_tpot('off'):.2f} ms vs {best_spec} "
              f"{conc1_tpot(best_spec):.2f} ms", flush=True)
    return artifact


def main() -> None:
    artifact = run_ladder(
        train_steps=int(os.environ.get("SPEC_BENCH_TRAIN_STEPS", "300")),
        n_requests=int(os.environ.get("SPEC_BENCH_REQUESTS", "24")),
        kv_layout=os.environ.get("SPEC_BENCH_KV_LAYOUT", "paged"),
        out_path=OUT,
    )
    ok = artifact["spec_beats_plain_conc1"] and all(
        artifact["legs"][leg]["tokens_per_spec_dispatch"] is not None
        and artifact["legs"][leg]["tokens_per_spec_dispatch"] > 1.0
        for leg in ("ngram", "draft"))
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
