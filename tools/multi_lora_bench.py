"""Multi-LoRA bench — BENCH_MULTI_LORA artifact producer (CPU).

Pins the ISSUE 15 claim: one base model serving N tenants through the
batched-BGMV registry costs ~flat base memory and keeps the
1-jitted-dispatch-per-step invariant, at N ∈ {1, 4, 16} adapters. Every
leg replays the SAME seeded bursty arrival trace (serve/arrivals.py —
identical load shape across the ladder, adapters assigned round-robin),
so throughput deltas are the adapter count's, not the schedule's.

Per leg the artifact records trace-replay throughput/TPOT, registry
swap/byte accounting, the weight-memory ledger (base params once +
adapter payload vs the merged-engine world's N full copies), and GATES:

- **golden parity**: EVERY adapter's registry-engine output is
  byte-identical to a merged-weight engine's for the probe prompt —
  the gathered delta is exact at every rank bucket in the ladder;
- **1 dispatch/step**: a mixed-adapter decode probe (one slot per
  adapter + a base slot) asserts ``dispatch_meter.last_step == 1``;
- **flat base memory**: base param bytes are identical across legs,
  each adapter's bank payload stays a small fraction of one base copy,
  and the savings multiple over the merged-engine world (which pays
  ``N ×`` base) grows with N. The toy model exaggerates the per-adapter
  fraction (rank-8 factors against a 2-layer embed-64 base); on a real
  checkpoint the same ledger shrinks it by orders of magnitude.

Run: ``JAX_PLATFORMS=cpu python tools/multi_lora_bench.py``
Writes ``BENCH_MULTI_LORA_r11.json`` at the repo root; the tier-1
suite gates on the checked-in artifact and a ``main(quick=True)``
smoke runs under ``-m slow``.

CPU caveat: absolute tok/s are CPU-backend numbers; what this artifact
pins is the parity guarantee, the dispatch invariant, and the memory
ledger — on a real chip run the same ladder by pointing the engine
kwargs at a TPU build.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

OUT = os.path.join(REPO, "BENCH_MULTI_LORA_r11.json")
VOCAB = 128
MAX_PER_ADAPTER_FRACTION = 0.1  # one adapter's bank payload vs base copy
RANK_LADDER = (2, 3, 4, 6, 8)  # cycles over buckets {2, 4, 8}


def _model_params():
    import jax
    import jax.numpy as jnp

    from llm_in_practise_tpu.models.gpt import GPT, GPTConfig

    cfg = GPTConfig(vocab_size=VOCAB, seq_len=256, n_layer=2, n_head=2,
                    embed_dim=64, dropout=0.0, pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    return model, params


def _param_bytes(tree) -> int:
    import jax

    return int(sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(tree)))


def _make_adapters(params, n: int):
    """N lora trees cycling the rank ladder (B randomized so each
    tenant really steers tokens its own way)."""
    import jax

    from llm_in_practise_tpu.peft.lora import LoRAConfig, init_lora

    out = {}
    for i in range(n):
        r = RANK_LADDER[i % len(RANK_LADDER)]
        cfg = LoRAConfig(r=r, alpha=2.0 * r,
                         target_patterns=("attn/q_proj", "mlp"))
        tree = init_lora(params, cfg, jax.random.PRNGKey(100 + i))
        key = jax.random.PRNGKey(200 + i)
        tree = {k: {"a": v["a"],
                    "b": jax.random.normal(
                        jax.random.fold_in(key, j), v["b"].shape) * 0.3}
                for j, (k, v) in enumerate(sorted(tree.items()))}
        out[f"tenant-{i}"] = (tree, cfg)
    return out


def _engine(model, params, registry=None):
    import jax.numpy as jnp

    from llm_in_practise_tpu.serve.engine import InferenceEngine

    return InferenceEngine(
        model, params, max_slots=8, cache_len=256,
        cache_dtype=jnp.float32, chunked_prefill=32,
        prefix_cache=True, kv_layout="paged",
        adapter_registry=registry)


def _prompt(rng: np.random.Generator, n: int) -> list[int]:
    return [int(x) for x in rng.integers(1, VOCAB, size=n)]

PROBE = [(i * 7 + 3) % VOCAB for i in range(24)]


def _parity_gate(model, params, engine, adapters) -> dict:
    """Registry output == merged-weight engine output, EVERY adapter."""
    from llm_in_practise_tpu.peft.lora import merge_lora
    from llm_in_practise_tpu.serve.engine import SamplingParams

    sp = SamplingParams(greedy=True, max_tokens=16)
    checked = 0
    for name, (tree, cfg) in adapters.items():
        got = engine.generate(PROBE, sp, adapter=name)
        ref = _engine(model, merge_lora(params, tree, cfg)).generate(
            PROBE, sp)
        assert got == ref, f"parity broke for {name}: {got} != {ref}"
        checked += 1
    return {"checked": checked, "ok": True}


def _dispatch_probe(engine, adapters) -> dict:
    """Mixed-adapter decode: one slot per adapter (bounded by the slot
    count) plus a base slot must share ONE jitted dispatch per step."""
    from llm_in_practise_tpu.serve.engine import SamplingParams

    sp = SamplingParams(greedy=True, max_tokens=24)
    names = list(adapters)[:engine.max_slots - 1]
    handles = [engine.submit(PROBE, sp)]
    handles += [engine.submit(PROBE, sp, adapter=n) for n in names]
    engine.step()                      # admission (prefill dispatches)
    n_decode = mixed_steps = 0
    while engine.step():
        if not engine.slot_prefill:
            n_decode += 1
            if any(engine.slot_adapter):
                mixed_steps += 1
                assert engine.dispatch_meter.last_step == 1, (
                    f"{engine.dispatch_meter.last_step} dispatches in a "
                    "mixed-adapter decode step")
    for h in handles:
        h.result()
    assert mixed_steps > 0, "probe never hit a mixed decode step"
    return {"slots": len(handles), "decode_only_steps": n_decode,
            "mixed_adapter_steps": mixed_steps, "dispatches_per_step": 1}


def _trace_replay(engine, schedule, names) -> dict:
    """Replay the shared trace, arrival i pinned to adapter i mod N
    (``None`` rides along when the leg has a base share)."""
    from llm_in_practise_tpu.serve.arrivals import lateness_stats, replay
    from llm_in_practise_tpu.serve.engine import SamplingParams

    rng = np.random.default_rng(7)
    counter = itertools.count()
    lock = threading.Lock()

    def submit(arrival):
        with lock:
            i = next(counter)
            prompt = _prompt(rng, arrival.prompt_tokens)
        h = engine.submit(
            prompt,
            SamplingParams(greedy=True, max_tokens=arrival.max_tokens),
            adapter=names[i % len(names)])
        return h, h.result()

    t0 = time.monotonic()
    late: list = []
    pairs = replay(schedule, submit, workers=8, lateness=late)
    wall = time.monotonic() - t0
    toks = sum(len(out) for _, out in pairs)
    tpots = [h.tpot_s for h, _ in pairs if h.tpot_s is not None]
    out = {
        "requests": len(pairs),
        "output_tokens": toks,
        "wall_s": round(wall, 3),
        "output_tok_per_s": round(toks / wall, 2) if wall > 0 else None,
        "tpot_mean_ms": round(1e3 * float(np.mean(tpots)), 3)
        if tpots else None,
        "tpot_p99_ms": round(1e3 * float(np.percentile(tpots, 99)), 3)
        if tpots else None,
    }
    out.update(lateness_stats(late))
    return out


def run_leg(model, params, n_adapters: int, schedule) -> dict:
    from llm_in_practise_tpu.serve.multi_lora import AdapterRegistry

    adapters = _make_adapters(params, n_adapters)
    registry = AdapterRegistry(params)
    for name, (tree, cfg) in adapters.items():
        registry.register_tree(name, tree, cfg)
    engine = _engine(model, params, registry=registry)

    parity = _parity_gate(model, params, engine, adapters)
    dispatch = _dispatch_probe(engine, adapters)

    engine.start()
    try:
        names = list(adapters)
        trace = _trace_replay(engine, schedule, names)
    finally:
        engine.stop()

    stats = registry.stats()
    base_bytes = _param_bytes(engine.params)
    adapter_bytes = stats["bytes_loaded"]
    assert all(stats["tenant_tokens"].get(n, 0) > 0 for n in names), (
        "every tenant must have tokens booked after the trace")
    assert all(v == 0 for v in stats["refcounts"].values())
    return {
        "n_adapters": n_adapters,
        "rank_buckets": {str(rb): b["cap"] - 1 - b["free"]
                         for rb, b in stats["buckets"].items()},
        "trace_replay": trace,
        "parity": parity,
        "dispatch_probe": dispatch,
        "registry": {
            "loads_total": stats["loads_total"],
            "swap_seconds_total": round(stats["swap_seconds_total"], 4),
            "tenant_tokens_total": sum(stats["tenant_tokens"].values()),
        },
        "weight_memory": {
            "base_param_bytes": base_bytes,
            "adapter_bytes": adapter_bytes,
            "adapter_fraction_of_base": round(
                adapter_bytes / base_bytes, 5),
            "per_adapter_fraction_of_base": round(
                adapter_bytes / n_adapters / base_bytes, 5),
            # what engine-per-adapter merged serving would pay instead
            "merged_world_bytes": n_adapters * base_bytes,
            "savings_x": round(
                (n_adapters * base_bytes)
                / (base_bytes + adapter_bytes), 2),
        },
    }


def main(*, quick: bool = False, out: str = OUT) -> dict:
    from llm_in_practise_tpu.serve import arrivals

    ladder = (1, 4) if quick else (1, 4, 16)
    n_requests = 12 if quick else 48
    # ONE trace shared by every leg — deltas are the adapter count's
    schedule = arrivals.synthesize(
        seed=42, n_requests=n_requests, mean_iat_s=0.02, cv=2.0,
        prompt_tokens=(8, 48), max_tokens=(16, 48))
    model, params = _model_params()
    legs = []
    for n in ladder:
        leg = run_leg(model, params, n, schedule)
        print(json.dumps({
            "n_adapters": n,
            "output_tok_per_s": leg["trace_replay"]["output_tok_per_s"],
            "adapter_fraction_of_base":
                leg["weight_memory"]["adapter_fraction_of_base"],
            "savings_x": leg["weight_memory"]["savings_x"]}))
        legs.append(leg)
    base = {leg["weight_memory"]["base_param_bytes"] for leg in legs}
    assert len(base) == 1, f"base bytes must be flat across legs: {base}"
    for leg in legs:
        per = leg["weight_memory"]["per_adapter_fraction_of_base"]
        assert per <= MAX_PER_ADAPTER_FRACTION, (
            f"per-adapter payload {per} of base at "
            f"N={leg['n_adapters']} exceeds {MAX_PER_ADAPTER_FRACTION}")
    savings = [leg["weight_memory"]["savings_x"] for leg in legs]
    assert savings == sorted(savings), (
        f"savings over the merged world must grow with N: {savings}")
    artifact = {
        "bench": "multi_lora",
        "round": "r11",
        "issue": 15,
        "backend": "cpu",
        "quick": quick,
        "adapter_ladder": list(ladder),
        "rank_ladder": list(RANK_LADDER),
        "max_per_adapter_fraction": MAX_PER_ADAPTER_FRACTION,
        "arrivals": arrivals.describe(schedule),
        "legs": legs,
    }
    with open(out, "w") as f:
        json.dump(artifact, f, indent=1)
    print(f"wrote {out}")
    return artifact


if __name__ == "__main__":
    main(quick="--quick" in sys.argv)
