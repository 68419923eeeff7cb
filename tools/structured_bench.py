"""Structured-output bench — BENCH_STRUCTURED artifact producer (CPU).

Pins the cost and the correctness of constrained decoding (ISSUE 12)
across every CPU-reproducible engine path — {contiguous, paged} x
{spec off, ngram} — with TWO load shapes per leg:

- **closed-loop unconstrained**: the baseline ladder (N workers,
  back-to-back) — the TPOT reference constrained decoding is compared
  against;
- **trace-replay constrained**: the SAME engine under a seeded bursty
  arrival schedule (Gamma inter-arrivals, cv=2, mixed prompt/output
  lengths — serve/arrivals.py, ROADMAP item 2b first slice), every
  request carrying a ``json_schema`` grammar.

Per leg the artifact records constrained-vs-unconstrained TPOT
overhead, output tok/s, grammar mask-staging seconds, dispatches/step,
spec acceptance + grammar-rejected drafts (spec legs), and GATES on

- conformance: EVERY constrained completion parses and validates
  (``constrain.validate_instance``) — the acceptance criterion;
- steptrace coverage >= 0.95 with grammar on: the new
  ``grammar_compile``/``grammar_mask`` host activities keep PR 11's
  step-timeline partition honest.

Run: ``JAX_PLATFORMS=cpu python tools/structured_bench.py``
Writes ``BENCH_STRUCTURED_r10.json`` at the repo root; the tier-1
smoke runs ``main(quick=True)`` against a temp path.

CPU caveat: absolute milliseconds are CPU-backend numbers; what this
artifact pins is the RELATIVE overhead (mask staging vs dispatch), the
conformance guarantee, and the attribution machinery — on a real chip
run the same legs by pointing the engine kwargs at a TPU build.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

OUT = os.path.join(REPO, "BENCH_STRUCTURED_r10.json")
COVERAGE_GATE = 0.95
VOCAB = 128

SCHEMA = {
    "type": "object",
    "properties": {
        "name": {"type": "string", "minLength": 1, "maxLength": 10},
        "age": {"type": "integer"},
        "tags": {"type": "array", "items": {"enum": ["a", "b", "c"]},
                 "minItems": 1, "maxItems": 3},
    },
    "required": ["name", "age", "tags"],
}


class CharTok:
    def encode(self, text):
        return [min(ord(c), VOCAB - 1) for c in text]

    def decode(self, ids):
        return "".join(chr(int(i) % VOCAB) for i in ids)


def _build(kv_layout: str, spec: bool):
    import jax
    import jax.numpy as jnp

    from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
    from llm_in_practise_tpu.serve.engine import InferenceEngine

    cfg = GPTConfig(vocab_size=VOCAB, seq_len=256, n_layer=2, n_head=2,
                    embed_dim=64, dropout=0.0, pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    return InferenceEngine(
        model, params, max_slots=8, cache_len=256,
        cache_dtype=jnp.float32, chunked_prefill=32,
        prefix_cache=True, kv_layout=kv_layout,
        speculative_k=4 if spec else None)


def _prompt(rng: np.random.Generator, n_tokens: int) -> list[int]:
    # printable chars so the grammar vocab and the prompt share space;
    # a repeated phrase gives the ngram speculator something to draft
    base = "fill the json fields now please "
    text = (base * (n_tokens // len(base) + 1))[:n_tokens]
    return [min(ord(c), VOCAB - 1) for c in text]


def _stats(pairs, wall: float) -> dict:
    """Aggregates over (handle, output-token-list) pairs. Streams are
    drained exactly ONCE by the caller — Request.result() consumes the
    token queue, a second drain would block forever."""
    tpots, ttfts, toks = [], [], 0
    finish = {}
    for h, out in pairs:
        toks += len(out)
        finish[h.finish_reason] = finish.get(h.finish_reason, 0) + 1
        if h.tpot_s is not None:
            tpots.append(h.tpot_s)
        if h.ttft_s is not None:
            ttfts.append(h.ttft_s)
    return {
        "requests": len(pairs),
        "output_tokens": toks,
        "finish_reasons": finish,
        "wall_s": round(wall, 3),
        "output_tok_per_s": round(toks / wall, 2) if wall > 0 else None,
        "tpot_mean_ms": round(1e3 * float(np.mean(tpots)), 3)
        if tpots else None,
        "tpot_p99_ms": round(1e3 * float(np.percentile(tpots, 99)), 3)
        if tpots else None,
        "ttft_p99_ms": round(1e3 * float(np.percentile(ttfts, 99)), 3)
        if ttfts else None,
    }


def _closed_loop(engine, prompts, *, concurrency: int,
                 max_tokens: int, constraint=None) -> dict:
    from llm_in_practise_tpu.serve.engine import SamplingParams

    pairs, lock = [], threading.Lock()
    left = [len(prompts)]

    def worker():
        while True:
            with lock:
                if left[0] <= 0:
                    return
                left[0] -= 1
                i = left[0]
            h = engine.submit(prompts[i], SamplingParams(
                greedy=True, max_tokens=max_tokens,
                constraint=constraint))
            out = h.result()
            with lock:
                pairs.append((h, out))

    t0 = time.monotonic()
    threads = [threading.Thread(target=worker)
               for _ in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return _stats(pairs, time.monotonic() - t0)


def _trace_replay(engine, schedule, *, constraint, tokenizer) -> dict:
    """Replay the SAME seeded schedule with or without the grammar —
    the constrained-vs-unconstrained TPOT pin compares identical load
    shapes, not a closed ladder against an open trace."""
    from llm_in_practise_tpu.serve import constrain
    from llm_in_practise_tpu.serve.arrivals import replay
    from llm_in_practise_tpu.serve.engine import SamplingParams

    rng = np.random.default_rng(11)

    def submit(arrival):
        # open-loop: submit at the scheduled instant, drain the stream
        # on the same worker (the arrival clock never slows)
        h = engine.submit(
            _prompt(rng, arrival.prompt_tokens),
            SamplingParams(greedy=True, max_tokens=arrival.max_tokens,
                           constraint=constraint))
        return h, h.result()

    t0 = time.monotonic()
    late: list = []
    pairs = replay(schedule, submit, workers=8, lateness=late)
    out = _stats(pairs, time.monotonic() - t0)
    # realized arrival lateness: workers drain streams, so the open
    # loop bounds in-flight at the pool size — the artifact states how
    # far the applied load drifted from the schedule
    from llm_in_practise_tpu.serve.arrivals import lateness_stats

    out.update(lateness_stats(late))
    if constraint is None:
        return out
    # conformance gate: every completed stream validates; "length"
    # truncations (output budget < the schema's canonical need) are
    # counted separately — they are the client's budget choice, not a
    # grammar failure
    conformant = truncated = 0
    for h, ids in pairs:
        text = tokenizer.decode(ids)
        if h.finish_reason != "stop":
            truncated += 1
            continue
        value = json.loads(text)          # raises on any drift = gate
        assert constrain.validate_instance(value, SCHEMA), text
        conformant += 1
    out["conformant"] = conformant
    out["truncated"] = truncated
    return out


def run_leg(name: str, kv_layout: str, spec: bool, *, n_requests: int,
            arrival_seed: int) -> dict:
    from llm_in_practise_tpu.serve import arrivals, constrain

    tok = CharTok()
    vocab = constrain.vocab_strings(tok, VOCAB)
    auto = constrain.compile_request_constraint(
        response_format={"type": "json_schema",
                         "json_schema": {"schema": SCHEMA}},
        vocab=vocab, eos_id=None)
    engine = _build(kv_layout, spec)
    engine.start()
    try:
        rng = np.random.default_rng(5)
        prompts = [_prompt(rng, int(n)) for n in
                   rng.integers(8, 48, size=n_requests)]
        # warmup: compile the whole program family before timing
        _closed_loop(engine, prompts[:2], concurrency=2, max_tokens=8)
        _closed_loop(engine, prompts[:2], concurrency=2, max_tokens=8,
                     constraint=auto)
        baseline = _closed_loop(engine, prompts, concurrency=8,
                                max_tokens=64)
        # output budgets sized for the schema's canonical need (~50
        # chars + digit caps) so every stream can complete; truncation
        # accounting stays in place for under-budgeted client traffic
        sched = arrivals.synthesize(
            seed=arrival_seed, n_requests=n_requests,
            mean_iat_s=0.02, cv=2.0, prompt_tokens=(8, 48),
            max_tokens=(72, 128))
        unconstrained = _trace_replay(engine, sched, constraint=None,
                                      tokenizer=tok)
        constrained = _trace_replay(engine, sched, constraint=auto,
                                    tokenizer=tok)
        snap = engine.steptrace.snapshot()
        dm = engine.dispatch_meter
        leg = {
            "leg": name,
            "kv_layout": kv_layout,
            "speculative": spec,
            "baseline_closed_loop": baseline,
            "unconstrained_trace_replay": unconstrained,
            "constrained_trace_replay": constrained,
            "arrivals": arrivals.describe(sched),
            # same seeded arrival trace with and without the grammar:
            # THE constrained-decoding overhead number
            "tpot_overhead_x": round(
                constrained["tpot_mean_ms"]
                / unconstrained["tpot_mean_ms"], 3)
            if (constrained["tpot_mean_ms"]
                and unconstrained["tpot_mean_ms"]) else None,
            "grammar_mask_seconds_total": round(
                engine.grammar_mask_seconds_total, 4),
            "grammar_states_compiled": auto.states_compiled,
            "dispatches_per_step": round(dm.mean_per_step, 3),
            "host_gap": {
                "coverage": round(snap["coverage"], 6),
                "coverage_ok": snap["coverage"] >= COVERAGE_GATE,
                "grammar_compile_s": round(
                    snap["host_seconds"]["grammar_compile"], 4),
                "grammar_mask_s": round(
                    snap["host_seconds"]["grammar_mask"], 4),
            },
        }
        if spec:
            leg["spec"] = {
                "rounds": engine.spec_rounds,
                "proposed": engine.spec_proposed,
                "accepted": engine.spec_accepted,
                "acceptance": round(
                    engine.spec_accepted / max(engine.spec_proposed, 1),
                    4),
                "grammar_rejects": engine.spec_grammar_rejects,
                "tokens_per_round": round(
                    engine.spec_round_tokens
                    / max(engine.spec_rounds, 1), 3),
            }
        assert leg["host_gap"]["coverage_ok"], (
            f"{name}: steptrace coverage "
            f"{leg['host_gap']['coverage']} < {COVERAGE_GATE} with "
            "grammar on")
        return leg
    finally:
        engine.stop()


def main(*, quick: bool = False, out: str = OUT) -> dict:
    n = 12 if quick else 48
    legs = []
    for name, layout, spec in (
        ("contiguous", "contiguous", False),
        ("contiguous_spec", "contiguous", True),
        ("paged", "paged", False),
        ("paged_spec", "paged", True),
    ):
        leg = run_leg(name, layout, spec, n_requests=n, arrival_seed=42)
        print(json.dumps({k: leg[k] for k in
                          ("leg", "tpot_overhead_x",
                           "grammar_mask_seconds_total")}))
        legs.append(leg)
    artifact = {
        "bench": "structured_output",
        "round": "r10",
        "issue": 12,
        "backend": "cpu",
        "quick": quick,
        "schema": SCHEMA,
        "coverage_gate": COVERAGE_GATE,
        "legs": legs,
    }
    with open(out, "w") as f:
        json.dump(artifact, f, indent=1)
    print(f"wrote {out}")
    return artifact


if __name__ == "__main__":
    main(quick="--quick" in sys.argv)
