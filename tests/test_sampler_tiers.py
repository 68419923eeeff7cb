"""The sampler does only the work its live rows ask for (PR 30).

``infer/sampling.py::sample_token_batched`` switches, inside the compiled
program and on its own per-row flags, between three bodies: ``argmax``
(every row greedy), ``plain`` (no sampled row filters) and ``filtered``
(the full-vocabulary sort). These tests pin:

- every body returns, bit for bit, what the sampler returned before the
  switch (``reference_sampler`` below is a transcription of that body),
  eagerly, under ``jit`` and inside a ``lax.scan``, with and without an
  additive grammar mask, and a sampled row's token does not change when a
  neighbour row turns a filter on;
- which body runs for which rows, on the device (a debug callback in each
  body) and in the jaxpr (only ``filtered`` holds a ``sort``);
- the engine hands rows with no live request to the sampler as greedy, so
  stale or initial flags cannot choose the body, and books the body it
  chose: the step records' ``sampler_tier`` and
  ``llm_sampler_steps_total{tier=...}``.

CPU, small vocabulary, seconds."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_in_practise_tpu.infer.sampling import (
    NEG_INF, sample_token_batched, sampler_tier, sampler_tier_name,
)
from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
from llm_in_practise_tpu.serve.engine import InferenceEngine, SamplingParams
from promparse import parse_exposition

ROWS, VOCAB = 6, 97


def reference_sampler(rng, logits, *, temperature, top_k, top_p, greedy):
    """The sampler as it was before the switch: every row pays the
    divide, the sort, both masks and the draw; greedy rows discard it."""
    n_vocab = logits.shape[-1]
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    k_idx = jnp.clip(top_k - 1, 0, n_vocab - 1)
    kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
    k_on = top_k[:, None] > 0
    scaled = jnp.where(k_on & (scaled < kth), NEG_INF, scaled)
    sorted_desc = jnp.where(
        k_on & (jnp.arange(n_vocab)[None, :] > k_idx[:, None]), NEG_INF,
        sorted_desc)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_mask = cum - probs > top_p[:, None]
    cutoff_logit = jnp.min(
        jnp.where(cutoff_mask, jnp.inf, sorted_desc), axis=-1, keepdims=True)
    use_p = (top_p < 1.0)[:, None]
    scaled = jnp.where(use_p & (scaled < cutoff_logit), NEG_INF, scaled)
    sampled = jax.random.categorical(rng, scaled, axis=-1)
    return jnp.where(greedy, jnp.argmax(logits, axis=-1), sampled)


def rows(greedy, temperature, top_k, top_p):
    return dict(greedy=jnp.asarray(greedy, bool),
                temperature=jnp.asarray(temperature, jnp.float32),
                top_k=jnp.asarray(top_k, jnp.int32),
                top_p=jnp.asarray(top_p, jnp.float32))


T = [0.8, 1.3, 0.5, 1.0, 0.7, 0.9]
# name -> (the rows' arguments, the body they must take)
MIXES = {
    "all_greedy": (rows([1] * 6, [0.0] * 6, [0] * 6, [1.0] * 6), "argmax"),
    # a greedy row's filters are nobody's business
    "greedy_rows_with_filters": (
        rows([1] * 6, [0.0] * 6, [5, 0, 3, 0, 0, 9], [0.5, 1, 1, 0, 1, 1]),
        "argmax"),
    "temperature_only": (rows([0] * 6, T, [0] * 6, [1.0] * 6), "plain"),
    "greedy_and_temperature": (
        rows([1, 0, 1, 0, 0, 1], T, [0] * 6, [1.0] * 6), "plain"),
    "top_k_only": (rows([0] * 6, T, [5, 1, 20, 3, 96, 97], [1.0] * 6),
                   "filtered"),
    "top_p_only": (rows([0] * 6, T, [0] * 6, [0.9, 0.5, 0.99, 0.1, 0.7, 0.95]),
                   "filtered"),
    "top_p_zero": (rows([0] * 6, T, [0] * 6, [0.0] * 6), "filtered"),
    "mixed_rows": (
        rows([1, 0, 0, 0, 1, 0], T, [0, 0, 5, 0, 7, 20],
             [1.0, 1.0, 1.0, 0.9, 0.3, 0.95]), "filtered"),
    "one_filter_among_plain": (
        rows([0] * 6, T, [0, 0, 0, 4, 0, 0], [1.0] * 6), "filtered"),
}


def the_logits(masked):
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(11), (ROWS, VOCAB))
    if masked:
        # an additive grammar mask (serve/constrain.py): some rows
        # constrained to a handful of tokens, the others untouched
        allowed = jax.random.bernoulli(jax.random.PRNGKey(5), 0.1,
                                       (ROWS, VOCAB)).at[:, 7].set(True)
        constrained = jnp.asarray([1, 0, 1, 1, 0, 1], bool)[:, None]
        logits = logits + jnp.where(constrained & ~allowed, NEG_INF, 0.0)
    return logits


def run(fn, mode, rng, logits, kw):
    """``fn`` eagerly, under jit, or as the body of a two-step scan (the
    shape of ``mixed_step.decode_scan``: one key a step)."""
    if mode == "eager":
        return fn(rng, logits, **kw)
    if mode == "jit":
        return jax.jit(lambda r, x, k: fn(r, x, **k))(rng, logits, kw)

    def body(carry, key):
        tok = fn(key, logits + carry[:, None], **kw)
        return 0.01 * tok.astype(jnp.float32), tok

    return jax.jit(lambda r: jax.lax.scan(
        body, jnp.zeros((ROWS,)), jax.random.split(r, 2))[1])(rng)


@pytest.mark.parametrize("masked", [False, True], ids=["free", "grammar"])
@pytest.mark.parametrize("mode", ["eager", "jit", "scan"])
@pytest.mark.parametrize("mix", MIXES)
def test_every_tier_returns_the_old_samplers_tokens(mix, mode, masked):
    kw, _ = MIXES[mix]
    logits = the_logits(masked)
    for seed in (0, 3):
        rng = jax.random.PRNGKey(seed)
        np.testing.assert_array_equal(
            np.asarray(run(sample_token_batched, mode, rng, logits, kw)),
            np.asarray(run(reference_sampler, mode, rng, logits, kw)))


@pytest.mark.parametrize("mix", MIXES)
def test_the_rows_flags_choose_the_tier(mix, tiers_run):
    kw, tier = MIXES[mix]
    # the host's reading (numpy, what the engine books) and the device's
    host = {k: np.asarray(v) for k, v in kw.items()}
    assert sampler_tier_name(
        host["greedy"], host["top_k"], host["top_p"]) == tier
    jax.jit(lambda r, x, k: sample_token_batched(r, x, **k))(
        jax.random.PRNGKey(0), the_logits(False), kw)
    assert tiers_run() == [tier]


@pytest.mark.parametrize("neighbour", [
    dict(top_k=5), dict(top_p=0.9), dict(top_p=0.0), dict(top_k=3, top_p=0.5)],
    ids=lambda d: "-".join(f"{k}{v}" for k, v in d.items()))
def test_a_neighbours_filter_does_not_change_a_sampled_rows_token(neighbour):
    """Rows 0–4 sample with a temperature alone; row 5 turns a filter on
    and off. The plane goes from ``plain`` to ``filtered`` and back, and
    rows 0–4 draw the same tokens from the same key."""
    off = rows([0] * 6, T, [0] * 6, [1.0] * 6)
    on = {**off,
          "top_k": off["top_k"].at[5].set(neighbour.get("top_k", 0)),
          "top_p": off["top_p"].at[5].set(neighbour.get("top_p", 1.0))}
    assert int(sampler_tier(off["greedy"], off["top_k"], off["top_p"])) == 1
    assert int(sampler_tier(on["greedy"], on["top_k"], on["top_p"])) == 2
    fn = jax.jit(lambda r, x, k: sample_token_batched(r, x, **k))
    for seed in range(4):
        rng, logits = jax.random.PRNGKey(seed), the_logits(False)
        np.testing.assert_array_equal(np.asarray(fn(rng, logits, off))[:5],
                                      np.asarray(fn(rng, logits, on))[:5])


# ----------------------------------------------------------------- the engine


@pytest.fixture(scope="module")
def model_params():
    cfg = GPTConfig(vocab_size=64, seq_len=192, n_layer=2, n_head=2,
                    embed_dim=32, dropout=0.0, pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    return model, params


SHORT = ([3, 1, 4, 1, 5, 9], [2, 7, 1, 8, 2, 8])
LONG = [(i * 7 + 3) % 64 for i in range(40)]   # 5 chunks of 8
MODES = {
    # one token a dispatch: engine._decode_fn / the paged decode program
    "decode": dict(chunked_prefill=None),
    # a long prompt chunks while the others decode: the fused mixed
    # step, the sampler inside decode_scan's scan body
    "mixed": dict(chunked_prefill=8),
}


def engine_of(model_params, layout, mode):
    model, params = model_params
    return InferenceEngine(model, params, max_slots=4, cache_len=192,
                           cache_dtype=jnp.float32, kv_layout=layout,
                           **MODES[mode])


def serve(eng, mode, sampled):
    """``sampled`` requests decode together; in ``mixed`` mode a long
    prompt with the first request's sampling joins after the first step,
    chunk-prefills beside them and ends with its first token: sampled by
    the paged program that ends its prompt, whose step books the body
    (PR 32), or by the contiguous layout's jitted host sampler, which
    the device sees and no step books. Returns the requests' tokens."""
    reqs = [eng.submit(p, sp) for p, sp in zip(SHORT, sampled)]
    eng.step()
    if mode == "mixed":
        eng.submit(LONG, dataclasses.replace(sampled[0], max_tokens=1))
    while eng.step():
        pass
    return [r.result() for r in reqs]


def booked(eng):
    """The body of every step that dispatched a sampling program."""
    return [r["sampler_tier"] for r in eng.steptrace.records()
            if r["sampler_tier"] is not None]


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_engine_takes_the_tier_its_live_rows_ask_for(
        model_params, layout, mode, tiers_run):
    def fresh():
        return engine_of(model_params, layout, mode)

    def on_device():
        return set(tiers_run())

    # one greedy request on a fresh engine: three rows never held a
    # request (their flag is the initial False) and still the plane is
    # all greedy
    eng = fresh()
    assert not eng._greedy.any()
    serve(eng, mode, [SamplingParams(greedy=True, max_tokens=12)])
    assert set(booked(eng)) == {"argmax"} == set(tiers_run())
    assert (eng.mixed_blocks > 0) == (mode == "mixed")
    tiers_run().clear()

    # two requests with a temperature alone: no sort
    warm = SamplingParams(temperature=0.8, max_tokens=14)
    eng = fresh()
    alone = serve(eng, mode, [warm, SamplingParams(temperature=0.8,
                                                   max_tokens=5)])
    assert set(booked(eng)) == {"plain"} == on_device()
    tiers_run().clear()

    # the neighbour brings a top-k: the sort runs while it lives, and not
    # after it finished, though its row keeps top_k = 5; the first
    # request's tokens do not change
    eng = fresh()
    beside = serve(eng, mode, [warm, SamplingParams(
        temperature=0.8, top_k=5, max_tokens=5)])
    tiers = booked(eng)
    assert tiers[0] == "filtered" and tiers[-1] == "plain"
    assert sorted(set(tiers), key=tiers.index) == ["filtered", "plain"]
    # (in mixed mode the long prompt took the finished row over)
    assert mode == "mixed" or (eng._top_k == 5).any()
    assert on_device() == set(tiers)
    assert beside[0] == alone[0]
    assert beside[1] != alone[1]          # the filter really bit

    # the counters say what the records say
    snap = eng.steptrace.snapshot()["sampler_steps"]
    assert snap == {t: tiers.count(t) for t in ("filtered", "plain")}
    from llm_in_practise_tpu.serve.api import OpenAIServer

    class Tok:
        def encode(self, t):
            return [b % 64 for b in t.encode()][:32]

        def decode(self, ids):
            return " ".join(map(str, ids))

    fams = parse_exposition(
        OpenAIServer(eng, Tok(), model_name="tiers").metrics_text())
    got = {dict(k[1])["tier"]: v
           for k, v in fams["llm_sampler_steps_total"].samples.items()}
    assert got == {"argmax": 0, **snap}


def primitives(jaxpr, skip=()):
    """Names of every primitive in ``jaxpr`` and below, except under the
    equations in ``skip``."""
    out = []
    for eqn in jaxpr.eqns:
        if any(eqn is s for s in skip):
            continue
        out.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += primitives(sub)
    return out


def switches(jaxpr):
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond" and len(eqn.params["branches"]) == 3:
            out.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += switches(sub)
    return out


def test_only_the_filtered_branch_of_the_decode_program_sorts(model_params):
    """The lowered decode program holds the sort inside the switch's
    third branch and nowhere else: greedy and temperature-only traffic
    cannot reach it."""
    eng = engine_of(model_params, "contiguous", "decode")
    jaxpr = jax.make_jaxpr(eng._decode_fn)(
        eng.params, eng.cache, jnp.zeros((4,), jnp.int32),
        jax.random.PRNGKey(0), jnp.ones((4,), jnp.float32),
        jnp.zeros((4,), jnp.int32), jnp.ones((4,), jnp.float32),
        jnp.ones((4,), bool)).jaxpr
    (switch,) = switches(jaxpr)
    argmax, plain, filtered = (
        primitives(b.jaxpr) for b in switch.params["branches"])
    assert "sort" in filtered and "cumsum" in filtered
    assert "sort" not in argmax + plain
    assert "div" not in argmax and "random_bits" not in argmax
    assert "sort" not in primitives(jaxpr, skip=[switch])
