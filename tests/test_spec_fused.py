"""Fused speculative decode (ISSUE 9 / ROADMAP item 4).

The engine verifies the k drafted tokens inside ONE jitted dispatch
(``serve/mixed_step.spec_verify_block``): acceptance is computed on
device and the index fixup is folded in. These tests pin:

- golden-token parity: fused spec ≡ plain greedy across
  {contiguous, paged} × {ngram, draft-model};
- a ``role="decode"`` engine keeps speculating while a (degraded)
  local prefill is in flight;
- preemption-mid-burst (paged): pool-pressure preemption between spec
  rounds still yields byte-identical streams;
- draft-cache admission math (paged): an explicit page budget is
  reduced by the contiguous draft cache's byte-equivalent tokens;
- the disagg handoff path with speculation on the decode replica;
- the spec-ladder bench's CPU smoke
  (``tools/spec_ladder_bench.run_ladder``).
"""

import jax
import jax.numpy as jnp
import pytest

from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
from llm_in_practise_tpu.serve.disagg import (
    DECODE_DEFAULT_SPEC_K,
    LocalHandoff,
    default_speculative_k,
    new_handoff_id,
)
from llm_in_practise_tpu.serve.engine import InferenceEngine, SamplingParams


@pytest.fixture(scope="module")
def model_params():
    cfg = GPTConfig(vocab_size=64, seq_len=192, n_layer=2, n_head=2,
                    embed_dim=32, dropout=0.0, pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    return model, params


def _engine(model, params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("cache_len", 192)
    kw.setdefault("cache_dtype", jnp.float32)
    return InferenceEngine(model, params, **kw)


REPETITIVE = [1, 2, 3, 4, 5] * 6
LONG = [(i * 7 + 3) % 64 for i in range(40)]
SP = SamplingParams(greedy=True, max_tokens=40)


# --- golden parity -----------------------------------------------------------


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
@pytest.mark.parametrize("proposer", ["ngram", "draft"])
def test_fused_spec_parity(model_params, layout, proposer):
    """Spec on ≡ spec off (greedy), both KV layouts, both proposers.
    The draft leg
    uses the target itself as draft — every proposal is the exact
    greedy continuation, so acceptance is total and the fused commit
    path is exercised at full width deterministically."""
    model, params = model_params
    ref = _engine(model, params).generate(REPETITIVE, SP)
    kw = dict(speculative_k=4)
    if layout == "paged":
        kw["kv_layout"] = "paged"
    if proposer == "draft":
        kw.update(draft_model=model, draft_params=params)
    spec = _engine(model, params, **kw)
    assert spec.generate(REPETITIVE, SP) == ref
    assert spec.spec_rounds > 0
    # committed tokens per spec dispatch strictly beat one-token
    # dispatches
    assert spec.spec_round_tokens / spec.spec_rounds > 1.0
    if proposer == "draft":
        # target-as-draft: every drafted token is accepted
        assert spec.spec_accepted == spec.spec_proposed > 0
    if layout == "paged":
        spec.paged.pool.check_leaks(
            0 if spec.prefix_cache is None
            else spec.prefix_cache.n_entries)


def test_fused_spec_parity_interleaved_slots(model_params):
    """Several greedy streams over fewer slots, ngram + paged: every
    stream equals its isolated plain run."""
    model, params = model_params
    prompts = [REPETITIVE, [2, 9] * 10, LONG[:20]]
    plain = _engine(model, params, max_slots=1)
    refs = []
    plain.start()
    for p in prompts:
        refs.append(plain.submit(p, SP).result())
    plain.stop()
    spec = _engine(model, params, max_slots=2, kv_layout="paged",
                   speculative_k=3)
    spec.start()
    outs = [h.result() for h in
            [spec.submit(p, SP) for p in prompts]]
    spec.stop()
    assert outs == refs


# --- speculation beside a local prefill -------------------------------------


def test_decode_role_speculates_beside_a_local_prefill(model_params):
    """On role='decode' spec rounds keep landing WHILE a degraded local
    prefill is in flight, and outputs equal the plain decode-role
    engine's."""
    model, params = model_params

    def run(eng):
        h = eng.submit(REPETITIVE, SamplingParams(greedy=True,
                                                  max_tokens=30))
        eng.step()
        hl = eng.submit(LONG, SamplingParams(greedy=True, max_tokens=8))
        mid_prefill_rounds = 0
        while True:
            before = getattr(eng, "spec_rounds", 0)
            busy = eng.step()
            if eng.slot_prefill and getattr(eng, "spec_rounds", 0) > before:
                mid_prefill_rounds += 1
            if not busy:
                break
        return [h.result(), hl.result()], mid_prefill_rounds

    ref, _ = run(_engine(model, params, role="decode",
                         chunked_prefill=8))
    # target-as-draft: proposals flow EVERY round, so the while-prefill
    # composition is observed deterministically
    spec = _engine(model, params, role="decode", chunked_prefill=8,
                   speculative_k=3, draft_model=model, draft_params=params)
    out, mid_rounds = run(spec)
    assert out == ref
    assert mid_rounds > 0                    # spec ran DURING prefill
    assert spec.spec_rounds > 0


# --- preemption mid-burst (paged) -------------------------------------------


def test_preemption_mid_spec_burst_exact_streams(model_params):
    """Pool sized for ~2 of 3 requests while fused spec rounds write
    k+1 rows per reservation: preemption must fire BETWEEN rounds
    and every stream still equals the unconstrained plain run (the
    recompute-resume path neither drops nor re-samples, and the
    preempted slot's draft watermark resets)."""
    model, params = model_params
    prompts = [[(j * 3 + i) % 64 for i in range(20)] for j in range(3)]
    # 864 budget − 768 draft-cache equivalent = 96 usable pool tokens:
    # the same pressure regime as test_paged_kv's preemption test, with
    # the draft deduction (this PR's admission satellite) in the loop
    t = _engine(model, params, kv_layout="paged", kv_pool_tokens=864,
                prefix_cache=True, speculative_k=3,
                draft_model=model, draft_params=params)
    rs = [t.submit(p, SP) for p in prompts]
    while t.step():
        pass
    outs = [r.result() for r in rs]
    assert t.preemptions > 0
    assert t.spec_rounds > 0
    plain = _engine(model, params)
    for p, out, r in zip(prompts, outs, rs):
        assert r.finish_reason in ("length", "stop")
        assert out == plain.generate(p, SP)
    t.prefix_cache.clear()
    t.paged.pool.check_leaks(0)


# --- draft cache in the paged admission math --------------------------------


def test_draft_cache_deducts_from_explicit_page_budget(model_params):
    """With a draft model and an explicit kv_pool_tokens, the page pool
    shrinks by the draft cache's byte-equivalent tokens (the draft and
    target here are the same model: equivalent tokens = max_slots *
    cache_len exactly), /debug/kv reports the reservation, and a
    budget the draft eats entirely raises at construction."""
    from llm_in_practise_tpu.serve.paged_kv import kv_row_bytes, pages_for

    model, params = model_params
    no_draft = _engine(model, params, kv_layout="paged",
                       kv_pool_tokens=2048)
    drafted = _engine(model, params, kv_layout="paged",
                      kv_pool_tokens=2048, speculative_k=3,
                      draft_model=model, draft_params=params)
    reserved = drafted.draft_kv_reserved_tokens
    assert reserved == drafted.max_slots * drafted.cache_len
    assert (kv_row_bytes(model, jnp.float32)
            == kv_row_bytes(model, jnp.float32))   # deterministic probe
    assert (drafted.paged.pool.capacity
            == no_draft.paged.pool.capacity
            - pages_for(reserved, drafted.paged.page_size))
    assert drafted.debug_kv()["draft_kv_reserved_tokens"] == reserved
    # the DEFAULT pool size keeps worst-case semantics: no deduction
    default_pool = _engine(model, params, kv_layout="paged",
                           speculative_k=3, draft_model=model,
                           draft_params=params)
    assert default_pool.draft_kv_reserved_tokens == 0
    # parity still holds on the shrunken pool
    assert (drafted.generate(REPETITIVE, SP)
            == _engine(model, params).generate(REPETITIVE, SP))
    with pytest.raises(ValueError, match="draft cache"):
        _engine(model, params, kv_layout="paged", kv_pool_tokens=768,
                speculative_k=3, draft_model=model, draft_params=params)


# --- disagg handoff with a speculating decode replica -----------------------


def test_handoff_to_speculating_decode_replica(model_params):
    """The production shape this PR defaults to: prefill replica hands
    KV off, the decode replica speculates over the claimed slot —
    tokens equal the plain role-both engine's, zero local prefills."""
    model, params = model_params
    prompt = REPETITIVE
    ref = _engine(model, params).generate(prompt, SP)
    store = LocalHandoff()
    pre = _engine(model, params, role="prefill", handoff=store)
    dec = _engine(model, params, role="decode", speculative_k=4,
                  kv_layout="paged")
    hid = new_handoff_id()
    h = pre.submit(prompt, SP, handoff_id=hid)
    while pre.step():
        pass
    assert h.result() == [] and h.finish_reason == "handoff"
    host = store.claim(hid)
    assert host is not None
    h2 = dec.submit(prompt, SP, kv_entry=host)
    while dec.step():
        pass
    assert h2.result() == ref
    assert dec.spec_rounds > 0
    assert dec.local_prefills == 0


# --- CLI default -------------------------------------------------------------


def test_default_speculative_k_policy():
    assert default_speculative_k("decode", None) == DECODE_DEFAULT_SPEC_K
    assert default_speculative_k("decode", 0) is None    # explicit opt-out
    assert default_speculative_k("decode", 6) == 6
    assert default_speculative_k("both", None) is None
    assert default_speculative_k("prefill", None) is None
    assert default_speculative_k("both", 0) is None


# --- spec ladder bench smoke -------------------------------------------------


def test_spec_ladder_smoke(tmp_path):
    """The BENCH_SPEC_LADDER artifact's CPU smoke: reduced training and
    request counts, structure + the tokens-per-spec-dispatch gate (> 1
    by construction of the fused round)."""
    from tools.spec_ladder_bench import run_ladder

    artifact = run_ladder(train_steps=40, n_requests=6, max_tokens=24,
                          concurrencies=(1,),
                          out_path=str(tmp_path / "ladder.json"))
    assert set(artifact["legs"]) == {"off", "ngram", "draft"}
    assert artifact["legs"]["off"]["spec_rounds"] == 0
    for leg in ("ngram", "draft"):
        d = artifact["legs"][leg]
        assert d["spec_rounds"] > 0
        assert d["tokens_per_spec_dispatch"] > 1.0
    assert "conc1_tpot_p50_ms" in artifact
