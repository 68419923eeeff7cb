"""SDAR-MoE on the serving path (PR 29): the block-causal mask, the dropless
grouped expert layer, the model against the plain reference
(``benchmark/reference/sdar_moe.py``), and block-diffusion decoding in the
engine — logits at every pass, the reveal rules against a literal
transcription of the family's generate loop, rows at independent phases,
``max_tokens`` / EOS inside a block, the refusals, the counters.

CPU, small sizes, seeded float32 weights (so that the comparison is of the
mathematics and not of bf16 rounding)."""

import http.client
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import sdar_moe as ref
from llm_in_practise_tpu.models.sdar_moe import (
    SDARMoE, SDARMoEConfig, random_params, sdar_moe_config,
)
from llm_in_practise_tpu.ops import attention
from llm_in_practise_tpu.ops.grouped_experts import grouped_expert_ffn, route
from llm_in_practise_tpu.serve.block_step import reveal, reveal_quota
from llm_in_practise_tpu.serve.engine import InferenceEngine, SamplingParams

CFG = sdar_moe_config(compute_dtype="float32")
B = CFG.block_length
GEOM = dict(n_head=CFG.n_head, n_kv_head=CFG.n_kv_head,
            head_dim=CFG.head_dim, rms_norm_eps=CFG.rms_norm_eps,
            rope_theta=CFG.rope_theta, block_length=B,
            top_k=CFG.n_experts_per_tok, norm_topk=CFG.norm_topk_prob)
GREEDY = dict(temperature=0.0, greedy=True)


@pytest.fixture(scope="module")
def params():
    # std 0.2: logits with a spread of ~1.5, so argmaxes are not ties
    return random_params(CFG, 3, jnp.float32, std=0.2)


@pytest.fixture(scope="module")
def model():
    return SDARMoE(CFG)


@pytest.fixture(scope="module")
def reference():
    return ref.Reference(GEOM)


def make_engine(model, params, **kw):
    kw = {"max_slots": 4, "cache_len": 64, "kv_layout": "paged",
          "cache_dtype": jnp.float32, "chunked_prefill": 16,
          "prefill_buckets": (8, 16, 32), **kw}
    return InferenceEngine(model, params, **kw)


def drain(engine):
    while engine.step():
        pass


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size - 1, n).tolist()


# ------------------------------------------------------------------ the mask


@pytest.mark.parametrize("block", [2, 4])
@pytest.mark.parametrize("offset", [None, 8, "vector"])
def test_block_causal_mask_is_its_definition(block, offset):
    q_len, kv_len = (12, 12) if offset is None else (4, 16)
    q_offset = jnp.asarray([8, 4]) if offset == "vector" else offset
    got = np.asarray(attention.causal_mask(q_len, kv_len, q_offset=q_offset,
                                           block=block)) == 0.0
    starts = ([8, 4] if offset == "vector"
              else [kv_len - q_len if offset is None else offset])
    for b, start in enumerate(starts):
        for i in range(q_len):
            for j in range(kv_len):
                assert got[b, 0, i, j] == (j // block <= (start + i) // block)


@pytest.mark.parametrize("offset", [None, 3, "vector"])
def test_block_one_is_todays_mask_bit_for_bit(offset):
    q_offset = jnp.asarray([0, 5, 9]) if offset == "vector" else offset
    old = attention.causal_mask(6, 16, q_offset=q_offset)
    new = attention.causal_mask(6, 16, q_offset=q_offset, block=1)
    assert np.array_equal(np.asarray(old), np.asarray(new))
    # and the traced program is the same one
    f = lambda b: jax.make_jaxpr(  # noqa: E731
        lambda: attention.causal_mask(6, 16, q_offset=q_offset, **b))()
    assert str(f({})) == str(f({"block": 1}))


# ---------------------------------------------------------- the expert layer


def expert_loop(x, ids, weights, w_gate, w_up, w_down):
    out = np.zeros_like(x)
    for n in range(x.shape[0]):
        for j in range(ids.shape[1]):
            e = ids[n, j]
            g = x[n] @ w_gate[e]
            h = (g / (1 + np.exp(-g))) * (x[n] @ w_up[e])
            out[n] += weights[n, j] * (h @ w_down[e])
    return out


@pytest.mark.parametrize("routing", ["uniform", "two_experts", "one_empty",
                                     "ragged_rows"])
def test_grouped_experts_equal_a_per_token_loop(routing):
    rng = np.random.default_rng(5)
    n, k, e, h, w = (13 if routing == "ragged_rows" else 24), 2, 8, 32, 16
    x = rng.standard_normal((n, h)).astype(np.float32)
    wg, wu = (rng.standard_normal((e, h, w)).astype(np.float32) * 0.2
              for _ in range(2))
    wd = rng.standard_normal((e, w, h)).astype(np.float32) * 0.2
    if routing == "two_experts":        # every token to experts 2 and 5
        ids = np.tile(np.array([[2, 5]], np.int32), (n, 1))
    elif routing == "one_empty":        # expert 3 gets nothing
        ids = rng.choice([0, 1, 2, 4, 5, 6, 7], (n, k)).astype(np.int32)
    else:
        ids = np.stack([rng.permutation(e)[:k] for _ in range(n)]).astype(
            np.int32)
    weights = rng.random((n, k)).astype(np.float32)
    got = np.asarray(grouped_expert_ffn(
        jnp.asarray(x), jnp.asarray(ids), jnp.asarray(weights),
        jnp.asarray(wg), jnp.asarray(wu), jnp.asarray(wd)))
    want = expert_loop(x, ids, weights, wg, wu, wd)
    # nothing dropped: every token's every assignment is in the sum
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_router_is_softmax_topk_renormalised():
    rng = np.random.default_rng(7)
    x = rng.standard_normal((9, 32)).astype(np.float32)
    wr = rng.standard_normal((32, 8)).astype(np.float32)
    ids, weights = route(jnp.asarray(x), jnp.asarray(wr), 3)
    z = x @ wr
    p = np.exp(z - z.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    want = np.argsort(-p, axis=1)[:, :3]
    assert np.array_equal(np.sort(np.asarray(ids), 1), np.sort(want, 1))
    np.testing.assert_allclose(np.asarray(weights).sum(1), 1.0, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(weights),
        np.take_along_axis(p, np.asarray(ids), 1)
        / np.take_along_axis(p, np.asarray(ids), 1).sum(1, keepdims=True),
        rtol=1e-4)


# ------------------------------------------------------------------ the model


def test_config_reads_the_published_keys():
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "sdar-30b-a3b-bf16-serve.json")) as f:
        hf = json.load(f)
    cfg = SDARMoEConfig.from_hf_config(hf)
    assert (cfg.hidden_size, cfg.n_head, cfg.n_kv_head, cfg.head_dim) == (
        2048, 32, 4, 128)
    assert (cfg.n_experts, cfg.n_experts_per_tok,
            cfg.moe_intermediate_size) == (128, 8, 768)
    assert (cfg.block_length, cfg.mask_token_id, cfg.denoising_steps,
            cfg.remasking) == (4, 151669, 4, "low_confidence_static")
    assert cfg.attn_cfg.attn_block == 4 and cfg.norm_topk_prob
    with pytest.raises(ValueError, match="every layer sparse"):
        SDARMoEConfig.from_hf_config(dict(hf, decoder_sparse_step=2))
    with pytest.raises(ValueError, match="denoising_steps"):
        sdar_moe_config(denoising_steps=5)


def test_model_forward_matches_the_plain_reference(model, params, reference):
    ids = prompt_of(22)
    got = np.asarray(model.apply({"params": params}, jnp.asarray([ids])))[0]
    want, _ = reference.logits(params, ids, last=len(ids))
    assert np.abs(got - want).max() <= 1e-4 * np.std(want)


# ----------------------------------------------------- the engine's block step


def replay_against_reference(engine, reference, params, requests):
    """Every captured pass: the block program's logits against the
    reference's full forward of the block as that pass saw it."""
    worst = 0.0
    for c in engine.block.capture:
        req, prompt = requests[c["uid"]]
        seq, _ = ref.block_inputs(prompt, req.reveal_log, B,
                                  CFG.mask_token_id, c["block"], c["pass"])
        want, found = reference.logits(params, seq, last=B,
                                       engine_experts=c["experts"])
        assert found["flipped"] == 0
        worst = max(worst, np.abs(want - c["logits"]).max() / np.std(want))
    return worst


@pytest.mark.parametrize("plen", [5, 14, 22, 43])
def test_prefill_then_block_decode_equals_the_reference_at_every_pass(
        model, params, reference, plen):
    """Prompt lengths that are no multiple of B; 22 and 43 exceed the
    chunk (16) and prefill in chunks, 5 and 14 in one shot."""
    engine = make_engine(model, params)
    engine.block.capture = []
    prompt = prompt_of(plen, seed=plen)
    req = engine.submit(prompt, SamplingParams(max_tokens=9, **GREEDY))
    drain(engine)
    assert req.finish_reason == "length" and req.n_generated == 9
    assert len(engine.block.capture) == req.block_passes > 0
    assert replay_against_reference(
        engine, reference, params, {req.uid: (req, prompt)}) <= 1e-4



def test_capture_fetches_from_the_one_program(model, params):
    """A reference comparison reads the logits of the SAME executable
    that serves: switching ``capture`` on builds nothing, and the run it
    records is the run an uncaptured engine makes."""
    prompt = prompt_of(14, seed=2)
    sp = SamplingParams(max_tokens=10, **GREEDY)
    engine = make_engine(model, params)
    plain = engine.submit(prompt, sp)
    drain(engine)
    built = engine.compile_meter.compile_events
    engine.block.capture = []
    seen = engine.submit(prompt, sp)
    drain(engine)
    assert engine.compile_meter.compile_events == built
    assert len(engine.block.capture) == seen.block_passes
    assert seen.reveal_log == plain.reveal_log
    assert list(seen.tokens.queue) == list(plain.tokens.queue)


@pytest.mark.parametrize("greedy,tier", [(True, "argmax"), (False, "filtered")])
def test_idle_rows_do_not_send_a_greedy_plane_to_the_sampler(
        model, params, tiers_run, greedy, tier):
    """``engine._greedy`` starts False and is written at activation only:
    one greedy request on a fresh 4-slot engine must still take the
    sampler's ``argmax`` body (the block program hands idle rows to the
    shared switch of ``infer/sampling.py`` as greedy, so the
    full-vocabulary sort is decided on LIVE rows); a request with a top-k
    takes ``filtered``. The step records say the same."""
    engine = make_engine(model, params)
    sp = (SamplingParams(max_tokens=5, **GREEDY) if greedy
          else SamplingParams(max_tokens=5, temperature=0.8, top_k=5))
    req = engine.submit(prompt_of(9, seed=1), sp)
    drain(engine)
    assert req.n_generated == 5 and engine._greedy.sum() == int(greedy)
    assert set(tiers_run()) == {tier}
    booked = {r["sampler_tier"] for r in engine.steptrace.records()}
    assert booked - {None} == {tier}
    assert engine.steptrace.snapshot()["sampler_steps"] == {
        tier: engine.block.passes}


def generate_loop(model, params, prompt, n_new, steps, dynamic, threshold):
    """A literal transcription of the family's block_diffusion_generate,
    greedy: full forward passes, no cache."""
    mask = CFG.mask_token_id
    whole = len(prompt) // B * B
    x = list(prompt) + [mask] * (B - (len(prompt) - whole))
    out, passes = [], 0
    while len(out) < n_new:
        start = len(x) - B
        for step in range(steps + 1):
            masked = [j for j in range(B) if x[start + j] == mask]
            logits = np.asarray(model.apply(
                {"params": params}, jnp.asarray([x])))[0, start:]
            passes += 1
            if not masked:
                break                               # the commit pass
            z = logits - logits.max(1, keepdims=True)
            p = np.exp(z) / np.exp(z).sum(1, keepdims=True)
            cand, conf = p.argmax(1), p.max(1)
            quota = reveal_quota(B, steps, step)
            high = [j for j in masked if conf[j] > threshold]
            if dynamic and len(high) >= quota:
                chosen = high
            else:
                chosen = sorted(masked, key=lambda j: -conf[j])[:quota]
            for j in chosen:
                x[start + j] = int(cand[j])
        out += x[max(start, len(prompt)):]
        x += [mask] * B
    return out[:n_new], passes


@pytest.mark.parametrize("rule,steps,threshold", [
    ("low_confidence_static", 4, 0.9), ("low_confidence_static", 2, 0.9),
    ("low_confidence_dynamic", 4, 0.02), ("low_confidence_dynamic", 4, 0.9)])
def test_reveal_rules_equal_the_generate_loop(model, params, rule, steps,
                                              threshold):
    prompt = prompt_of(10, seed=2)          # no mask id inside
    want, want_passes = generate_loop(
        model, params, prompt, 10, steps,
        rule == "low_confidence_dynamic", threshold)
    engine = make_engine(model, params)
    req = engine.submit(prompt, SamplingParams(
        max_tokens=10, denoising_steps=steps, remasking=rule,
        confidence_threshold=threshold, **GREEDY))
    drain(engine)
    assert list(req.tokens.queue)[:-1] == want
    assert req.block_passes == want_passes
    if rule == "low_confidence_dynamic" and threshold < 0.5:
        # the threshold did reveal several positions in one pass
        assert req.block_passes < (steps + 1) * 3


def test_reveal_rule_on_hand_made_confidences():
    cand = jnp.asarray([[11, 12, 13, 14]] * 3)
    conf = jnp.asarray([[0.1, 0.95, 0.3, 0.92]] * 3)
    tokens = jnp.asarray([[7, 0, 0, 0]] * 3)
    revealed = jnp.asarray([[True, False, False, False]] * 3)
    new_tok, new_rev = reveal(
        cand, conf, tokens, revealed, quota=jnp.asarray([1, 1, 2]),
        threshold=jnp.asarray([0.9, 0.9, 0.99]),
        dynamic=jnp.asarray([False, True, True]))
    # static: the one most confident; dynamic: both above 0.9; dynamic with
    # too few above 0.99: the two most confident
    assert np.asarray(new_rev).tolist() == [
        [True, True, False, False], [True, True, False, True],
        [True, True, False, True]]
    assert np.asarray(new_tok)[1].tolist() == [7, 12, 0, 14]


def test_rows_at_different_phases_equal_each_row_alone(model, params):
    prompts = [prompt_of(n, seed=n) for n in (6, 13, 19)]
    sp = SamplingParams(max_tokens=11, **GREEDY)
    alone = []
    for p in prompts:
        engine = make_engine(model, params)
        r = engine.submit(p, sp)
        drain(engine)
        alone.append(list(r.tokens.queue)[:-1])
    engine = make_engine(model, params)
    reqs = [engine.submit(prompts[0], sp)]
    for p in prompts[1:]:
        engine.step()
        engine.step()                   # the earlier rows are mid-block
        reqs.append(engine.submit(p, sp))
    phases = set()
    while engine.step():
        phases.add(tuple(engine.block.passes_in_block[:3].tolist()))
    assert any(len(set(ph)) > 1 for ph in phases)   # really out of phase
    assert [list(r.tokens.queue)[:-1] for r in reqs] == alone


def test_max_tokens_and_eos_cut_inside_a_block(model, params):
    prompt = prompt_of(9, seed=4)
    engine = make_engine(model, params)
    full = engine.submit(prompt, SamplingParams(max_tokens=12, **GREEDY))
    drain(engine)
    tokens = list(full.tokens.queue)[:-1]
    assert len(tokens) == 12 and full.finish_reason == "length"
    engine = make_engine(model, params)
    cut = engine.submit(prompt, SamplingParams(max_tokens=6, **GREEDY))
    drain(engine)
    assert list(cut.tokens.queue)[:-1] == tokens[:6]    # mid-block
    assert cut.finish_reason == "length" and cut.n_generated == 6
    # EOS: the first token that appears no earlier, at a position inside
    # a block of the output (9 = 2 whole blocks + 1: outputs 3.. are
    # block-aligned at 3, 7, 11)
    at = next(i for i in (4, 5, 8, 9, 1) if tokens[i] not in tokens[:i])
    engine = make_engine(model, params, eos_id=tokens[at])
    stop = engine.submit(prompt, SamplingParams(max_tokens=12, **GREEDY))
    drain(engine)
    assert list(stop.tokens.queue)[:-1] == tokens[:at]
    assert stop.finish_reason == "stop"
    assert engine.block.tokens_committed == at


def test_a_prompt_holding_the_mask_id_is_served_like_any_other(
        model, params, reference):
    """The engine keeps the revealed flags itself: the mask id inside the
    prompt's whole blocks AND in its remainder is an ordinary token."""
    m = CFG.mask_token_id
    prompt = prompt_of(10, seed=8)
    prompt[2] = prompt[5] = prompt[9] = m       # 9 is in the remainder
    engine = make_engine(model, params)
    engine.block.capture = []
    req = engine.submit(prompt, SamplingParams(max_tokens=6, **GREEDY))
    drain(engine)
    assert req.n_generated == 6
    # the first block opened with 2 revealed positions, so it took 2
    # denoise passes + 1 commit pass, not 4 + 1
    assert [c["pass"] for c in engine.block.capture
            if c["block"] == 0] == [0, 1, 2]
    assert replay_against_reference(
        engine, reference, params, {req.uid: (req, prompt)}) <= 1e-4


class _Store:
    def attach(self, engine):
        pass

    def close(self):
        pass


@pytest.mark.parametrize("kw,match", [
    ({"kv_layout": "contiguous"}, "kv_layout='contiguous'"),
    ({"speculative_k": 2}, "speculative decoding"),
    ({"prefix_cache": True}, "prefix caching"),
    ({"session_store": _Store()}, "session store"),
    ({"kv_pool_tokens": 128}, "a page pool of 128 tokens"),
    ({"chunked_prefill": 6}, "chunked_prefill=6"),
    ({"cache_len": 62}, "cache_len=62"),
    ({"prefill_buckets": (6, 16)}, "prefill bucket 6"),
])
def test_build_time_refusals_say_why(model, params, kw, match):
    with pytest.raises(ValueError, match="block-diffusion model.*" + match):
        make_engine(model, params, **kw)


def test_multi_lora_is_refused(model, params):
    from llm_in_practise_tpu.serve.multi_lora import AdapterRegistry

    with pytest.raises(ValueError, match="multi-LoRA"):
        make_engine(model, params, adapter_registry=AdapterRegistry(params))


@pytest.mark.parametrize("sp,kw,match", [
    ({"constraint": object()}, {}, "grammar-constrained"),
    ({}, {"adapter": "a"}, "LoRA adapters"),
    ({}, {"session_id": "s"}, "sessions"),
    ({}, {"handoff_id": "h"}, "handed-off KV"),
    ({"denoising_steps": 5}, {}, "denoising_steps must be in"),
    ({"denoising_steps": 0}, {}, "denoising_steps must be in"),
    ({"remasking": "random"}, {}, "remasking must be one of"),
])
def test_submit_time_refusals_say_why(model, params, sp, kw, match):
    engine = make_engine(model, params)
    with pytest.raises(ValueError, match=match):
        engine.submit(prompt_of(6), SamplingParams(**sp), **kw)
    assert engine.pending.qsize() == 0


def test_counters_add_up(model, params):
    engine = make_engine(model, params)
    sp = SamplingParams(max_tokens=10, **GREEDY)
    reqs = [engine.submit(prompt_of(n, seed=n), sp) for n in (7, 12, 18, 21)]
    drain(engine)
    blk = engine.block
    streamed = sum(len(list(r.tokens.queue)) - 1 for r in reqs)
    assert blk.row_passes == sum(r.block_passes for r in reqs)
    assert blk.tokens_committed == streamed == 40
    assert blk.tokens_revealed == sum(len(r.reveal_log) for r in reqs)
    records = engine.steptrace.records()
    assert sum(r["block_rows"] for r in records) == blk.row_passes
    assert sum(r["block_commits"] for r in records) == blk.blocks_committed
    assert sum(r["tokens_committed"] for r in records) == streamed
    assert sum(r["tokens_revealed"] for r in records) == blk.tokens_revealed
    # expert load: every pass routes the whole plane through every layer
    per_pass = engine.max_slots * B * CFG.n_experts_per_tok * CFG.n_layer
    assert blk.routing.assignments == blk.passes * per_pass
    assert (blk.passes * CFG.n_layer <= blk.routing.experts_touched
            <= blk.passes * CFG.n_layer * CFG.n_experts)
    assert blk.routing.max_load * CFG.n_experts >= blk.routing.assignments
    assert blk.routing.mean_load == pytest.approx(
        blk.routing.assignments / CFG.n_experts)
    # every dispatch window is booked to the requests that rode it
    assert all(r.cp.get("decode_dispatch", 0.0) > 0 for r in reqs)


def test_a_pass_blocks_in_one_fetch_and_requests_carry_the_thread_states(
        model, params):
    """PR 41, PR 42: every pass is read inside ONE ``fetch:decode``
    segment, the capture's logits included, by the step that issued it
    or (a pass that ran ahead: most) by the one after; a record's wall is
    cpu + blocked + stalled, and every finished request carries the four
    ``engine_*`` overlays, which sum."""
    from tests.thread_state_checks import (
        check_records,
        check_requests,
        spy_window_closes,
    )

    engine = make_engine(model, params)
    engine.block.capture = []
    closed = spy_window_closes(engine)
    sp = SamplingParams(max_tokens=10, **GREEDY)
    reqs = [engine.submit(prompt_of(n, seed=n), sp) for n in (7, 12, 18, 21)]
    drain(engine)
    records = engine.steptrace.records()
    check_records(records, closed)
    check_requests(list(engine.finished))
    assert len(engine.finished) == len(reqs)
    passes = [r for r in records if r["block_rows"]]
    assert len(passes) == engine.block.passes
    for r in passes:
        assert r["read_seq"] in (r["seq"], r["seq"] - 1)
        assert [n for n, _, _ in r["segments"]].count("fetch:decode") == 1
    behind = [r for r in passes if r["read_seq"] == r["seq"] - 1]
    assert sum(r["ahead"] for r in behind) >= 0.6 * len(passes)
    snap = engine.steptrace.snapshot()
    assert sum(snap["thread_seconds"].values()) == pytest.approx(
        snap["step_wall_seconds_total"])


def test_sse_streams_blocks_and_metrics_render(model, params):
    from llm_in_practise_tpu.serve.api import OpenAIServer
    from tests.test_serve_api import ByteTokenizer

    engine = make_engine(model, params, cache_len=256)
    srv = OpenAIServer(engine, ByteTokenizer(), model_name="sdar-test")
    port = srv.serve(host="127.0.0.1", port=0, background=True)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        conn.request("POST", "/v1/chat/completions", json.dumps({
            "model": "sdar-test", "stream": True, "temperature": 0.0,
            "max_tokens": 10,
            "messages": [{"role": "user", "content": "hello"}]}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read().decode()
        conn.close()
        assert resp.status == 200 and "[DONE]" in body
        events = [json.loads(line[5:]) for line in body.splitlines()
                  if line.startswith("data:") and "[DONE]" not in line]
        assert events[-1]["choices"][0]["finish_reason"] == "length"
        assert engine.block.tokens_committed == 10
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
        conn.close()
        for family in ("llm_block_passes_total", "llm_block_row_passes_total",
                       "llm_blocks_committed_total",
                       "llm_block_tokens_committed_total",
                       "llm_moe_assignments_total",
                       "llm_moe_experts_touched_total",
                       "llm_moe_max_expert_load_total",
                       "llm_moe_mean_expert_load_total"):
            assert f"\n{family} " in text, family
        # structured output is refused by the front end
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("POST", "/v1/chat/completions", json.dumps({
            "model": "sdar-test", "max_tokens": 4,
            "response_format": {"type": "json_object"},
            "messages": [{"role": "user", "content": "x"}]}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 422 and b"block-diffusion" in resp.read()
        conn.close()
    finally:
        srv.shutdown()


def test_benchmark_tokenizer_reaches_letter_run_pieces():
    """The block cell's tokenizer (benchmark/runners/serve_block_cell.py):
    every id still has exactly one piece, text round-trips, and the
    benchmark's random-letter prompts reach hundreds of distinct ids
    where the stock tokenizer spells them letter by letter."""
    from benchmark import serving, traffic
    from benchmark.runners.serve_block_cell import letter_run_tokenizer

    tok = letter_run_tokenizer(8192)
    assert sorted(tok.vocab.values()) == list(range(8192))
    assert tok.decode(tok.encode("qzx hello")) == "qzx hello"
    stock = serving.full_vocab_tokenizer(8192)

    def distinct(t):
        writer = traffic.PromptWriter(t, serving.render, 5)
        ids = [i for n in (48, 96, 200, 256)
               for i in t.encode(serving.render(writer.write(n)))]
        return len(set(ids))

    assert distinct(tok) > 300 > 60 > distinct(stock)
