"""Constrained decoding (serve/constrain.py, ISSUE 12): grammar unit
tests, schema-conformance fuzz (every emitted completion parses AND
validates), the {contiguous,paged} x {spec off,ngram} x mixed-step
composition matrix with the 1-dispatch-per-step invariant, preemption-
resume byte-identical streams under an active grammar, and the OpenAI
``response_format`` / ``tools`` surface (422 on invalid schemas)."""

import http.client
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
from llm_in_practise_tpu.serve import constrain
from llm_in_practise_tpu.serve.engine import InferenceEngine, SamplingParams

VOCAB = 128


class CharTok:
    """One printable-ASCII char = one token — grammar masks are exact."""

    def encode(self, text: str) -> list[int]:
        return [min(ord(c), VOCAB - 1) for c in text]

    def decode(self, ids) -> str:
        return "".join(chr(int(i) % VOCAB) for i in ids)


TOK = CharTok()
VOCAB_STRS = constrain.vocab_strings(TOK, VOCAB)

SCHEMA = {
    "type": "object",
    "properties": {
        "name": {"type": "string", "minLength": 1, "maxLength": 8},
        "age": {"type": "integer"},
        "tags": {"type": "array", "items": {"enum": ["a", "b", "c"]},
                 "minItems": 1, "maxItems": 3},
    },
    "required": ["name", "age", "tags"],
}

FUZZ_SCHEMAS = [
    SCHEMA,
    {"type": "object",
     "properties": {"ok": {"type": "boolean"},
                    "score": {"type": "number"}},
     "required": ["ok", "score"]},
    {"type": "object",
     "properties": {"code": {"type": "string",
                             "pattern": "[A-Z]{2}[0-9]{3}"},
                    "null_or_int": {"anyOf": [{"type": "null"},
                                              {"type": "integer"}]}},
     "required": ["code", "null_or_int"]},
    {"type": "object",
     "properties": {"inner": {"type": "object",
                              "properties": {"v": {"const": "x"}},
                              "required": ["v"]},
                    "xs": {"type": "array",
                           "items": {"type": "integer"},
                           "minItems": 2, "maxItems": 4}},
     "required": ["inner", "xs"]},
]


@pytest.fixture(scope="module")
def model_params():
    cfg = GPTConfig(vocab_size=VOCAB, seq_len=256, n_layer=2, n_head=2,
                    embed_dim=32, dropout=0.0, pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    return model, params


def _engine(model, params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("cache_len", 256)
    kw.setdefault("cache_dtype", jnp.float32)
    return InferenceEngine(model, params, **kw)


def _automaton(schema=None, kind_rf=None, eos_id=None):
    rf = kind_rf or {"type": "json_schema",
                     "json_schema": {"schema": schema or SCHEMA}}
    return constrain.compile_request_constraint(
        response_format=rf, vocab=VOCAB_STRS, eos_id=eos_id)


PROMPT = TOK.encode("emit json now: ")


# --- grammar core ------------------------------------------------------------


def test_regex_core_membership():
    auto = constrain.TokenAutomaton(
        constrain.compile_regex("ab+(c|d)[0-9]{2}"), VOCAB_STRS,
        eos_id=None)

    def accepts(text, *, complete):
        cur = auto.start
        for ch in text:
            nxt = auto.step(cur, ord(ch))
            if nxt is None:
                return False
            cur = nxt
        return constrain.is_accepting(cur) if complete else True

    assert accepts("abbc07", complete=True)
    assert accepts("abd99", complete=True)
    assert not accepts("ac", complete=False)      # b required
    assert not accepts("abc0", complete=True)     # needs two digits
    assert not accepts("abc007", complete=False)  # at most two


def test_regex_unsupported_syntax_rejected():
    for bad in ("a(", "a[", "*a", "a{2", "a(?=b)"):
        with pytest.raises(constrain.ConstraintError):
            constrain.compile_regex(bad)


def test_unsupported_schema_keywords_rejected():
    for bad in (
        {"type": "integer", "minimum": 3},
        {"type": "object", "minProperties": 1},
        {"type": "string", "format": "date-time"},
        {"oneOf": [{"type": "integer"}]},
        {"type": "frobnicate"},
    ):
        with pytest.raises(constrain.ConstraintError):
            constrain.compile_schema(bad)


def test_validate_instance_spot_checks():
    assert constrain.validate_instance(
        {"name": "x", "age": 3, "tags": ["a"]}, SCHEMA)
    assert not constrain.validate_instance(
        {"name": "x", "age": "3", "tags": ["a"]}, SCHEMA)
    assert not constrain.validate_instance(
        {"name": "x", "age": 3, "tags": []}, SCHEMA)
    assert not constrain.validate_instance({"age": 3}, SCHEMA)


def test_eos_only_at_accepting_states():
    auto = _automaton(schema={"type": "integer"}, eos_id=0)
    start_mask = auto.mask(auto.start)
    assert start_mask[0] == constrain.NEG_INF       # eos before any digit
    cur = auto.step(auto.start, ord("4"))
    assert auto.mask(cur)[0] == 0.0                 # "4" is a complete int
    assert auto.mask(cur)[ord("2")] == 0.0          # …but may continue


# --- conformance fuzz --------------------------------------------------------


@pytest.mark.parametrize("schema", FUZZ_SCHEMAS)
@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_schema_conformance_fuzz(model_params, schema, temperature):
    """Every completion — greedy AND sampled, several rng seeds —
    parses and validates against its schema (the acceptance criterion:
    masks make conformance a property, not a probability)."""
    model, params = model_params
    auto = _automaton(schema=schema)
    for seed in (0, 1, 2):
        eng = _engine(model, params, rng=jax.random.PRNGKey(seed))
        out = eng.generate(PROMPT, SamplingParams(
            greedy=temperature == 0.0, temperature=max(temperature, 1e-6),
            max_tokens=200, constraint=auto))
        req = eng.finished[-1]
        assert req.finish_reason == "stop", TOK.decode(out)
        value = json.loads(TOK.decode(out))
        assert constrain.validate_instance(value, schema), TOK.decode(out)
        eng.stop()


def test_json_object_mode(model_params):
    model, params = model_params
    auto = _automaton(kind_rf={"type": "json_object"})
    assert auto.kind == "json_object"
    eng = _engine(model, params)
    out = eng.generate(PROMPT, SamplingParams(greedy=True, max_tokens=200,
                                              constraint=auto))
    value = json.loads(TOK.decode(out))
    assert isinstance(value, dict)


# --- composition matrix ------------------------------------------------------


@pytest.mark.parametrize("kv_layout", ["contiguous", "paged"])
@pytest.mark.parametrize("spec_k", [None, 3])
def test_composition_matrix_golden_and_one_dispatch(model_params,
                                                    kv_layout, spec_k):
    """{contiguous,paged} x {spec off,ngram} x mixed-step: a constrained
    request next to a plain one — the constrained output is IDENTICAL
    across every cell (greedy + grammar is path-invariant), the plain
    neighbour still finishes, and every steady-decode step costs ONE
    jitted dispatch with grammar on (the pinned invariant)."""
    model, params = model_params
    auto = _automaton()
    eng = _engine(model, params, kv_layout=kv_layout,
                  speculative_k=spec_k, chunked_prefill=16,
                  mixed_step=True)
    sp = SamplingParams(greedy=True, max_tokens=150, constraint=auto)
    r_con = eng.submit(PROMPT, sp)
    r_plain = eng.submit(TOK.encode("hello there friend"),
                         SamplingParams(greedy=True, max_tokens=24))
    steady_dispatches = []
    while True:
        # a step that ENDS a chunked prompt is not steady decode: in the
        # contiguous layout it also runs the host's jitted first-token
        # sampler, which the meter counts since PR 32
        prefilling = bool(eng.slot_prefill)
        if not eng.step():
            break
        if (not prefilling and not eng.slot_prefill
                and any(eng.slot_ready[s] for s in range(eng.max_slots)
                        if eng.slot_req[s] is not None)):
            steady_dispatches.append(eng.dispatch_meter.last_step)
    out_con, out_plain = r_con.result(), r_plain.result()
    assert r_plain.finish_reason in ("stop", "length", "cache")
    value = json.loads(TOK.decode(out_con))
    assert constrain.validate_instance(value, SCHEMA)
    # steady decode (no prefill in flight) is one dispatch per step —
    # grammar on, every layout, spec on or off
    assert steady_dispatches and all(d == 1 for d in steady_dispatches)
    # the grammar work was booked, not hidden
    assert eng.grammar_mask_seconds_total > 0
    snap = eng.steptrace.snapshot()
    assert snap["host_seconds"]["grammar_mask"] >= 0
    if spec_k is not None:
        assert eng.spec_rounds > 0          # speculation really composed
    eng.stop()
    # cross-cell parity: pin against the plain contiguous reference
    ref = _engine(model, params)
    assert out_con == ref.generate(PROMPT, sp)
    ref.stop()


def test_spec_grammar_rejects_counted(model_params):
    """An ngram draft proposing grammar-forbidden continuations is
    rejected in staging and counted (llm_spec_grammar_rejects_total)."""
    model, params = model_params
    auto = _automaton()
    eng = _engine(model, params, speculative_k=4)
    out = eng.generate(PROMPT, SamplingParams(greedy=True, max_tokens=150,
                                              constraint=auto))
    assert constrain.validate_instance(json.loads(TOK.decode(out)), SCHEMA)
    assert eng.spec_rounds > 0
    assert eng.spec_grammar_rejects >= 0    # counter exists and is sane
    eng.stop()


# --- preemption resume -------------------------------------------------------


def test_preempt_resume_byte_identical_under_grammar(model_params):
    """Pool sized to force preemption while grammars are active: every
    resumed stream equals the free-pool run byte for byte, and every
    output still validates (the cursor rides the request through the
    requeue — nothing is replayed or re-sampled)."""
    model, params = model_params
    auto = _automaton()
    sp = SamplingParams(greedy=True, max_tokens=60, constraint=auto)
    prompts = [TOK.encode(f"request {j} wants json: ") for j in range(3)]
    tight = _engine(model, params, kv_layout="paged", kv_pool_tokens=160,
                    prefix_cache=True, cache_len=192)
    rs = [tight.submit(p, sp) for p in prompts]
    while tight.step():
        pass
    outs = [r.result() for r in rs]
    assert tight.preemptions > 0
    free = _engine(model, params, kv_layout="paged", cache_len=192)
    for p, out in zip(prompts, outs):
        assert out == free.generate(p, sp)
        assert constrain.validate_instance(
            json.loads(TOK.decode(out)), SCHEMA)
    free.stop()
    tight.stop()


# --- OpenAI surface ----------------------------------------------------------


@pytest.fixture(scope="module")
def server(model_params):
    from llm_in_practise_tpu.serve.api import OpenAIServer

    model, params = model_params
    engine = _engine(model, params, max_slots=2)
    srv = OpenAIServer(engine, TOK, model_name="structured-test")
    port = srv.serve(host="127.0.0.1", port=0, background=True)
    yield ("127.0.0.1", port)
    srv.shutdown()


def _post(addr, path, payload):
    conn = http.client.HTTPConnection(*addr, timeout=120)
    conn.request("POST", path, json.dumps(payload),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    return resp.status, body


def _get(addr, path):
    conn = http.client.HTTPConnection(*addr, timeout=30)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    return resp.status, body


def _chat(extra):
    return {"model": "structured-test",
            "messages": [{"role": "user", "content": "json please"}],
            "max_tokens": 180, "temperature": 0.0, **extra}


def test_api_json_schema_roundtrip(server):
    status, body = _post(server, "/v1/chat/completions", _chat({
        "response_format": {"type": "json_schema",
                            "json_schema": {"schema": SCHEMA}}}))
    assert status == 200, body
    data = json.loads(body)
    content = data["choices"][0]["message"]["content"]
    assert constrain.validate_instance(json.loads(content), SCHEMA)
    assert data["choices"][0]["finish_reason"] == "stop"


def test_api_streaming_constrained(server):
    conn = http.client.HTTPConnection(*server, timeout=120)
    conn.request("POST", "/v1/chat/completions", json.dumps(_chat({
        "stream": True,
        "response_format": {"type": "json_object"}})),
        {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    raw = resp.read().decode()
    conn.close()
    events = [line[6:] for line in raw.split("\n")
              if line.startswith("data: ")]
    assert events[-1] == "[DONE]"
    parsed = [json.loads(e) for e in events[:-1]]
    text = "".join(p["choices"][0]["delta"].get("content", "")
                   for p in parsed)
    assert isinstance(json.loads(text), dict)


def test_api_tool_choice_roundtrip(server):
    tool = {"type": "function", "function": {
        "name": "lookup",
        "parameters": {"type": "object",
                       "properties": {"q": {"type": "string",
                                            "maxLength": 6}},
                       "required": ["q"]}}}
    status, body = _post(server, "/v1/chat/completions", _chat({
        "tools": [tool],
        "tool_choice": {"type": "function",
                        "function": {"name": "lookup"}}}))
    assert status == 200, body
    msg = json.loads(body)["choices"][0]
    assert msg["finish_reason"] == "tool_calls"
    call = msg["message"]["tool_calls"][0]
    assert call["function"]["name"] == "lookup"
    args = json.loads(call["function"]["arguments"])
    assert isinstance(args["q"], str) and len(args["q"]) <= 6


def test_api_422_on_invalid_or_unsupported(server):
    # unsupported schema keyword → 422 with the constraint code
    status, body = _post(server, "/v1/chat/completions", _chat({
        "response_format": {"type": "json_schema", "json_schema": {
            "schema": {"type": "integer", "minimum": 2}}}}))
    assert status == 422
    assert json.loads(body)["error"]["code"] == "invalid_constraint"
    # malformed response_format shape → schema-level 422
    status, _ = _post(server, "/v1/chat/completions", _chat({
        "response_format": {"type": "yaml"}}))
    assert status == 422
    # tool_choice naming an undeclared function → 422
    status, _ = _post(server, "/v1/chat/completions", _chat({
        "tools": [{"type": "function", "function": {"name": "a"}}],
        "tool_choice": {"type": "function", "function": {"name": "b"}}}))
    assert status == 422


def test_api_structured_metrics(server):
    status, body = _get(server, "/metrics")
    assert status == 200
    text = body.decode()
    assert 'llm_structured_requests_total{kind="json_schema"}' in text
    assert "llm_grammar_mask_seconds_total" in text
    assert "llm_spec_grammar_rejects_total" in text
    # the roundtrip tests above really counted
    fams = {}
    for line in text.splitlines():
        if line.startswith("llm_structured_requests_total{"):
            k, v = line.rsplit(" ", 1)
            fams[k] = float(v)
    assert sum(fams.values()) >= 1


def test_gateway_semantic_cache_skips_structured():
    """The gateway's SEMANTIC response tier matches on conversation
    text alone — it must never satisfy a schema-constrained request
    with a cached free-text answer (exact-key hits stay allowed: the
    key hashes every non-transport field)."""
    from llm_in_practise_tpu.serve.gateway import ResponseCache

    cache = ResponseCache(semantic_threshold=0.5)
    base = {"model": "m",
            "messages": [{"role": "user", "content": "hello there"}]}
    cache.put(base, {"answer": "free text"})
    # identical conversation, different sampling params → semantic hit
    assert cache.get(dict(base, temperature=0.5)) is not None
    # same conversation but structured → the semantic tier must skip
    structured = dict(base, temperature=0.5,
                      response_format={"type": "json_object"})
    assert cache.get(structured) is None
    # structured responses never seed the semantic tier either
    cache.put(structured, {"answer": "{}"})
    assert cache.get(dict(structured, temperature=0.7)) is None
    # …but the exact key still serves the identical structured request
    assert cache.get(dict(structured)) == {"answer": "{}"}


# --- trace-replay arrivals ---------------------------------------------------


def test_arrival_schedule_seeded_and_bursty():
    from llm_in_practise_tpu.serve import arrivals

    a = arrivals.synthesize(seed=7, n_requests=200, mean_iat_s=0.05,
                            cv=2.0, prompt_tokens=(8, 64),
                            max_tokens=(4, 32))
    b = arrivals.synthesize(seed=7, n_requests=200, mean_iat_s=0.05,
                            cv=2.0, prompt_tokens=(8, 64),
                            max_tokens=(4, 32))
    assert a == b                                   # replayable
    stats = arrivals.describe(a)
    assert stats["n_requests"] == 200
    assert 0.02 < stats["iat_mean_s"] < 0.10        # mean is calibrated
    assert stats["iat_cv"] > 1.2                    # burstier than uniform
    assert all(8 <= x.prompt_tokens <= 64 for x in a)
    assert all(4 <= x.max_tokens <= 32 for x in a)
    uni = arrivals.synthesize(seed=7, n_requests=50, mean_iat_s=0.01,
                              cv=0.0)
    assert arrivals.describe(uni)["iat_cv"] == 0.0


def test_arrival_replay_order_and_results():
    from llm_in_practise_tpu.serve import arrivals

    sched = arrivals.synthesize(seed=3, n_requests=40, mean_iat_s=0.001)
    got = arrivals.replay(sched, lambda a: a.prompt_tokens, workers=4)
    assert got == [a.prompt_tokens for a in sched]


# --- bench artifact + smoke --------------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_structured_artifact_gates():
    """The checked-in BENCH_STRUCTURED artifact meets the acceptance
    criteria: all four engine-path legs present, every completed
    constrained stream conformant, constrained-vs-unconstrained TPOT
    pinned on the SAME replayed trace, steptrace coverage >= 0.95 with
    grammar on, and spec acceptance measured under grammar."""
    with open(os.path.join(REPO, "BENCH_STRUCTURED_r10.json")) as f:
        artifact = json.load(f)
    legs = {leg["leg"] for leg in artifact["legs"]}
    assert {"contiguous", "contiguous_spec", "paged",
            "paged_spec"} <= legs
    for leg in artifact["legs"]:
        c = leg["constrained_trace_replay"]
        assert c["conformant"] > 0
        assert c["conformant"] + c["truncated"] == c["requests"]
        assert leg["tpot_overhead_x"] is not None
        assert leg["host_gap"]["coverage"] >= artifact["coverage_gate"]
        assert leg["host_gap"]["coverage_ok"] is True
        assert leg["grammar_mask_seconds_total"] > 0
        assert leg["arrivals"]["iat_cv"] > 1.0      # really bursty
    for name in ("contiguous_spec", "paged_spec"):
        spec = next(leg for leg in artifact["legs"]
                    if leg["leg"] == name)["spec"]
        assert spec["rounds"] > 0
        assert 0.0 < spec["acceptance"] <= 1.0


@pytest.mark.slow
def test_structured_bench_smoke(tmp_path):
    """End-to-end smoke of the bench harness itself (tiny counts)."""
    from tools.structured_bench import main

    artifact = main(quick=True, out=str(tmp_path / "st.json"))
    assert len(artifact["legs"]) == 4
