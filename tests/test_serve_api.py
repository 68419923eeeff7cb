"""HTTP-level tests of the OpenAI-compatible server (stdlib http.client
against a live ThreadingHTTPServer on an ephemeral port)."""

import http.client
import json

import jax.numpy as jnp
import pytest

from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
from llm_in_practise_tpu.serve.api import OpenAIServer
from llm_in_practise_tpu.serve.engine import InferenceEngine


class ByteTokenizer:
    """Minimal tokenizer protocol for tests: one byte = one token."""

    def encode(self, text: str) -> list[int]:
        return list(text.encode("utf-8", errors="replace")[:200])

    def decode(self, ids) -> str:
        return bytes(int(i) % 256 for i in ids).decode("utf-8", errors="replace")


@pytest.fixture(scope="module")
def srv():
    import jax

    cfg = GPTConfig(vocab_size=256, seq_len=256, n_layer=2, n_head=2,
                    embed_dim=32, dropout=0.0, pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"]
    engine = InferenceEngine(model, params, max_slots=2, cache_len=256,
                             cache_dtype=jnp.float32)
    srv = OpenAIServer(engine, ByteTokenizer(), model_name="tiny-test")
    srv.addr = ("127.0.0.1",
                srv.serve(host="127.0.0.1", port=0, background=True))
    yield srv
    srv.shutdown()


@pytest.fixture(scope="module")
def server(srv):
    return srv.addr


def _post(addr, path, payload):
    conn = http.client.HTTPConnection(*addr, timeout=60)
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


def test_health_and_models(server):
    status, body = _get(server, "/health")
    assert status == 200 and json.loads(body)["status"] == "ok"
    status, body = _get(server, "/v1/models")
    data = json.loads(body)
    assert status == 200 and data["data"][0]["id"] == "tiny-test"


def test_chat_completion_roundtrip(server):
    status, body = _post(server, "/v1/chat/completions", {
        "model": "tiny-test",
        "messages": [{"role": "user", "content": "hi"}],
        "max_tokens": 8,
        "temperature": 0.0,
    })
    assert status == 200, body
    data = json.loads(body)
    assert data["object"] == "chat.completion"
    choice = data["choices"][0]
    assert choice["message"]["role"] == "assistant"
    assert choice["finish_reason"] in ("stop", "length", "cache")
    usage = data["usage"]
    assert usage["prompt_tokens"] > 0
    assert usage["total_tokens"] == usage["prompt_tokens"] + usage["completion_tokens"]
    assert usage["completion_tokens"] <= 8


def test_validation_errors(server):
    status, body = _post(server, "/v1/chat/completions",
                         {"model": "tiny-test", "messages": []})
    assert status == 422
    assert "messages" in json.loads(body)["error"]["message"]
    status, _ = _post(server, "/v1/chat/completions", {
        "model": "tiny-test",
        "messages": [{"role": "alien", "content": "x"}],
    })
    assert status == 422


def test_streaming_sse(server):
    conn = http.client.HTTPConnection(*server, timeout=60)
    conn.request("POST", "/v1/chat/completions", json.dumps({
        "model": "tiny-test",
        "messages": [{"role": "user", "content": "stream please"}],
        "max_tokens": 6,
        "temperature": 0.0,
        "stream": True,
    }), {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    assert resp.getheader("Content-Type").startswith("text/event-stream")
    raw = resp.read().decode()
    conn.close()
    events = [line[6:] for line in raw.split("\n") if line.startswith("data: ")]
    assert events[-1] == "[DONE]"
    parsed = [json.loads(e) for e in events[:-1]]
    assert parsed[0]["choices"][0]["delta"].get("role") == "assistant"
    assert parsed[-1]["choices"][0]["finish_reason"] in ("stop", "length", "cache")
    text = "".join(p["choices"][0]["delta"].get("content", "") for p in parsed)
    assert isinstance(text, str)


def _stream(addr, content, max_tokens):
    """A streamed chat's ``content`` deltas, in order, and its finish
    reason; the handler is done when the body has been read to its end."""
    conn = http.client.HTTPConnection(*addr, timeout=60)
    conn.request("POST", "/v1/chat/completions", json.dumps({
        "model": "tiny-test",
        "messages": [{"role": "user", "content": content}],
        "max_tokens": max_tokens, "temperature": 0.0, "stream": True,
    }), {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    raw = resp.read().decode()
    conn.close()
    events = [line[6:] for line in raw.split("\n")
              if line.startswith("data: ")]
    assert events[-1] == "[DONE]"
    choices = [json.loads(e)["choices"][0] for e in events[:-1]]
    deltas = [c["delta"]["content"] for c in choices
              if "content" in c["delta"]]
    return deltas, choices[-1]["finish_reason"]


@pytest.mark.parametrize("content,max_tokens", [
    ("stream please", 48), ("日本語 😀", 64), ("x", 1)])
def test_streamed_content_equals_non_streamed(server, content, max_tokens):
    """Greedy, the same prompt: the stream's deltas concatenate to the
    non-streamed ``content`` (a seeded toy model's bytes are mostly NOT
    characters, so ``decode`` puts U+FFFD and the handler has to hold
    and release text exactly as ``decode`` of the whole list renders
    it)."""
    status, body = _post(server, "/v1/chat/completions", {
        "model": "tiny-test",
        "messages": [{"role": "user", "content": content}],
        "max_tokens": max_tokens, "temperature": 0.0,
    })
    assert status == 200, body
    want = json.loads(body)["choices"][0]
    deltas, finish = _stream(server, content, max_tokens)
    assert "".join(deltas) == want["message"]["content"]
    assert finish == want["finish_reason"]
    assert all(deltas)      # an event with content carries some


def test_stream_sends_a_split_character_once_and_whole(srv, monkeypatch):
    """The ids of a stream are scripted (one byte a token: two, three and
    four tokens a character): each character is ONE event, sent with the
    token that completes it, and a stream that ends inside a character
    sends ``decode``'s rendering of the tail before the finish event."""
    from llm_in_practise_tpu.serve.engine import _FINISH

    text = "é日😀!"
    script = list(text.encode()) + list("本".encode()[:2])
    submit = srv.engine.submit

    def scripted_submit(*args, **kwargs):
        handle = submit(*args, **kwargs)
        next_item, ids = handle.next_item, iter(script)

        def scripted_next():
            item = next_item()
            return item if item is _FINISH else next(ids)
        handle.next_item = scripted_next
        return handle

    monkeypatch.setattr(srv.engine, "submit", scripted_submit)
    deltas, _ = _stream(srv.addr, "anything", len(script))
    assert deltas == ["é", "日", "😀", "!", "\ufffd"]
    assert "".join(deltas) == srv.tokenizer.decode(script)


def test_stream_decode_metrics(server):
    """The two families of the streams' detokeniser: token events (text
    sent or held back) sum to the tokens the streams generated, booked at
    a stream's end."""
    from promparse import parse_exposition

    def scrape():
        fams = parse_exposition(_get(server, "/metrics")[1].decode())
        events = fams["llm_stream_token_events_total"].samples
        assert {dict(k[1])["text"] for k in events} == {"yes", "held"}
        (detok,) = fams["llm_stream_detokenize_seconds_total"].samples.values()
        (tokens,) = fams["llm_tokens_generated_total"].samples.values()
        return sum(events.values()), detok, tokens

    events0, detok0, tokens0 = scrape()
    _stream(server, "count my tokens", 24)
    _stream(server, "and mine", 7)
    events1, detok1, tokens1 = scrape()
    assert tokens1 - tokens0 > 0
    assert events1 - events0 == tokens1 - tokens0
    assert detok1 > detok0
    # the same two readings a stream, on its api.stream_flush span
    traces = json.loads(_get(server, "/debug/traces")[1])["traces"]
    flushes = [s["attrs"] for t in traces for s in t["spans"]
               if s["name"] == "api.stream_flush"]
    assert flushes and all(
        a["detokenize_s"] > 0 and a["held"] >= 0 for a in flushes)


def test_metrics_exposition(server):
    status, body = _get(server, "/metrics")
    assert status == 200
    text = body.decode()
    assert "llm_requests_total" in text
    # TTFT/TPOT are bucketed histograms now (was: full-history
    # summaries) — PromQL quantiles come from histogram_quantile()
    assert "# TYPE llm_ttft_seconds histogram" in text
    assert 'llm_ttft_seconds_bucket{le="+Inf"}' in text
    assert "llm_ttft_seconds_count" in text
    assert "llm_tpot_seconds_sum" in text
    # dispatch accounting (fused mixed-step observability)
    assert "llm_dispatches_total" in text
    assert "llm_dispatches_per_step" in text
    assert "llm_mixed_blocks_total" in text


def test_debug_traces_endpoint(server):
    """/debug/traces serves the span ring: a served request leaves an
    api.chat span (and its engine phase spans) behind."""
    status, _ = _post(server, "/v1/chat/completions", {
        "model": "tiny-test",
        "messages": [{"role": "user", "content": "trace me"}],
        "max_tokens": 4, "temperature": 0.0,
    })
    assert status == 200
    status, body = _get(server, "/debug/traces")
    assert status == 200
    payload = json.loads(body)
    names = {s["name"] for t in payload["traces"] for s in t["spans"]}
    assert "api.chat" in names
    assert "engine.queue_wait" in names and "engine.decode" in names
    assert payload["summary"]["spans_recorded"] >= 3


def test_dead_engine_streaming_returns_503():
    """A dead engine loop must surface as a 5xx on a streaming request,
    not a client hanging forever with no headers (the first-token wait
    is bounded with an engine-liveness check between waits)."""
    import jax

    cfg = GPTConfig(vocab_size=256, seq_len=64, n_layer=1, n_head=2,
                    embed_dim=32, dropout=0.0, pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    engine = InferenceEngine(model, params, max_slots=1, cache_len=64,
                             cache_dtype=jnp.float32)
    srv = OpenAIServer(engine, ByteTokenizer(), model_name="dead-test")
    port = srv.serve(host="127.0.0.1", port=0, background=True)
    engine.stop()                       # engine dies; HTTP stays up
    assert not engine.is_alive()
    status, body = _post(("127.0.0.1", port), "/v1/chat/completions", {
        "model": "dead-test",
        "messages": [{"role": "user", "content": "hi"}],
        "max_tokens": 4,
        "temperature": 0.0,
        "stream": True,
    })
    assert status == 503
    assert json.loads(body)["error"]["code"] == "engine_dead"
    srv.shutdown()


def test_webui_page(server):
    status, body = _get(server, "/")
    assert status == 200
    text = body.decode()
    assert "<form" in text and "/v1/chat/completions" in text


def test_adapter_routing(tmp_path):
    """vLLM --lora-modules parity: adapter model names route to merged
    weights; unknown models 404."""
    import jax

    from llm_in_practise_tpu.ckpt import checkpoint as ckpt_lib
    from llm_in_practise_tpu.peft import LoRAConfig, init_lora
    from llm_in_practise_tpu.serve.adapters import (
        build_adapter_engines,
        parse_lora_modules,
    )

    cfg = GPTConfig(vocab_size=256, seq_len=64, n_layer=1, n_head=2,
                    embed_dim=32, dropout=0.0, pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"]
    lcfg = LoRAConfig(r=2, alpha=4.0, target_patterns=("attn/q_proj",))
    lp = init_lora(params, lcfg, jax.random.PRNGKey(1))
    ckpt_lib.save_named(str(tmp_path), lp, "adapter",
                        metadata={"lora_config": lcfg.to_dict()})

    modules = parse_lora_modules([f"tuned={tmp_path}"])
    adapters = build_adapter_engines(
        model, params, modules, max_slots=1, cache_len=64,
        cache_dtype=jnp.float32,
    )
    engine = InferenceEngine(model, params, max_slots=1, cache_len=64,
                             cache_dtype=jnp.float32)
    srv = OpenAIServer(engine, ByteTokenizer(), model_name="base",
                       adapters=adapters)
    port = srv.serve(host="127.0.0.1", port=0, background=True)
    addr = ("127.0.0.1", port)
    try:
        status, body = _get(addr, "/v1/models")
        ids = [m["id"] for m in json.loads(body)["data"]]
        assert ids == ["base", "tuned"]
        msg = {"messages": [{"role": "user", "content": "hi"}],
               "max_tokens": 4, "temperature": 0.0}
        for name in ("base", "tuned"):
            status, body = _post(addr, "/v1/chat/completions",
                                 dict(msg, model=name))
            assert status == 200, body
            assert json.loads(body)["usage"]["completion_tokens"] >= 1
        status, body = _post(addr, "/v1/chat/completions",
                             dict(msg, model="missing"))
        assert status == 404
    finally:
        srv.shutdown()


def test_parse_lora_modules_errors():
    from llm_in_practise_tpu.serve.adapters import parse_lora_modules

    with pytest.raises(ValueError):
        parse_lora_modules(["noequals"])
    assert parse_lora_modules(["a=/p", "b=/q"]) == {"a": "/p", "b": "/q"}


def test_embeddings_endpoint(server):
    """OpenAI /v1/embeddings schema: list input, unit-norm vectors,
    usage accounting, and the same text embedding identically."""
    import math

    status, body = _post(server, "/v1/embeddings",
                         {"input": ["hello world", "hello world", "bye"]})
    assert status == 200, body
    out = json.loads(body)
    assert out["object"] == "list" and len(out["data"]) == 3
    e0, e1, e2 = (d["embedding"] for d in out["data"])
    assert [d["index"] for d in out["data"]] == [0, 1, 2]
    assert abs(sum(x * x for x in e0) - 1.0) < 1e-6     # unit norm
    assert e0 == e1                                     # deterministic
    assert e0 != e2
    assert out["usage"]["prompt_tokens"] == len("hello world") * 2 + 3


def test_embeddings_validation(server):
    status, _ = _post(server, "/v1/embeddings", {"input": 7})
    assert status == 422
    status, _ = _post(server, "/v1/embeddings",
                      {"input": "x", "model": "nope"})
    assert status == 404
    # string input is accepted as a singleton
    status, body = _post(server, "/v1/embeddings", {"input": "just one"})
    assert status == 200
    assert len(json.loads(body)["data"]) == 1
