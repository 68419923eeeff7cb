"""Disaggregated prefill/decode serving (serve/disagg.py + the role
split across engine, api, gateway, kv_pool).

The contract under test, from the llm-d stage the subsystem mirrors:

- **golden token equality** — a prompt served prefill-replica → pinned
  KV handoff → decode-replica produces bit-identical greedy tokens to a
  single ``role=both`` engine (the handoff is a pure relocation of the
  prefill, not an approximation);
- **pin-until-claimed** — no amount of pool eviction pressure can drop
  a handoff entry before its claim; TTL is the only reclaim;
- **graceful degradation** — a lost/expired/mismatched entry means the
  serving replica re-prefills locally (counted), never a failed request;
- **interference-free decode** — a decode replica serving handed-off
  requests under concurrent load runs zero mixed prefill/decode blocks
  (``DispatchMeter`` / ``llm_mixed_blocks_total`` stay 0).
"""

import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
from llm_in_practise_tpu.serve.disagg import (
    LocalHandoff,
    RemoteHandoff,
    new_handoff_id,
)
from llm_in_practise_tpu.serve.engine import InferenceEngine, SamplingParams
from llm_in_practise_tpu.serve.gateway import (
    DisaggRouter,
    Gateway,
    RetryPolicy,
    Upstream,
)
from llm_in_practise_tpu.serve.kv_pool import KVPoolServer


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


PROMPTS = [[(i * 7 + j * 3 + 5) % 64 for i in range(20 + 4 * j)]
           for j in range(4)]
SP = SamplingParams(greedy=True, max_tokens=12)


@pytest.fixture(scope="module")
def both_engine(model_params):
    """ONE colocated role=both engine shared by every golden
    comparison (engine construction re-jits all programs — per-test
    copies would dominate the module's runtime)."""
    model, params = model_params
    return _engine(model, params)


@pytest.fixture(scope="module")
def ref_outputs(both_engine):
    """Golden outputs from the colocated engine — computed once."""
    return [both_engine.generate(p, SP) for p in PROMPTS]


def _prefill_to(store, pre, prompt, sp=SP):
    hid = new_handoff_id()
    h = pre.submit(prompt, sp, handoff_id=hid)
    while pre.step():
        pass
    # result() drains to _FINISH, which the async publisher emits only
    # once the entry is pinned — finish_reason is settled after it
    assert h.result() == []          # prefill replicas emit no tokens
    assert h.finish_reason == "handoff", h.finish_reason
    return hid


# --- golden equality ---------------------------------------------------------


def test_handoff_golden_tokens_local_store(model_params, ref_outputs):
    model, params = model_params
    ref = ref_outputs
    store = LocalHandoff()
    pre = _engine(model, params, role="prefill", handoff=store)
    dec = _engine(model, params, role="decode")
    for prompt, want in zip(PROMPTS, ref):
        hid = _prefill_to(store, pre, prompt)
        host = store.claim(hid)
        assert host is not None and host.length == len(prompt)
        h = dec.submit(prompt, SP, kv_entry=host)
        while dec.step():
            pass
        assert h.result() == want
    assert pre.handoff_published == len(PROMPTS)
    assert dec.kv_admitted == len(PROMPTS)
    assert dec.local_prefills == 0 and dec.kv_rejected == 0


def test_handoff_golden_tokens_over_pool_server(model_params, ref_outputs):
    """Same equality through the real wire: prefill publishes into a
    KVPoolServer's pinned handoff namespace, decode claims over TCP —
    the full serialization round-trip the k8s stage runs."""
    model, params = model_params
    ref = ref_outputs[:2]
    server = KVPoolServer(min_prefix=4).start()
    try:
        store = RemoteHandoff(server.address, namespace="m")
        pre = _engine(model, params, role="prefill", handoff=store)
        dec = _engine(model, params, role="decode")
        for prompt, want in zip(PROMPTS[:2], ref):
            hid = _prefill_to(store, pre, prompt)
            host = store.claim(hid)
            assert host is not None
            h = dec.submit(prompt, SP, kv_entry=host)
            while dec.step():
                pass
            assert h.result() == want
        assert server.handoff_puts == 2 and server.handoff_claims == 2
        # claim-once: a second claim of the same id is a miss
        assert store.claim(hid) is None
    finally:
        server.stop()


# --- degradation -------------------------------------------------------------


def test_handoff_lost_reprefills_and_completes(model_params, ref_outputs):
    """A lost entry (expired / never published / pool down) degrades to
    a local prefill on the decode replica — correct output, counted."""
    model, params = model_params
    ref = ref_outputs[0]
    dec = _engine(model, params, role="decode")
    store = LocalHandoff()
    assert store.claim("never-published") is None
    h = dec.submit(PROMPTS[0], SP, kv_entry=None)   # claim came back empty
    while dec.step():
        pass
    assert h.result() == ref
    assert dec.local_prefills == 1 and dec.kv_admitted == 0


def test_mismatched_entry_rejected_then_reprefilled(model_params, ref_outputs):
    """Replica config drift (entry padded beyond this engine's cache,
    or wrong length) must be rejected BEFORE any device scatter and
    degrade to local prefill."""
    from llm_in_practise_tpu.serve.kv_pool import HostEntry

    model, params = model_params
    ref = ref_outputs[0]
    dec = _engine(model, params, role="decode")
    bogus = HostEntry(length=len(PROMPTS[0]), bucket=1024,  # > cache_len
                      rows=[], last_logits=np.zeros((1, 64), np.float32))
    h = dec.submit(PROMPTS[0], SP, kv_entry=bogus)
    while dec.step():
        pass
    assert h.result() == ref
    assert dec.kv_rejected == 1 and dec.kv_admitted == 0
    short = HostEntry(length=4, bucket=16, rows=[],
                      last_logits=np.zeros((1, 64), np.float32))
    h2 = dec.submit(PROMPTS[0], SP, kv_entry=short)  # length mismatch
    while dec.step():
        pass
    assert h2.result() == ref
    assert dec.kv_rejected == 2


def test_pool_down_mid_claim_degrades(model_params):
    """RemoteHandoff folds transport faults into 'lost': the decode
    replica serves the request anyway."""
    model, params = model_params
    store = RemoteHandoff(("127.0.0.1", 1), namespace="m")  # nothing there
    assert store.claim("any") is None
    assert store.claim_errors == 1


# --- interference-free decode ------------------------------------------------


def test_decode_replica_zero_mixed_blocks_under_concurrent_load(
        model_params, ref_outputs):
    """The acceptance bar: a decode replica serving ONLY handed-off
    requests under concurrent load never runs a prefill chunk, so no
    decode block ever shares a dispatch with prefill work
    (``mixed_blocks``/``llm_mixed_blocks_total`` == 0) — on an engine
    configured so that local prefills WOULD trigger the fused mixed
    path (chunked_prefill, the Finding 17 machinery)."""
    model, params = model_params
    ref = ref_outputs
    # this config DOES produce mixed blocks when prompts prefill
    # locally — tests/test_mixed_step.py pins that (fused.mixed_blocks
    # > 0 under the same chunked_prefill mixed load), so
    # the 0 below is a meaningful absence, not a disabled path
    mixed_kw = dict(chunked_prefill=8)

    store = LocalHandoff()
    pre = _engine(model, params, role="prefill", handoff=store, **mixed_kw)
    dec = _engine(model, params, role="decode", **mixed_kw)
    hosts = [store.claim(_prefill_to(store, pre, p)) for p in PROMPTS]
    assert all(h is not None for h in hosts)
    dec.start()
    try:
        handles = [dec.submit(p, SP, kv_entry=h)
                   for p, h in zip(PROMPTS, hosts)]
        outs = [h.result() for h in handles]
    finally:
        dec.stop()
    assert outs == ref
    assert dec.mixed_blocks == 0, "decode replica ran a mixed block"
    assert not dec.slot_prefill
    assert dec.kv_admitted == len(PROMPTS) and dec.local_prefills == 0


# --- pin-until-claimed + TTL -------------------------------------------------


def test_pinned_handoff_survives_pool_eviction_pressure():
    """The LRU store can churn completely; the pinned entry must still
    be claimable — eviction racing the claim is the failure mode the
    pin semantics exist to close."""
    from llm_in_practise_tpu.serve.kv_pool import (
        HostEntry, RemoteKVClient, encode_entry,
    )

    def he(seed):
        rng = np.random.default_rng(seed)
        return HostEntry(
            length=16, bucket=16,
            rows=[{"k": rng.standard_normal((1, 16, 2, 4)).astype(
                np.float32)}],
            last_logits=rng.standard_normal((1, 8)).astype(np.float32))

    blob = len(encode_entry(he(0)))
    server = KVPoolServer(min_prefix=4, max_bytes=int(blob * 1.5)).start()
    try:
        client = RemoteKVClient(server.address, namespace="m")
        client.handoff_put("pinned", he(0))
        # every put evicts the previous LRU entry; the byte budget fits
        # ONE entry, so the store churns completely several times over
        for i in range(4):
            client.put([100 + i, *range(1, 16)], he(i + 1))
        got = client.handoff_claim("pinned")
        assert got is not None and got.length == 16
        np.testing.assert_array_equal(got.rows[0]["k"], he(0).rows[0]["k"])
    finally:
        server.stop()


def test_handoff_ttl_reclaim_and_budget():
    from llm_in_practise_tpu.serve.kv_pool import (
        HandoffRejected, HostEntry, RemoteKVClient, encode_entry,
    )

    def he():
        rng = np.random.default_rng(0)
        return HostEntry(
            length=16, bucket=16,
            rows=[{"k": rng.standard_normal((1, 16, 2, 4)).astype(
                np.float32)}],
            last_logits=rng.standard_normal((1, 8)).astype(np.float32))

    clock = {"t": 0.0}
    server = KVPoolServer(min_prefix=4, handoff_ttl_s=30.0,
                          clock=lambda: clock["t"]).start()
    try:
        client = RemoteKVClient(server.address, namespace="m")
        client.handoff_put("h", he())
        clock["t"] = 31.0
        assert client.handoff_claim("h") is None      # TTL reclaimed
        assert server.handoff_expired == 1
        assert server._handoff_bytes == 0             # bytes released
    finally:
        server.stop()

    blob = len(encode_entry(he()))
    tight = KVPoolServer(min_prefix=4, max_handoff_bytes=blob).start()
    try:
        client = RemoteKVClient(tight.address, namespace="m")
        client.handoff_put("a", he())
        with pytest.raises(HandoffRejected):
            client.handoff_put("b", he())             # refused, not evicted
        assert tight.handoff_rejected == 1
        assert client.handoff_claim("a") is not None  # the pin held
    finally:
        tight.stop()


def test_local_handoff_ttl():
    clock = {"t": 0.0}
    store = LocalHandoff(ttl_s=10.0, clock=lambda: clock["t"])
    store.publish("x", object())
    clock["t"] = 11.0
    assert store.claim("x") is None
    assert store.expired == 1


# --- router + gateway --------------------------------------------------------


def _upstreams():
    return {
        "pre": Upstream("http://p", "m", group="chat", role="prefill"),
        "dec": Upstream("http://d", "m", group="chat", role="decode"),
        "both": Upstream("http://b", "m", group="chat", role="both"),
    }


def test_disagg_router_pools_and_degradation():
    u = _upstreams()
    router = DisaggRouter(list(u.values()))
    assert router.disaggregated("chat")
    assert router.pick_prefill("chat") is u["pre"]
    # decode-pool pick for a handed-off body; least-pending within pool
    body = {"kv_transfer_params": {"handoff_id": "x"}}
    assert router.pick_for_request("chat", body) is u["dec"]
    # a NON-handed-off body load-balances over the WHOLE group (forcing
    # it onto the decode pool would buy a pointless local re-prefill)
    u["pre"].pending, u["dec"].pending, u["both"].pending = 2, 1, 0
    assert router.pick_for_request("chat", {}) is u["both"]
    u["pre"].pending = u["dec"].pending = u["both"].pending = 0
    # decode upstream cooled down: handed-off traffic falls back to both
    u["dec"].cooldown_until = time.time() + 60
    assert router.pick_for_request("chat", body) is u["both"]
    # prefill pool gone AND no both → split inoperable → no prefill phase
    router2 = DisaggRouter([u2 for u2 in [
        Upstream("http://d1", "m", group="chat", role="decode")]])
    assert not router2.disaggregated("chat")
    assert router2.pick_prefill("chat") is None
    assert router2.degraded_picks == 1
    # both-only fleet: plain routing, no two-phase overhead
    router3 = DisaggRouter([Upstream("http://b1", "m", group="chat")])
    assert not router3.disaggregated("chat")
    # prefill + both (no dedicated decode): operable — both decodes
    router4 = DisaggRouter([
        Upstream("http://p1", "m", group="chat", role="prefill"),
        Upstream("http://b1", "m", group="chat", role="both")])
    assert router4.disaggregated("chat")


def test_handed_off_pick_prefers_matching_model():
    """Mixed-model decode pools (|MODEL renames): the handoff namespace
    is the publishing model's name, so the decode pick must choose a
    replica serving THAT model — a less-loaded replica of another model
    could never claim the entry."""
    m1 = Upstream("http://d1", "m1", group="chat", role="decode")
    m2 = Upstream("http://d2", "m2", group="chat", role="decode")
    router = DisaggRouter([
        Upstream("http://p", "m1", group="chat", role="prefill"), m1, m2])
    m1.pending, m2.pending = 5, 0      # m2 is far less loaded...
    body = {"kv_transfer_params": {"handoff_id": "x", "model": "m1"}}
    assert router.pick_for_request("chat", body) is m1   # ...but can't claim
    # no matching replica at all: serve anyway (claim will miss → local
    # re-prefill, graceful degradation)
    body2 = {"kv_transfer_params": {"handoff_id": "y", "model": "m9"}}
    assert router.pick_for_request("chat", body2) is m2


def test_disagg_autoscalers_scale_roles_independently():
    from llm_in_practise_tpu.serve.autoscale import (
        AutoscaleConfig, make_disagg_autoscalers,
    )

    u = _upstreams()
    router = DisaggRouter(list(u.values()))
    spawned = {"prefill": 0, "decode": 0}

    def spawn(role):
        spawned[role] += 1
        return Upstream(f"http://{role}{spawned[role]}", "m",
                        group="chat", role=role)

    cfg = AutoscaleConfig(min_replicas=1, max_replicas=4,
                          target_ongoing_requests=2.0,
                          upscale_delay_s=10.0, look_back_period_s=30.0)
    pre, dec = make_disagg_autoscalers(
        router, "chat",
        spawn_prefill=lambda: spawn("prefill"),
        stop_prefill=lambda _u: None,
        spawn_decode=lambda: spawn("decode"),
        stop_decode=lambda _u: None,
        prefill_config=cfg, decode_config=cfg)
    # prefill pool under queue pressure; decode idle
    u["pre"].pending = 8
    t = 0.0
    for _ in range(4):
        pre.tick(t)
        dec.tick(t)
        t += 10.0
    assert spawned["prefill"] >= 1, "prefill pool should have scaled"
    assert spawned["decode"] == 0, "idle decode pool must not scale"
    roles = [x.role for x in router.upstreams]
    assert roles.count("prefill") == 1 + spawned["prefill"]


class _FakeReplica:
    """Scriptable role replica: answers /internal/handoff/prefill and
    /v1/chat/completions, recording what arrived."""

    def __init__(self, name, *, prefill_ok=True, prefill_status=503):
        import http.server

        self.name = name
        self.prefill_calls = 0
        self.chat_bodies = []
        outer = self

        class Handler(http.server.BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def _send(self, status, payload):
                body = json.dumps(payload).encode()
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(n) or b"{}")
                if self.path == "/internal/handoff/prefill":
                    outer.prefill_calls += 1
                    if not prefill_ok:
                        return self._send(prefill_status, {"error": {
                            "message": "no pool"}})
                    return self._send(200, {
                        "handoff_id": f"h-{outer.prefill_calls}",
                        "prompt_tokens": 3})
                outer.chat_bodies.append(body)
                return self._send(200, {
                    "id": "x", "object": "chat.completion",
                    "model": outer.name,
                    "choices": [{"index": 0, "message": {
                        "role": "assistant",
                        "content": f"from {outer.name}"},
                        "finish_reason": "stop"}],
                    "usage": {"prompt_tokens": 1, "completion_tokens": 1,
                              "total_tokens": 2}})

        import http.server as hs

        self.httpd = hs.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.port = self.httpd.server_address[1]
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    @property
    def base_url(self):
        return f"http://127.0.0.1:{self.port}"

    def close(self):
        self.httpd.shutdown()


def test_gateway_two_phase_dispatch_and_metrics():
    """The gateway prefills at the prefill pool, then forwards to the
    decode pool with kv_transfer_params; /metrics exports the handoff
    counters and per-upstream picks."""
    pre, dec = _FakeReplica("pre"), _FakeReplica("dec")
    try:
        router = DisaggRouter([
            Upstream(pre.base_url, "m", group="chat", role="prefill"),
            Upstream(dec.base_url, "m", group="chat", role="decode")])
        gw = Gateway(router, retry_policy=RetryPolicy(backoff_s=0.01),
                     health_check_interval_s=0)
        status, resp = gw.handle_completion({
            "model": "chat",
            "messages": [{"role": "user", "content": "hi"}]})
        assert status == 200
        assert resp["choices"][0]["message"]["content"] == "from dec"
        assert pre.prefill_calls == 1
        assert dec.chat_bodies[0]["kv_transfer_params"]["handoff_id"] \
            == "h-1"
        assert gw.handoff_total == 1 and gw.handoff_failed_total == 0
        text = gw.metrics_text()
        assert "gateway_handoff_total 1" in text
        assert 'role="prefill"' in text and 'role="decode"' in text
        assert "gateway_upstream_picks_total" in text
    finally:
        pre.close()
        dec.close()


def test_gateway_degrades_when_prefill_phase_fails():
    """A prefill-pool failure must not fail the request: the decode
    upstream gets the raw body (it re-prefills locally) and the failure
    is counted."""
    pre, dec = _FakeReplica("pre", prefill_ok=False), _FakeReplica("dec")
    try:
        router = DisaggRouter([
            Upstream(pre.base_url, "m", group="chat", role="prefill"),
            Upstream(dec.base_url, "m", group="chat", role="decode")])
        gw = Gateway(router, retry_policy=RetryPolicy(backoff_s=0.01),
                     health_check_interval_s=0)
        status, resp = gw.handle_completion({
            "model": "chat",
            "messages": [{"role": "user", "content": "hi"}]})
        assert status == 200
        assert "kv_transfer_params" not in dec.chat_bodies[0]
        assert gw.handoff_failed_total == 1
    finally:
        pre.close()
        dec.close()


def test_mismatched_role_pool_models_skip_the_prefill_phase():
    """A prefill pool publishing under model m1 can never be claimed by
    a decode pool serving m2 (the handoff namespace IS the model name)
    — the gateway must skip the phase instead of burning a prefill per
    request that is guaranteed to be lost."""
    pre, dec = _FakeReplica("pre"), _FakeReplica("dec")
    try:
        router = DisaggRouter([
            Upstream(pre.base_url, "m1", group="chat", role="prefill"),
            Upstream(dec.base_url, "m2", group="chat", role="decode")])
        gw = Gateway(router, retry_policy=RetryPolicy(backoff_s=0.01),
                     health_check_interval_s=0)
        status, _ = gw.handle_completion({
            "model": "chat",
            "messages": [{"role": "user", "content": "hi"}]})
        assert status == 200
        assert pre.prefill_calls == 0            # phase skipped entirely
        assert "kv_transfer_params" not in dec.chat_bodies[0]
        assert gw.handoff_failed_total == 1
    finally:
        pre.close()
        dec.close()


def test_prefill_501_does_not_trip_the_breaker():
    """A 501 from /internal/handoff/prefill means 'this model can't
    disaggregate here' (e.g. a LoRA adapter without a handoff store) —
    the upstream is healthy, and cooling it down would pull it from
    rotation for EVERY model it serves."""
    pre = _FakeReplica("pre", prefill_ok=False, prefill_status=501)
    dec = _FakeReplica("dec")
    try:
        u_pre = Upstream(pre.base_url, "m", group="chat",
                         role="prefill", allowed_fails=1)
        router = DisaggRouter([
            u_pre, Upstream(dec.base_url, "m", group="chat",
                            role="decode")])
        gw = Gateway(router, retry_policy=RetryPolicy(backoff_s=0.01),
                     health_check_interval_s=0)
        for _ in range(3):
            status, _ = gw.handle_completion({
                "model": "chat",
                "messages": [{"role": "user", "content": "hi"}]})
            assert status == 200
        assert gw.handoff_failed_total == 3
        assert u_pre.fails == 0 and u_pre.cooldowns == 0
        assert u_pre.available(time.time())   # never cooled down
    finally:
        pre.close()
        dec.close()


# --- full HTTP stack ---------------------------------------------------------


class _ByteTokenizer:
    """Deterministic toy tokenizer into the module model's 64-id vocab.
    Decode need not invert encode — golden comparisons decode the SAME
    token ids on both sides."""

    def encode(self, text):
        return [b % 64 for b in text.encode("utf-8", errors="replace")][:60]

    def decode(self, ids):
        return "".join(chr(33 + int(i) % 64) for i in ids)


def test_disagg_http_full_stack(model_params, both_engine):
    """End to end over real sockets: OpenAIServer(role=prefill) +
    OpenAIServer(role=decode) sharing a KVPoolServer handoff namespace,
    fronted by a Gateway(DisaggRouter) — the whole 11-disagg stage in
    one process — answers bit-identically to a colocated engine."""
    model, params = model_params
    from llm_in_practise_tpu.serve import schemas
    from llm_in_practise_tpu.serve.api import OpenAIServer, build_prompt

    tok = _ByteTokenizer()
    body = {"model": "m", "max_tokens": 8, "temperature": 0.0,
            "messages": [{"role": "user", "content": "hello world"}]}
    # colocated reference via a direct engine (same prompt pipeline)
    prompt_ids = tok.encode(build_prompt(
        [schemas.ChatMessage(m["role"], m["content"])
         for m in body["messages"]]))
    ref_text = tok.decode(both_engine.generate(
        prompt_ids, SamplingParams(temperature=0.0, greedy=True,
                                   max_tokens=8)))

    pool = KVPoolServer(min_prefix=4).start()
    servers, port = [], {}
    try:
        for role in ("prefill", "decode"):
            store = RemoteHandoff(pool.address, namespace="m")
            eng = _engine(model, params, role=role,
                          handoff=store if role == "prefill" else None)
            srv = OpenAIServer(eng, tok, model_name="m", role=role,
                               handoff=store if role == "decode" else None)
            port[role] = srv.serve(host="127.0.0.1", port=0,
                                   background=True)
            servers.append(srv)

        gw = Gateway(DisaggRouter([
            Upstream(f"http://127.0.0.1:{port['prefill']}", "m",
                     group="m", role="prefill"),
            Upstream(f"http://127.0.0.1:{port['decode']}", "m",
                     group="m", role="decode")]),
            retry_policy=RetryPolicy(backoff_s=0.01),
            health_check_interval_s=0)
        status, got = gw.handle_completion(dict(body))
        assert status == 200
        assert got["choices"][0]["message"]["content"] == ref_text
        assert gw.handoff_total == 1
        dec_srv = servers[1]
        assert dec_srv.engine.kv_admitted == 1
        assert dec_srv.engine.mixed_blocks == 0
        assert dec_srv.engine.local_prefills == 0
        # per-role metrics render on both sides
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port['decode']}/metrics") as r:
            text = r.read().decode()
        assert 'llm_handoff_total{event="kv_admitted"} 1' in text
        assert 'role="decode"' in text
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port['prefill']}/metrics") as r:
            text = r.read().decode()
        assert 'llm_handoff_total{event="published"} 1' in text
    finally:
        for srv in servers:
            srv.shutdown()
        pool.stop()
