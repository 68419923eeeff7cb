"""Session-native serving (serve/sessions.py + gateway ring + fleet pull).

The contract under test, from ISSUE 17 / ROADMAP item 2:

- **ring churn bound** — replica join/leave remaps ≤ 1/N + slack of
  live sessions (consistent hashing, not rehash-the-world), and the
  affinity-table ``id()`` bug stays fixed (stable base_url keys);
- **pin across turns** — a finished turn's KV pages stay refcount-
  pinned under the session handle; follow-up turns admit warm;
  eviction is TTL/capacity/pressure only, newest-page-first so the
  surviving pin is a valid chain prefix;
- **golden migration** — a session moved to a new replica via the
  kv-pool pull path produces bit-identical greedy tokens to a cold
  engine serving the same conversation;
- **graceful miss** — a dead/empty pool degrades to local re-prefill
  (counted, never an error), and a token-prefix mismatch discards the
  pulled entry instead of scattering wrong KV.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
from llm_in_practise_tpu.serve.disagg import LocalHandoff
from llm_in_practise_tpu.serve.engine import InferenceEngine, SamplingParams
from llm_in_practise_tpu.serve.gateway import (
    Gateway,
    HashRingRouter,
    PrefixAffinityRouter,
    Router,
    Upstream,
)
from llm_in_practise_tpu.serve.sessions import (
    ConsistentHashRing,
    SessionStore,
    session_hid,
)


@pytest.fixture(scope="module")
def model_params():
    cfg = GPTConfig(vocab_size=128, seq_len=192, n_layer=2, n_head=2,
                    embed_dim=32, dropout=0.0, pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    return model, params


def _engine(model, params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("cache_len", 192)
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("kv_layout", "paged")      # sessions pin KV *pages*
    kw.setdefault("prefix_cache", True)
    return InferenceEngine(model, params, **kw)


@pytest.fixture(scope="module")
def ref_engine(model_params):
    """Session-less reference engine for golden comparisons (module
    scoped — engine construction re-jits every program)."""
    model, params = model_params
    return _engine(model, params)


P1 = [(i * 11 + 3) % 128 for i in range(40)]
EXTRA = [(i * 5 + 1) % 128 for i in range(12)]
SP = SamplingParams(greedy=True, max_tokens=10)


def _run(eng, prompt, sid=None):
    h = eng.submit(prompt, SP, session_id=sid)
    while eng.step():
        pass
    return h.result()


# --- consistent-hash ring ----------------------------------------------------


def test_ring_deterministic_and_balanced():
    nodes = [f"http://h{i}:8000" for i in range(4)]
    a, b = ConsistentHashRing(nodes), ConsistentHashRing(list(nodes))
    keys = [f"sess-{k}" for k in range(400)]
    owned = {n: 0 for n in nodes}
    for k in keys:
        assert a.owner(k) == b.owner(k)    # pure function of topology
        owned[a.owner(k)] += 1
    assert min(owned.values()) >= 0.05 * len(keys), owned
    # two-choice set: distinct nodes, primary first
    o2 = a.owners("sess-0", 2)
    assert len(o2) == 2 and o2[0] != o2[1] and o2[0] == a.owner("sess-0")
    assert len(a.owners("sess-0", 99)) == len(nodes)


@pytest.mark.parametrize("change", ["leave", "join"])
def test_ring_churn_remaps_at_most_one_nth_plus_slack(change):
    nodes = [f"http://h{i}:8000" for i in range(4)]
    keys = [f"sess-{k}" for k in range(500)]
    before = ConsistentHashRing(nodes)
    after_nodes = (nodes[:-1] if change == "leave"
                   else nodes + ["http://h9:8000"])
    after = ConsistentHashRing(after_nodes)
    moved = sum(before.owner(k) != after.owner(k) for k in keys)
    n = max(len(nodes), len(after_nodes))
    assert 0 < moved <= len(keys) / n + 0.10 * len(keys), moved
    # survivors keep their keys: every moved key now maps to the new
    # node (join) / off the dead node (leave)
    if change == "leave":
        dead = nodes[-1]
        assert all(after.owner(k) != dead for k in keys)
        assert all(before.owner(k) == dead
                   for k in keys if before.owner(k) != after.owner(k))
    else:
        assert all(after.owner(k) == "http://h9:8000"
                   for k in keys if before.owner(k) != after.owner(k))


# --- HashRingRouter ----------------------------------------------------------


def _ring_router(n=4, **kw):
    ups = [Upstream(f"http://h{i}:8000", "m", group="chat")
           for i in range(n)]
    return HashRingRouter(ups, **kw), ups


def test_ring_router_sticky_and_leave_bound():
    router, ups = _ring_router(4)
    keys = [f"s{k}" for k in range(200)]
    first = {k: router.pick_for_request(
        "chat", {"session_id": k}).base_url for k in keys}
    # stable on repeat: zero remaps, all primary picks
    for k in keys:
        assert router.pick_for_request(
            "chat", {"session_id": k}).base_url == first[k]
    snap = router.ring_snapshot()
    assert snap["remapped"] == 0 and snap["rebuilds"] == 0
    assert snap["picks"]["primary"] == 2 * len(keys)
    # one replica leaves: ≤ 1/N + slack of sessions move, one rebuild
    dead = ups[2].base_url
    router.upstreams = [u for u in ups if u.base_url != dead]
    for k in keys:
        got = router.pick_for_request("chat", {"session_id": k}).base_url
        assert got != dead
        if first[k] != dead:
            assert got == first[k]          # survivors keep their keys
    snap = router.ring_snapshot()
    assert snap["rebuilds"] == 1
    assert 0 < snap["remapped"] <= len(keys) / 4 + 0.10 * len(keys)


def test_ring_router_cooldown_walks_successors_then_comes_home():
    import time as _time

    router, ups = _ring_router(3)
    key = "cool-session"
    home = router.pick_for_request("chat", {"session_id": key})
    home.cooldown_until = _time.time() + 60
    moved = router.pick_for_request("chat", {"session_id": key})
    assert moved.base_url != home.base_url
    # deterministic successor, and no ring rebuild happened
    assert router.pick_for_request(
        "chat", {"session_id": key}).base_url == moved.base_url
    assert router.ring_snapshot()["rebuilds"] == 0
    home.cooldown_until = 0.0
    assert router.pick_for_request(
        "chat", {"session_id": key}).base_url == home.base_url


def test_ring_router_bounded_load_overflows_to_second_owner():
    router, ups = _ring_router(4, bound=1.25)
    key = "hot-session"
    home = router.pick_for_request("chat", {"session_id": key})
    home.pending = 50                       # far past bound * mean
    second = router.pick_for_request("chat", {"session_id": key})
    assert second.base_url != home.base_url
    assert router.ring_snapshot()["picks"]["second"] >= 1
    # deterministic second choice — its cache warms too
    assert router.pick_for_request(
        "chat", {"session_id": key}).base_url == second.base_url
    home.pending = 0
    assert router.pick_for_request(
        "chat", {"session_id": key}).base_url == home.base_url


def test_ring_router_key_priority_and_fallback():
    router, _ = _ring_router(4)
    body_sid = {"session_id": "s1",
                "messages": [{"role": "user", "content": "hi"}]}
    body_pfx = {"messages": [{"role": "user", "content": "hi"}]}
    assert HashRingRouter.ring_key(body_sid) == "sid:s1"
    assert HashRingRouter.ring_key(body_pfx).startswith("pfx:")
    assert HashRingRouter.ring_key({"model": "ada"}) == "tenant:ada"
    assert HashRingRouter.ring_key({}) is None
    # keyless bodies load-balance (and never touch remap accounting)
    router.pick_for_request("chat", {})
    assert router.ring_snapshot()["tracked"] == 0


def test_gateway_exports_ring_families_for_any_router():
    router, _ = _ring_router(2)
    gw = Gateway(router, health_check_interval_s=0)
    router.pick_for_request("chat", {"session_id": "s"})
    text = gw.metrics_text()
    assert 'gateway_ring_picks_total{choice="primary"} 1' in text
    assert "gateway_ring_remapped_total 0" in text
    assert "gateway_ring_sessions_tracked 1" in text
    # plain routers: families present (census-stable), no samples
    plain = Gateway(Router([Upstream("http://h:1", "m", group="chat")]),
                    health_check_interval_s=0)
    assert "gateway_ring_picks_total" in plain.metrics_text()


# --- PrefixAffinityRouter bugfix ---------------------------------------------


def test_affinity_keys_by_base_url_not_object_identity():
    """Regression (gateway.py id(upstream) bug): the sticky table must
    survive the upstream OBJECTS being replaced — autoscaler churn
    rebuilds the list, and ``id()`` values get reused by the
    allocator, silently mis-pinning sessions."""
    urls = ["http://a:1", "http://b:1"]
    router = PrefixAffinityRouter(
        [Upstream(u, "m", group="chat") for u in urls])
    body = {"messages": [{"role": "user", "content": "pin me"}]}
    home = router.pick_for_request("chat", body)
    # replace every Upstream with a fresh object (new ids, same urls),
    # and make the OTHER replica strictly less loaded — only a working
    # sticky hit keeps the session home
    fresh = [Upstream(u, "m", group="chat") for u in urls]
    for u in fresh:
        if u.base_url != home.base_url:
            u.pending = 0
        else:
            u.pending = 1
    router.upstreams = fresh
    kept = router.pick_for_request("chat", body)
    assert kept.base_url == home.base_url
    assert kept.affinity_hits == 1


def test_affinity_invalidated_when_replica_leaves():
    urls = ["http://a:1", "http://b:1"]
    router = PrefixAffinityRouter(
        [Upstream(u, "m", group="chat") for u in urls])
    body = {"messages": [{"role": "user", "content": "pin me"}]}
    home = router.pick_for_request("chat", body)
    survivor = [u for u in urls if u != home.base_url][0]
    router.upstreams = [Upstream(survivor, "m", group="chat"),
                        Upstream("http://c:1", "m", group="chat")]
    got = router.pick_for_request("chat", body)
    assert got.base_url in (survivor, "http://c:1")
    # the stale pin is GONE, not lingering at a vanished url
    with router._lock:
        assert all(v[1] != home.base_url
                   for v in router._affinity.values())


# --- SessionStore (unit, fake pool) ------------------------------------------


class _FakePool:
    def __init__(self):
        self.refs: dict[int, int] = {}
        self.reclaim = None

    def share(self, pages):
        for p in pages:
            self.refs[p] = self.refs.get(p, 0) + 1

    def release(self, pages):
        for p in pages:
            self.refs[p] -= 1
            if self.refs[p] == 0:
                del self.refs[p]


def _store(**kw):
    pool = _FakePool()
    kw.setdefault("ttl_s", 100.0)
    store = SessionStore(**kw)
    store.attach(types.SimpleNamespace(
        handoff=None,
        # page_bytes 0: a fake pool's pins book nothing to the
        # process-wide HBM ledger
        paged=types.SimpleNamespace(pool=pool, page_size=16,
                                    page_bytes=0)))
    return store, pool


def test_store_pin_replace_and_release():
    store, pool = _store()
    store.note_finish("s", [1] * 32, [10, 11], cache_outcome="cold")
    assert pool.refs == {10: 1, 11: 1}
    store.note_finish("s", [1] * 64, [10, 11, 12], cache_outcome="partial")
    assert pool.refs == {10: 1, 11: 1, 12: 1}   # re-pin, never double
    assert store.lookup("s").turns == 2
    assert store.counters()["turns"] == {"hit": 0, "partial": 1, "cold": 1}
    assert store.drop("s") and pool.refs == {}


def test_store_ttl_and_capacity_eviction():
    clk = {"t": 0.0}
    store, pool = _store(ttl_s=10.0, max_sessions=2,
                         clock=lambda: clk["t"])
    store.note_finish("a", [1], [1])
    store.note_finish("b", [1], [2])
    store.note_finish("c", [1], [3])            # capacity: LRU 'a' dies
    assert store.lookup("a") is None and 1 not in pool.refs
    assert store.evictions["capacity"] == 1
    clk["t"] = 11.0
    assert store.sweep() == 2                   # TTL kills b and c
    assert store.active == 0 and pool.refs == {}
    assert store.evictions["ttl"] == 2


def test_store_pressure_reclaim_newest_pages_first():
    store, pool = _store()
    store.note_finish("old", list(range(64)), [1, 2, 3, 4])
    store.note_finish("new", list(range(64)), [9, 8, 7, 6])
    freed = store.reclaim_pages(2)
    assert freed == 2
    # LRU session first ('old'), NEWEST pages first — the surviving
    # pin [1, 2] is still a valid chain prefix
    assert store.lookup("old").pages == [1, 2]
    assert store.lookup("new").pages == [9, 8, 7, 6]
    assert 3 not in pool.refs and 4 not in pool.refs
    assert store.evictions["pressure"] == 1
    # pool-hook chaining: the prior hook's shortfall reaches sessions
    freed = pool.reclaim(3)
    assert freed == 3 and store.pinned_pages == 3


def test_store_reclaim_chains_after_prior_hook():
    pool = _FakePool()
    pool.reclaim = lambda n: min(n, 2)          # the COW index frees 2
    store = SessionStore(ttl_s=100.0)
    store.attach(types.SimpleNamespace(
        handoff=None,
        # page_bytes 0: a fake pool's pins book nothing to the
        # process-wide HBM ledger
        paged=types.SimpleNamespace(pool=pool, page_size=16,
                                    page_bytes=0)))
    store.note_finish("s", list(range(64)), [1, 2, 3, 4])
    assert pool.reclaim(3) == 3                 # 2 prior + 1 session pin
    assert store.lookup("s").pages == [1, 2, 3]


def _host(length, token_ids, **kw):
    return types.SimpleNamespace(length=length, token_ids=token_ids,
                                 last_logits=None, slot_axis=0, **kw)


def test_adopt_and_take_pending_validation():
    store, _ = _store()
    # entries without token ids can't be validated → lost
    assert not store.adopt("s", _host(32, None))
    assert store.pulls["lost"] == 1
    toks = list(range(32))
    assert store.adopt("s", _host(32, toks))
    assert store.known("s")
    # longest-common-prefix match, capped at KV length
    host, n = store.take_pending("s", toks + [99, 98])
    assert n == 32
    # consume-once
    assert store.take_pending("s", toks) is None
    # diverging tail → shorter match
    assert store.adopt("s", _host(32, toks))
    _, n = store.take_pending("s", toks[:20] + [101] * 12)
    assert n == 20
    # zero-length match (sid reused by another conversation) → lost
    assert store.adopt("s", _host(32, toks))
    assert store.take_pending("s", [101, 102, 103]) is None
    assert store.pulls["lost"] == 2
    assert store.pulls["claimed"] == 3


# --- engine integration ------------------------------------------------------


def test_session_turns_pin_and_warm_hit(model_params, ref_engine):
    model, params = model_params
    store = SessionStore()
    eng = _engine(model, params, session_store=store)
    outs1 = _run(eng, P1, sid="conv")
    sess = store.lookup("conv")
    assert sess is not None and sess.turns == 1
    hist = len(P1) + len(outs1) - 1             # final token's KV unwritten
    assert len(sess.pages) == hist // 16        # full-page chain pinned
    assert sess.token_ids == (P1 + outs1)[:hist]
    # follow-up turn: golden-identical to a cold engine, admitted warm
    p2 = P1 + outs1 + EXTRA
    want = ref_engine.generate(p2, SP)
    assert _run(eng, p2, sid="conv") == want
    c = store.counters()
    assert c["turns"]["hit"] + c["turns"]["partial"] == 1
    assert c["turns"]["cold"] == 1
    assert store.lookup("conv").turns == 2
    dbg = eng.debug_sessions()
    assert dbg["enabled"] and dbg["active"] == 1
    assert dbg["sessions"][0]["turns"] == 2
    eng.stop()                                  # close() drops every pin
    assert store.active == 0


def test_session_migration_via_pool_is_golden(model_params, ref_engine):
    """The mid-trace replica-kill story: A serves turn 1 and publishes;
    A dies; B claims the entry from the pool, token-validates, and
    serves turn 2 bit-identically to a cold engine."""
    model, params = model_params
    hand = LocalHandoff()
    store_a = SessionStore()
    eng_a = _engine(model, params, handoff=hand, session_store=store_a)
    outs1 = _run(eng_a, P1, sid="mig")
    assert store_a.flush(), "publisher did not drain"
    assert store_a.counters()["pulls"]["published"] == 1
    host = hand.claim(session_hid("mig"))       # what B's api layer does
    assert host is not None and host.token_ids is not None
    nfull = (len(P1) + len(outs1) - 1) // 16 * 16
    assert host.length == nfull
    assert list(host.token_ids) == (P1 + outs1)[:nfull]

    store_b = SessionStore()
    eng_b = _engine(model, params, session_store=store_b)
    assert store_b.adopt("mig", host)
    p2 = P1 + outs1 + EXTRA
    want = ref_engine.generate(p2, SP)
    assert _run(eng_b, p2, sid="mig") == want
    cb = store_b.counters()
    assert cb["pulls"]["claimed"] == 1
    assert cb["turns"]["partial"] == 1          # admitted warm, not cold
    # B now owns the session: pinned + republishable
    assert store_b.lookup("mig").turns == 1
    assert store_b.pinned_pages > 0


def test_session_pool_miss_degrades_to_local_prefill(model_params,
                                                     ref_engine):
    """A dead/empty pool NEVER fails the request — counted lost, local
    re-prefill, correct tokens."""
    model, params = model_params
    hand = LocalHandoff()
    assert hand.claim(session_hid("ghost")) is None
    store = SessionStore()
    eng = _engine(model, params, session_store=store)
    store.note_lost()                           # what the api layer counts
    want = ref_engine.generate(P1, SP)
    assert _run(eng, P1, sid="ghost") == want
    c = store.counters()
    assert c["pulls"]["lost"] == 1 and c["turns"]["cold"] == 1


def test_mismatched_pull_discarded_never_scattered(model_params,
                                                   ref_engine):
    """A pulled entry whose token ids share NO prefix with the prompt
    (sid reuse) must be dropped before any device scatter."""
    model, params = model_params
    hand = LocalHandoff()
    store_a = SessionStore()
    eng_a = _engine(model, params, handoff=hand, session_store=store_a)
    _run(eng_a, P1, sid="reused")
    assert store_a.flush()
    host = hand.claim(session_hid("reused"))
    store_b = SessionStore()
    eng_b = _engine(model, params, session_store=store_b)
    assert store_b.adopt("reused", host)
    other = [(i * 13 + 7) % 128 for i in range(48)]
    assert other[0] != P1[0]
    want = ref_engine.generate(other, SP)
    assert _run(eng_b, other, sid="reused") == want
    assert store_b.counters()["pulls"]["lost"] == 1


def test_hostentry_token_ids_wire_roundtrip():
    from llm_in_practise_tpu.serve.kv_pool import (
        HostEntry, decode_entry, encode_entry,
    )

    rows = [{"k": np.arange(8, dtype=np.float32).reshape(1, 2, 2, 2)}]
    host = HostEntry(length=2, bucket=2, rows=rows, last_logits=None,
                     token_ids=[5, 7])
    got = decode_entry(encode_entry(host))
    assert got.token_ids == [5, 7]
    np.testing.assert_array_equal(got.rows[0]["k"], rows[0]["k"])
    # legacy entries (no token ids) stay None — adopt() rejects them
    legacy = HostEntry(length=2, bucket=2, rows=rows, last_logits=None)
    assert decode_entry(encode_entry(legacy)).token_ids is None


# --- HTTP surface ------------------------------------------------------------


class _CharTok:
    """Invertible toy tokenizer (ids = code points mod 128): decoded
    replies re-encode to the SAME ids, so a rendered multi-turn ChatML
    prompt token-matches the published session history."""

    def encode(self, text):
        return [ord(c) % 128 for c in text][:180]

    def decode(self, ids):
        return "".join(chr(int(i) % 128) for i in ids)


def test_http_session_flow_and_debug_endpoint(model_params):
    import json
    import urllib.request

    from llm_in_practise_tpu.serve.api import OpenAIServer

    model, params = model_params
    store = SessionStore()
    eng = _engine(model, params, session_store=store)
    srv = OpenAIServer(eng, _CharTok(), model_name="m")
    port = srv.serve(host="127.0.0.1", port=0, background=True)
    base = f"http://127.0.0.1:{port}"
    try:
        def chat(messages, **hdr):
            req = urllib.request.Request(
                f"{base}/v1/chat/completions",
                data=json.dumps({"model": "m", "max_tokens": 6,
                                 "temperature": 0.0,
                                 "messages": messages}).encode(),
                headers={"Content-Type": "application/json", **hdr})
            with urllib.request.urlopen(req, timeout=30) as r:
                return json.loads(r.read())

        msgs = [{"role": "user", "content": "hello"}]
        got = chat(msgs, **{"X-Session-ID": "web-1"})
        reply = got["choices"][0]["message"]["content"]
        msgs += [{"role": "assistant", "content": reply},
                 {"role": "user", "content": "and again"}]
        chat(msgs, **{"X-Session-ID": "web-1"})

        with urllib.request.urlopen(f"{base}/debug/sessions",
                                    timeout=10) as r:
            dbg = json.loads(r.read())
        assert dbg["enabled"] and dbg["active"] == 1
        assert dbg["sessions"][0]["session_id"] == "web-1"
        assert dbg["sessions"][0]["turns"] == 2
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
            text = r.read().decode()
        assert "llm_sessions_active 1" in text
        assert 'llm_session_turns_total{cache="cold"} 1' in text
        assert "llm_session_pinned_pages" in text
    finally:
        srv.shutdown()


def test_http_claim_on_miss_pulls_from_shared_pool(model_params):
    """Two OpenAIServers over one handoff pool: turn 1 lands on A,
    turn 2 on B (the ring remapped) — B claims A's published entry at
    admission and serves the session warm."""
    import json
    import urllib.request

    from llm_in_practise_tpu.serve.api import OpenAIServer

    model, params = model_params
    hand = LocalHandoff()
    stores, servers, ports = [], [], []
    try:
        for _ in range(2):
            st = SessionStore()
            e = _engine(model, params, handoff=hand, session_store=st)
            srv = OpenAIServer(e, _CharTok(), model_name="m")
            ports.append(srv.serve(host="127.0.0.1", port=0,
                                   background=True))
            stores.append(st)
            servers.append(srv)

        def chat(port, messages, sid):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/chat/completions",
                data=json.dumps({"model": "m", "max_tokens": 6,
                                 "temperature": 0.0, "session_id": sid,
                                 "messages": messages}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                return json.loads(r.read())

        msgs = [{"role": "user", "content": "hello fleet"}]
        got = chat(ports[0], msgs, "moved-1")
        reply = got["choices"][0]["message"]["content"]
        assert stores[0].flush(), "A's publish did not drain"
        msgs += [{"role": "assistant", "content": reply},
                 {"role": "user", "content": "follow up"}]
        got2 = chat(ports[1], msgs, "moved-1")
        assert got2["choices"][0]["message"]["content"]
        cb = stores[1].counters()
        assert cb["pulls"]["claimed"] == 1      # pulled, token-validated
        assert cb["turns"]["partial"] == 1      # and admitted WARM
        assert stores[1].lookup("moved-1") is not None
    finally:
        for srv in servers:
            srv.shutdown()


# --- bench artifact + smoke --------------------------------------------------


def test_bench_sessions_artifact_gates():
    """The checked-in BENCH_SESSIONS artifact meets the acceptance
    criteria: warm-turn TTFT strictly below the paired cold TTFT,
    session hit-rate >= the gate, the churn drill's keyspace probe
    shows zero stray owner moves with the victim's arc share inside
    1/N + slack, at least one migrated session pulled its KV from the
    pool, and no stream dropped or diverged."""
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "BENCH_SESSIONS_r12.json")) as f:
        artifact = json.load(f)
    ttft = artifact["ttft"]
    assert ttft["warm_turn_mean_ms"] < ttft["paired_cold_mean_ms"]
    assert ttft["warm_speedup_x"] > 1.0
    assert artifact["session_hit_rate"] >= artifact["hit_rate_gate"]
    churn = artifact["churn"]
    assert churn["probe_stray_moves"] == 0
    assert churn["fraction"] <= churn["bound"]
    assert churn["migrated_claimed"] >= 1
    assert artifact["golden_mismatches"] == 0
    assert artifact["dropped_streams"] == 0
    assert artifact["turns_by_cache"]["hit"] + \
        artifact["turns_by_cache"]["partial"] > 0


def test_session_bench_smoke(tmp_path):
    """End-to-end CPU smoke of the bench harness itself (tiny trace,
    2 replicas + churn drill). Tier-1 on purpose — the warm path's
    whole promise is cross-process, and this is the one test that
    drives gateway ring -> engine sessions -> kv-pool migration in a
    single run. The gates inside main() are the assertions."""
    from tools.session_bench import main

    artifact = main(quick=True, out=str(tmp_path / "sessions.json"))
    assert artifact["quick"] is True
    assert artifact["churn"]["migrated_claimed"] >= 1
