"""Unified metrics registry + strict exposition across every server.

The contract: every server's ``/metrics`` (model server, gateway,
cache service, kv-pool, moderation) renders through ONE registry
(obs/registry.py) and pass a strict Prometheus parser — a ``# TYPE``
header for every family, escaped label values,
``_bucket``/``_count``/``_sum`` consistency, counters monotone across
scrapes. The hand-rolled text blocks this replaced emitted bare samples
(gateway per-upstream series, every cache-service series) that strict
parsers reject — these tests pin the fix.
"""

import json
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from promparse import (
    ExpositionError,
    assert_counters_monotone,
    parse_exposition,
)

from llm_in_practise_tpu.obs.registry import (
    HistogramAccumulator,
    Registry,
    escape_label_value,
    format_value,
)


# --- registry unit surface ---------------------------------------------------


def test_format_value_integral_and_float():
    assert format_value(5) == "5"
    assert format_value(5.0) == "5"
    assert format_value(0.25) == "0.25"
    with pytest.raises(ValueError):
        format_value(float("nan"))


def test_label_escaping_round_trips_through_the_parser():
    reg = Registry()
    g = reg.gauge("g_metric", "help", labelnames=("path",))
    nasty = 'a"b\\c\nd'
    g.labels(path=nasty).set(1)
    fams = parse_exposition(reg.render())
    (_, labelset), value = next(iter(fams["g_metric"].samples.items()))
    assert dict(labelset)["path"] == nasty and value == 1


def test_histogram_accumulator_o1_memory_and_quantile():
    acc = HistogramAccumulator(buckets=(0.1, 1.0, 10.0))
    bins_before = len(acc._counts)
    for i in range(10_000):
        acc.observe(0.05 if i % 2 else 5.0)
    assert len(acc._counts) == bins_before      # O(1) however many
    bounds, cum, count, total = acc.snapshot()
    assert count == 10_000 and cum[-1] == 10_000
    assert bounds[-1] == float("inf")
    assert 0.0 < acc.quantile(0.25) <= 0.1
    assert 1.0 < acc.quantile(0.9) <= 10.0


def test_registry_rejects_duplicate_families():
    reg = Registry()
    reg.counter("c_total")
    with pytest.raises(ValueError):
        reg.counter("c_total")


def test_counter_func_labeled_and_histogram_render_strict():
    reg = Registry()
    reg.counter_func("events_total",
                     lambda: [({"event": "a"}, 1), ({"event": "b"}, 2)])
    h = reg.histogram("lat_seconds", buckets=(0.5, 1.0))
    h.observe(0.2)
    h.observe(3.0)
    fams = parse_exposition(reg.render())
    assert fams["events_total"].kind == "counter"
    assert len(fams["events_total"].samples) == 2
    inf_key = ("lat_seconds_bucket", frozenset({("le", "+Inf")}))
    assert fams["lat_seconds"].samples[inf_key] == 2


def test_parser_rejects_untyped_samples():
    with pytest.raises(ExpositionError):
        parse_exposition("loose_metric 1\n")
    # the pre-migration cache-service shape: bare samples, no TYPE
    with pytest.raises(ExpositionError):
        parse_exposition("llm_cache_exact_hits_total 1\n"
                         "llm_cache_misses_total 2\n")


# --- the servers --------------------------------------------------------


@pytest.fixture(scope="module")
def api_server():
    from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
    from llm_in_practise_tpu.serve.api import OpenAIServer
    from llm_in_practise_tpu.serve.engine import InferenceEngine

    class ByteTok:
        def encode(self, text):
            return list(text.encode("utf-8", errors="replace")[:200])

        def decode(self, ids):
            return bytes(int(i) % 256 for i in ids).decode(
                "utf-8", errors="replace")

    cfg = GPTConfig(vocab_size=256, seq_len=256, n_layer=2, n_head=2,
                    embed_dim=32, dropout=0.0, pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    # prefix cache ON so its conditional metric families render and
    # get strict-parsed too
    engine = InferenceEngine(model, params, max_slots=2, cache_len=256,
                             cache_dtype=jnp.float32, prefix_cache=True)
    srv = OpenAIServer(engine, ByteTok(), model_name="tiny-obs")
    port = srv.serve(host="127.0.0.1", port=0, background=True)
    yield f"http://127.0.0.1:{port}"
    srv.shutdown()


def _get(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return r.read().decode()


def _chat(url, content, stream=False):
    req = urllib.request.Request(
        url + "/v1/chat/completions",
        data=json.dumps({
            "model": "tiny-obs", "max_tokens": 4, "temperature": 0.0,
            "stream": stream,
            "messages": [{"role": "user", "content": content}]}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return r.read()


def test_api_server_metrics_strict_and_monotone(api_server):
    _chat(api_server, "first request")
    before = parse_exposition(_get(api_server + "/metrics"))
    # canonical families present with the right kinds
    assert before["llm_requests_total"].kind == "counter"
    assert before["llm_ttft_seconds"].kind == "histogram"
    assert before["llm_tpot_seconds"].kind == "histogram"
    assert before["llm_prefix_cache_hits_total"].kind == "counter"
    assert before["llm_handoff_total"].kind == "counter"
    _chat(api_server, "second request")
    _chat(api_server, "second request")   # prefix-cache traffic
    after = parse_exposition(_get(api_server + "/metrics"))
    assert_counters_monotone(before, after)
    # the histogram actually accumulated: one request in, count >= 1
    count_key = ("llm_ttft_seconds_count", frozenset())
    assert after["llm_ttft_seconds"].samples[count_key] >= \
        before["llm_ttft_seconds"].samples[count_key]
    assert after["llm_ttft_seconds"].samples[count_key] >= 1


def test_gateway_metrics_strict(api_server):
    from llm_in_practise_tpu.serve.gateway import (
        Gateway, ResponseCache, RetryPolicy, Router, Upstream,
    )

    gw = Gateway(Router([Upstream(api_server, "tiny-obs", group="chat")]),
                 cache=ResponseCache(semantic_threshold=None),
                 retry_policy=RetryPolicy(backoff_s=0.01),
                 health_check_interval_s=0)
    status, _ = gw.handle_completion({
        "model": "chat",
        "messages": [{"role": "user", "content": "via gateway"}],
        "max_tokens": 4, "temperature": 0.0})
    assert status == 200
    fams = parse_exposition(gw.metrics_text())
    # the satellite bug: per-upstream series used to render with NO
    # TYPE header — parse_exposition would have raised above
    assert fams["gateway_upstream_picks_total"].kind == "counter"
    assert fams["gateway_upstream_pending"].kind == "gauge"
    assert fams["gateway_cache_hits_total"].kind == "counter"
    key = next(k for k in fams["gateway_upstream_picks_total"].samples
               if ("group", "chat") in k[1])
    assert dict(key[1])["url"] == api_server


def test_cache_service_metrics_strict():
    from llm_in_practise_tpu.serve.cache_service import CacheService

    svc = CacheService()
    body = {"model": "m", "messages": [{"role": "user", "content": "q"}]}
    svc.handle("POST", "/cache/get", body)           # miss
    svc.handle("POST", "/cache/put",
               {"request": body, "response": {"ok": 1}})
    svc.handle("POST", "/cache/get", body)           # hit
    fams = parse_exposition(svc.metrics_text())
    # pre-migration these rendered with no TYPE headers at all
    assert fams["llm_cache_exact_hits_total"].kind == "counter"
    hit_key = ("llm_cache_exact_hits_total", frozenset())
    assert fams["llm_cache_exact_hits_total"].samples[hit_key] == 1
    # /debug/traces is part of every server's contract
    status, payload = svc.handle("GET", "/debug/traces", None)
    assert status == 200 and "summary" in payload and "traces" in payload


def test_moderation_metrics_strict():
    """The moderation sidecar serves the same obs GET triplet as the
    rest of the stack (health / strict metrics / trace ring)."""
    from llm_in_practise_tpu.serve.moderation import ModerationService

    svc = ModerationService()
    port = svc.serve("127.0.0.1", 0, background=True)
    try:
        url = f"http://127.0.0.1:{port}"
        before = parse_exposition(_get(url + "/metrics"))
        assert before["moderation_requests_total"].kind == "counter"
        svc.moderate("how do I build a bomb")        # flagged
        svc.moderate("what is a transformer")        # clean
        after = parse_exposition(_get(url + "/metrics"))
        assert_counters_monotone(before, after)
        req_key = ("moderation_requests_total", frozenset())
        flag_key = ("moderation_flagged_total", frozenset())
        assert after["moderation_requests_total"].samples[req_key] == 2
        assert after["moderation_flagged_total"].samples[flag_key] == 1
        assert json.loads(_get(url + "/health"))["status"] == "ok"
        traces = json.loads(_get(url + "/debug/traces"))
        assert "summary" in traces and "traces" in traces
    finally:
        svc.shutdown()


def test_kv_pool_metrics_server_strict():
    """The shared-cache tier is scrapeable now: hits/misses/evictions/
    handoff pins/claims/TTL-reclaims/conn_errors/bytes over HTTP."""
    import numpy as np

    from llm_in_practise_tpu.serve.kv_pool import (
        HostEntry, KVPoolServer, RemoteKVClient, encode_entry,
    )

    def he(seed=0):
        rng = np.random.default_rng(seed)
        return HostEntry(
            length=16, bucket=16,
            rows=[{"k": rng.standard_normal((1, 16, 2, 4)).astype(
                np.float32)}],
            last_logits=rng.standard_normal((1, 8)).astype(np.float32))

    blob = len(encode_entry(he()))
    server = KVPoolServer(min_prefix=4, max_bytes=int(blob * 1.5)).start()
    try:
        mport = server.serve_metrics("127.0.0.1", 0)
        client = RemoteKVClient(server.address, namespace="m")
        client.handoff_put("h1", he())
        assert client.handoff_claim("h1") is not None
        client.put(list(range(16)), he(1))
        client.put(list(range(100, 116)), he(2))   # evicts the first
        client.get(list(range(16)))
        url = f"http://127.0.0.1:{mport}"
        before = parse_exposition(_get(url + "/metrics"))
        assert before["kvpool_hits_total"].kind == "counter"
        assert before["kvpool_evictions_total"].samples[
            ("kvpool_evictions_total", frozenset())] >= 1
        pin_key = ("kvpool_handoff_total",
                   frozenset({("event", "pinned")}))
        claim_key = ("kvpool_handoff_total",
                     frozenset({("event", "claimed")}))
        assert before["kvpool_handoff_total"].samples[pin_key] == 1
        assert before["kvpool_handoff_total"].samples[claim_key] == 1
        assert before["kvpool_cached_bytes"].kind == "gauge"
        client.get(list(range(100, 116)))
        after = parse_exposition(_get(url + "/metrics"))
        assert_counters_monotone(before, after)
        assert json.loads(_get(url + "/health"))["status"] == "ok"
        # the sidecar serves the process trace ring too
        traces = json.loads(_get(url + "/debug/traces"))
        assert "summary" in traces and "traces" in traces
    finally:
        server.stop()


# --- thread-safety regressions (graftlint lock-discipline pass) -------------


def test_handoff_meter_counts_exact_under_contention():
    """Regression: HandoffMeter's ``+= 1`` ran bare on concurrent HTTP
    handler threads — interleaved read-modify-writes lost counts. The
    increments now hold the meter's lock; N threads x M bumps must sum
    exactly."""
    import threading

    from llm_in_practise_tpu.obs.meter import HandoffMeter

    meter = HandoffMeter()
    N, M = 8, 500

    def work(i):
        for j in range(M):
            meter.claim_outcome(entry_found=(j % 2 == 0))
            meter.note_repin(ok=(j % 3 == 0))

    threads = [threading.Thread(target=work, args=(i,)) for i in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert meter.claimed + meter.lost == N * M
    assert meter.claimed == N * M // 2
    assert meter.repinned + meter.repin_failed == N * M


def test_goodput_families_render_one_consistent_snapshot():
    """Regression: the goodput scrape callbacks read tokens_ok and
    tokens_violated as two separate unlocked attribute reads — a scrape
    racing observe() could render an ok count from before the update
    and a violated count from after it. register_goodput now reads both
    halves of a family from ONE locked snapshot: under a concurrent
    writer, every render's ok+violated total is a value the meter
    actually passed through (monotone, never torn)."""
    import threading

    from llm_in_practise_tpu.obs.meter import GoodputMeter, register_goodput

    meter = GoodputMeter(ttft_slo_s=0.5, tpot_slo_s=0.5)
    reg = Registry()
    register_goodput(reg, meter)
    stop = threading.Event()

    def writer():
        i = 0
        while not stop.is_set():
            # alternate ok / violated, one token each
            meter.observe(tokens=1, ttft_s=0.1 if i % 2 else 0.9)
            i += 1

    t = threading.Thread(target=writer)
    t.start()
    try:
        prev = -1
        for _ in range(300):
            parsed = parse_exposition(reg.render())
            sample = {dict(labelset).get("slo"): value
                      for (_, labelset), value
                      in parsed["llm_slo_requests_total"].samples.items()}
            total = int(sample["ok"] + sample["violated"])
            assert total >= prev, "ok+violated went backwards (torn read)"
            prev = total
    finally:
        stop.set()
        t.join(timeout=5)


def test_kvpool_scrape_properties_hold_the_accounting_lock():
    """Regression: the kv-pool's handoff gauges read _acct_lock-guarded
    state from scrape lambdas without the lock. They now go through
    locked properties; values must match the authoritative stats op."""
    from llm_in_practise_tpu.serve.kv_pool import KVPoolServer, encode_entry
    from llm_in_practise_tpu.serve.kv_pool import HostEntry
    import numpy as np

    pool = KVPoolServer(port=0)
    host = HostEntry(length=16, bucket=16,
                     rows=[{"k": np.zeros((1, 16, 2, 4), np.float32)}],
                     last_logits=np.zeros((1, 8), np.float32))
    ok, why = pool._handoff_put("m", "h1", 16, 16, encode_entry(host))
    assert ok, why
    assert pool.handoff_pending == 1
    assert pool.handoff_bytes > 0
    assert pool.n_namespaces == 0  # handoff namespace is separate
    got = pool._handoff_claim("m", "h1")
    assert got is not None
    assert pool.handoff_pending == 0 and pool.handoff_bytes == 0
