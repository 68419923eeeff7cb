"""HBM ledger — byte attribution, reconciliation, churn-to-zero
(obs/hbm.py, ISSUE 19).

The acceptance matrix this file pins:

- ledger unit surface: signed booking with visible double-frees (a
  shortfall is a bug the gate must SEE, not clamp away), transient
  pulses that move the peak but not the balance, one-lock transfers,
  view/host accounts excluded from the device sum, fail-open
  reconciliation on stat-less backends;
- `/metrics` families render through the strict parser with one
  ``{owner}``-labelled sample per account, and ``/debug/hbm`` reads the
  same snapshot (they can never disagree);
- call-site lifecycle: an engine books its weights/KV on build and
  frees them on ``stop()`` — ``leaked_since(baseline)`` is empty after
  any build→serve→stop cycle (the churn-to-zero invariant);
- satellite cross-links: ``/debug/kv`` and ``/debug/hbm`` agree on the
  paged pool's bytes through the shared ``page_bytes`` exchange rate,
  and the draft cache's byte equivalent is a first-class account
  (``kv.draft``);
- the bench harness (tools/hbm_ledger_bench.py) drives all four churn
  legs — adapters, session pins, preempt-by-recompute, handoff — with
  its gates as the assertions.
"""

import http.client
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from promparse import parse_exposition

from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
from llm_in_practise_tpu.obs.cost import tree_bytes
from llm_in_practise_tpu.obs.hbm import (
    HOST_ACCOUNTS,
    VIEW_ACCOUNTS,
    HbmLedger,
    get_ledger,
    host_entry_bytes,
    register_hbm_ledger,
)
from llm_in_practise_tpu.serve.engine import InferenceEngine, SamplingParams


@pytest.fixture(scope="module")
def model_params():
    cfg = GPTConfig(vocab_size=64, seq_len=192, n_layer=2, n_head=4,
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


# --- ledger unit surface -----------------------------------------------------


def test_book_moves_balance_and_peak():
    led = HbmLedger(device_stats=lambda: {})
    led.book("weights/model", 100)
    led.book("weights/model", 50)
    led.book("weights/model", -30)
    snap = led.snapshot()["accounts"]["weights/model"]
    assert snap["bytes"] == 120
    assert snap["peak_bytes"] == 150
    assert snap["allocs"] == 2 and snap["frees"] == 1


def test_double_free_stays_visible_as_negative_balance():
    led = HbmLedger(device_stats=lambda: {})
    led.book("kv_pool.pages", 10)
    led.book("kv_pool.pages", -20)
    assert led.account_bytes("kv_pool.pages") == -10  # not clamped


def test_pulse_raises_peak_without_moving_bytes():
    led = HbmLedger(device_stats=lambda: {})
    led.book("kv_pool.pages", 100)
    led.pulse("transient_view", 40)
    led.pulse("transient_view", 25)
    tv = led.snapshot()["accounts"]["transient_view"]
    assert tv["bytes"] == 0                       # transient: no balance
    assert tv["peak_bytes"] == 40                 # high-water, not last
    assert tv["pulses"] == 2 and tv["last_pulse_bytes"] == 25
    # the coexistence semantics: a pulse on an account WITH a balance
    # peaks at balance + pulse
    led.book("transient_view", 10)
    led.pulse("transient_view", 40)
    assert led.snapshot()["accounts"]["transient_view"]["peak_bytes"] == 50


def test_transfer_conserves_the_device_total():
    led = HbmLedger(device_stats=lambda: {})
    led.book("weights/model", 100)
    led.transfer("weights/model", "weights/draft_model", 40)
    assert led.account_bytes("weights/model") == 60
    assert led.account_bytes("weights/draft_model") == 40
    assert led.device_bytes() == 100


def test_view_and_host_planes_excluded_from_device_sum():
    led = HbmLedger(device_stats=lambda: {})
    led.book("kv_pool.pages", 100)
    led.book("session_pins", 80)            # view INTO kv_pool.pages
    led.book("handoff_staging", 30)         # process RAM, not device
    assert "session_pins" in VIEW_ACCOUNTS
    assert "handoff_staging" in HOST_ACCOUNTS
    assert led.device_bytes() == 100        # no double counting


def test_reconciliation_residual_and_fail_open():
    led = HbmLedger(device_stats=lambda: {"bytes_in_use": 150})
    led.book("weights/model", 100)
    led.book("session_pins", 999)           # views never skew the residual
    assert led.unattributed_bytes() == 50
    tree = led.debug_tree()
    assert tree["reconciliation"]["unattributed_bytes"] == 50
    assert tree["reconciliation"]["fail_open"] is False
    open_led = HbmLedger(device_stats=lambda: {})
    open_led.book("weights/model", 100)
    assert open_led.unattributed_bytes() == 0   # fail-open, never a page
    assert open_led.debug_tree()["reconciliation"]["fail_open"] is True


def test_note_reclaim_accumulates_by_owner_and_reason():
    led = HbmLedger(device_stats=lambda: {})
    led.note_reclaim("kv_pool.pages", "preempt")
    led.note_reclaim("kv_pool.pages", "preempt", 2)
    led.note_reclaim("session_pins", "ttl")
    rows = {(r["owner"], r["reason"]): r["events"]
            for r in led.snapshot()["reclaims"]}
    assert rows == {("kv_pool.pages", "preempt"): 3,
                    ("session_pins", "ttl"): 1}


def test_leaked_since_diffs_against_a_baseline():
    led = HbmLedger(device_stats=lambda: {})
    led.book("weights/model", 100)
    base = led.baseline()
    led.book("kv_pool.pages", 64)
    assert led.leaked_since(base) == {"kv_pool.pages": 64}
    led.book("kv_pool.pages", -64)
    assert led.leaked_since(base) == {}


def test_debug_tree_groups_accounts_by_component():
    led = HbmLedger(device_stats=lambda: {})
    led.book("weights/model", 100)
    led.book("weights/draft_model", 40)
    led.book("session_pins", 16)
    tree = led.debug_tree()["tree"]
    assert tree["weights"]["bytes"] == 140
    assert set(tree["weights"]["accounts"]) == {"weights/model",
                                                "weights/draft_model"}
    assert tree["session_pins"]["accounts"]["session_pins"]["plane"] == "view"
    assert tree["weights"]["accounts"]["weights/model"]["plane"] == "device"


def test_host_entry_bytes_sums_rows_and_logits():
    class Host:
        rows = [{"k": np.zeros((4, 8), np.float32),
                 "v": np.zeros((4, 8), np.float32)}]
        last_logits = np.zeros(64, np.float32)

    assert host_entry_bytes(Host()) == 2 * 4 * 8 * 4 + 64 * 4
    assert host_entry_bytes(object()) == 0


# --- /metrics rendering ------------------------------------------------------


def test_register_hbm_ledger_renders_strict():
    from llm_in_practise_tpu.obs.registry import Registry

    led = HbmLedger(device_stats=lambda: {"bytes_in_use": 200})
    led.book("weights/model", 150)
    led.pulse("transient_view", 70)
    led.note_reclaim("kv_pool.pages", "preempt", 3)
    reg = Registry()
    register_hbm_ledger(reg, led)
    fams = parse_exposition(reg.render())
    bytes_fam = fams["llm_hbm_ledger_bytes"].samples
    assert bytes_fam[("llm_hbm_ledger_bytes",
                      frozenset({("owner", "weights/model")}))] == 150
    peaks = fams["llm_hbm_ledger_peak_bytes"].samples
    assert peaks[("llm_hbm_ledger_peak_bytes",
                  frozenset({("owner", "transient_view")}))] == 70
    recl = fams["llm_hbm_reclaims_total"].samples
    assert recl[("llm_hbm_reclaims_total",
                 frozenset({("owner", "kv_pool.pages"),
                            ("reason", "preempt")}))] == 3
    unatt = fams["llm_hbm_unattributed_bytes"].samples
    assert unatt[("llm_hbm_unattributed_bytes", frozenset())] == 50


# --- call-site lifecycle (churn-to-zero) -------------------------------------


def test_engine_books_on_build_and_restores_baseline_on_stop(model_params):
    model, params = model_params
    led = get_ledger()
    base = led.baseline()
    eng = _engine(model, params, kv_layout="paged", prefix_cache=True)
    grown = led.leaked_since(base)
    assert grown.get("weights/model") == tree_bytes(params)
    assert grown.get("kv_pool.pages") == eng.paged.pool_bytes
    assert eng.paged.pool_bytes == (eng.paged.pool.num_pages
                                    * eng.paged.page_bytes)
    out = eng.generate([1, 5, 9, 13], SamplingParams(greedy=True,
                                                     max_tokens=6))
    assert len(out) == 6
    # every paged dispatch pulsed the gather view
    tv = led.snapshot()["accounts"]["transient_view"]
    assert tv["pulses"] > 0 and tv["last_pulse_bytes"] > 0
    eng.prefix_cache.clear()
    eng.stop()
    assert led.leaked_since(base) == {}
    eng.stop()                                   # idempotent, no double free
    assert led.leaked_since(base) == {}


def test_a_decode_that_reads_pages_in_place_pulses_no_view():
    """A model that walks its paged layer's pages where they lie
    (``reads_pages``: Phi-4-mini-flash): its decode steps gather no view
    and pulse nothing; the chunk trip that prefills the prompt still
    pulses its ONE-row view."""
    from llm_in_practise_tpu.models import phi4flash as pf

    cfg = pf.phi4flash_config(compute_dtype="float32")
    eng = InferenceEngine(
        pf.Phi4Flash(cfg), pf.random_params(cfg, 3, jnp.float32, std=0.1),
        max_slots=2, cache_len=64, kv_layout="paged", kv_page_size=8,
        chunked_prefill=16, cache_dtype=jnp.float32)
    led = get_ledger()
    before = led.snapshot()["accounts"].get(
        "transient_view", {}).get("pulses", 0)
    out = eng.generate(list(range(4, 13)),
                       SamplingParams(greedy=True, max_tokens=5))
    assert len(out) == 5
    steps = eng.steptrace.records(limit=50)
    assert sum("shared_kv_pages_read" in r for r in steps) >= 4   # decodes
    tv = led.snapshot()["accounts"]["transient_view"]
    assert tv["pulses"] - before == 1
    assert tv["last_pulse_bytes"] == eng.paged.view_bytes(16, 1)
    eng.stop()


def test_contiguous_engine_books_kv_contiguous(model_params):
    model, params = model_params
    led = get_ledger()
    base = led.baseline()
    eng = _engine(model, params)
    grown = led.leaked_since(base)
    assert grown.get("kv.contiguous") == tree_bytes(eng.cache)
    assert eng.debug_kv()["ledger_account"] == "kv.contiguous"
    assert eng.debug_kv()["kv_bytes"] == tree_bytes(eng.cache)
    eng.stop()
    assert led.leaked_since(base) == {}


def test_draft_cache_is_a_first_class_account(model_params):
    """Satellite: the draft cache's byte equivalent (the kv_row_bytes
    exchange rate from the spec-decode budget) is the ``kv.draft``
    account, cross-linked from /debug/kv."""
    model, params = model_params
    led = get_ledger()
    base = led.baseline()
    eng = _engine(model, params, kv_layout="paged", kv_pool_tokens=1024,
                  speculative_k=3, draft_model=model, draft_params=params)
    grown = led.leaked_since(base)
    assert grown.get("kv.draft") == tree_bytes(eng.draft_cache)
    assert grown.get("weights/draft_model") == tree_bytes(params)
    snap = eng.debug_kv()
    assert snap["draft_kv_account_bytes"] == tree_bytes(eng.draft_cache)
    eng.stop()
    assert led.leaked_since(base) == {}


def test_adapter_registry_churn_to_zero(model_params):
    from llm_in_practise_tpu.peft.lora import LoRAConfig, init_lora
    from llm_in_practise_tpu.serve.multi_lora import AdapterRegistry

    model, params = model_params
    led = get_ledger()
    base = led.baseline()
    c = LoRAConfig(r=2, alpha=4.0, target_patterns=("attn/q_proj",))
    reg = AdapterRegistry(params)
    reg.register_tree("t0", init_lora(params, c, jax.random.PRNGKey(1)), c)
    per = reg.bytes_loaded
    assert led.leaked_since(base) == {"adapters/r2": per}
    budget = AdapterRegistry(params, max_bytes=int(per * 2.5))
    for i in range(5):
        budget.register_tree(
            f"t{i}", init_lora(params, c, jax.random.PRNGKey(i)), c)
    assert budget.evictions_total >= 3          # the budget really bit
    reclaims = {(r["owner"], r["reason"]): r["events"]
                for r in led.snapshot()["reclaims"]}
    assert reclaims[("adapters/r2", "budget")] >= 3
    for name in list(budget.names()) + ["t0"]:
        (budget if name in budget else reg).evict(name)
    assert led.leaked_since(base) == {}


def test_session_pins_expire_to_baseline(model_params):
    """Pins attribute pool pages to conversations; capacity + pressure
    + TTL each release them with a distinct reclaim reason, and the
    view account walks back to baseline."""
    from llm_in_practise_tpu.serve.sessions import SessionStore

    model, params = model_params
    led = get_ledger()
    base = led.baseline()
    store = SessionStore(ttl_s=0.2, max_sessions=2)
    eng = _engine(model, params, kv_layout="paged", prefix_cache=True,
                  session_store=store)
    eng.start()
    sp = SamplingParams(greedy=True, max_tokens=4)
    for k in range(3):                     # 3rd arrival: capacity evict
        eng.submit([k + 1, k + 2, k + 3, k + 4] * 5, sp,
                   session_id=f"s{k}").result()
    # over the baseline: the ledger is the process's, and an earlier
    # test of this worker that died before its cleanup left bytes in it
    pinned = led.account_bytes("session_pins") - base.get("session_pins", 0)
    assert pinned > 0
    assert pinned == store.pinned_pages * eng.paged.page_bytes
    store.reclaim_pages(1)                 # pressure evict
    time.sleep(0.25)
    store.sweep()                          # ttl evict
    assert led.account_bytes("session_pins") == base.get("session_pins", 0)
    reclaims = {(r["owner"], r["reason"]): r["events"]
                for r in led.snapshot()["reclaims"]}
    for reason in ("capacity", "pressure", "ttl"):
        assert reclaims.get(("session_pins", reason), 0) >= 1, reason
    eng.stop()
    store.close()
    assert led.leaked_since(base) == {}


# --- the debug/metrics HTTP surface ------------------------------------------


def test_debug_hbm_and_debug_kv_agree_over_http(model_params):
    """Satellite: one serving process, three windows — /debug/kv,
    /debug/hbm and /metrics — must tell the same byte story."""
    from llm_in_practise_tpu.serve.api import OpenAIServer

    class Tok:
        def encode(self, text):
            return list(text.encode()[:32])

        def decode(self, ids):
            return bytes(int(i) % 256 for i in ids).decode(
                "utf-8", "replace")

    model, params = model_params
    # the ledger is the process's: whatever an earlier test of this
    # worker left in the pool's account is not this server's
    before = get_ledger().account_bytes("kv_pool.pages")
    eng = _engine(model, params, kv_layout="paged", kv_pool_tokens=256,
                  prefix_cache=True)
    srv = OpenAIServer(eng, Tok(), model_name="hbm-test")
    eng.start()
    port = srv.serve(host="127.0.0.1", port=0, background=True)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("POST", "/v1/chat/completions", json.dumps({
            "model": "hbm-test",
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 4, "temperature": 0.0,
        }), {"Content-Type": "application/json"})
        assert conn.getresponse().status == 200
        conn.close()

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/debug/kv")
        kv = json.loads(conn.getresponse().read())
        conn.close()
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/debug/hbm")
        resp = conn.getresponse()
        assert resp.status == 200
        hbm = json.loads(resp.read())
        conn.close()

        # the cross-link: /debug/kv names its ledger account, and both
        # planes quote the SAME pool bytes through page_bytes
        assert kv["ledger_account"] == "kv_pool.pages"
        pool_acct = hbm["tree"]["kv_pool.pages"]["accounts"]["kv_pool.pages"]
        assert pool_acct["bytes"] - before == kv["pool_bytes"]
        # pages_total is USABLE capacity; the buffer also holds the
        # reserved trash page 0
        assert kv["pool_bytes"] == (kv["pages_total"] + 1) * kv["page_bytes"]
        assert kv["slot_mapped_bytes"] <= kv["pool_bytes"]
        assert hbm["reconciliation"]["fail_open"] in (True, False)
        # transient view pulsed during the completion above
        tv = hbm["tree"]["transient_view"]["accounts"]["transient_view"]
        assert tv["pulses"] > 0 and tv["peak_bytes"] > 0

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
        conn.close()
        fams = parse_exposition(text)           # strict parse
        sample = fams["llm_hbm_ledger_bytes"].samples[
            ("llm_hbm_ledger_bytes",
             frozenset({("owner", "kv_pool.pages")}))]
        assert sample - before == kv["pool_bytes"]
        assert "llm_hbm_unattributed_bytes" in fams
        assert "llm_hbm_ledger_peak_bytes" in fams
    finally:
        srv.shutdown()


# --- the bench harness -------------------------------------------------------


def test_hbm_ledger_bench_smoke(tmp_path):
    """End-to-end CPU smoke of the bench harness itself (all four churn
    legs). Tier-1 on purpose — this is the leak gate CI runs; the gates
    inside main() are the assertions."""
    from tools.hbm_ledger_bench import main

    artifact = main(quick=True, out=str(tmp_path / "hbm.json"))
    assert artifact["quick"] is True
    assert artifact["leaked_accounts"] == {}
    assert artifact["legs"]["paged_preempt"]["preemptions"] >= 1
