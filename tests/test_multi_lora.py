"""Batched multi-LoRA serving (serve/multi_lora.py, ISSUE 15).

One base model, N tenants in the same fused dispatch. The acceptance
matrix this file pins:

- mixed-adapter batch parity: base + two adapters (different rank
  buckets) interleaved in ONE engine produce tokens byte-identical to
  per-adapter merged-weight engines, across {contiguous, paged} ×
  {spec off, ngram} — the gathered-BGMV delta is exact, not approximate;
- the 1-jitted-dispatch-per-step invariant holds while slots carry
  heterogeneous adapters (DispatchMeter);
- registry lifecycle: hot-load into rank buckets, LRU eviction under a
  byte budget, refcount guards (busy adapters refuse eviction /
  hot-swap), zero leaked rows or bytes after churn;
- preemption-by-recompute under an adapter stays byte-identical and
  leaks no pages (the adapter pin rides the requeue);
- prefix-cache isolation: the same prompt under different adapters
  never cross-hits (namespace-shifted keys), same-adapter resubmission
  does hit;
- per-tenant fairness at the gateway: token-bucket quota exhaustion is
  a 429 before the upstream is touched, balances/rejections render;
- tensor-parallel leg: the factor banks shard with the base weights'
  rule and mixed-adapter parity holds at tp=2 (envcaps-guarded).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import envcaps
from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
from llm_in_practise_tpu.parallel import strategy as S
from llm_in_practise_tpu.peft.lora import LoRAConfig, init_lora, merge_lora
from llm_in_practise_tpu.serve.engine import (
    InferenceEngine,
    SamplingParams,
    shard_params_for_serving,
)
from llm_in_practise_tpu.serve.gateway import (
    Gateway,
    RetryPolicy,
    Router,
    Upstream,
)
from llm_in_practise_tpu.serve.multi_lora import (
    AdapterHandle,
    AdapterRegistry,
)

P0 = [1, 5, 9, 13, 2, 7, 1, 8, 2, 8, 3, 1, 4, 1, 5, 9]
P1 = [7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17, 18]
SP = SamplingParams(greedy=True, max_tokens=12)


def _noisy_b(tree, seed):
    """init_lora zeros B (delta starts at 0); randomize it so the
    adapters actually steer the tokens."""
    key = jax.random.PRNGKey(seed)
    out = {}
    for k, v in tree.items():
        key, sub = jax.random.split(key)
        out[k] = {"a": v["a"],
                  "b": jax.random.normal(sub, v["b"].shape) * 0.3}
    return out


@pytest.fixture(scope="module")
def world():
    # 4 heads / embed 32 so the tp=2 leg's contractions divide
    cfg = GPTConfig(vocab_size=64, seq_len=192, n_layer=2, n_head=4,
                    embed_dim=32, dropout=0.0, pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    c1 = LoRAConfig(r=2, alpha=4.0, target_patterns=("attn/q_proj", "mlp"))
    t1 = _noisy_b(init_lora(params, c1, jax.random.PRNGKey(1)), 2)
    c2 = LoRAConfig(r=3, alpha=6.0, target_patterns=("attn/q_proj",))
    t2 = _noisy_b(init_lora(params, c2, jax.random.PRNGKey(3)), 4)
    return model, params, (t1, c1), (t2, c2)


def _engine(model, params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("cache_len", 192)
    kw.setdefault("cache_dtype", jnp.float32)
    return InferenceEngine(model, params, **kw)


def _registry(world, **kw):
    model, params, (t1, c1), (t2, c2) = world
    reg = AdapterRegistry(params, **kw)
    reg.register_tree("t1", t1, c1)
    reg.register_tree("t2", t2, c2)
    return reg


@pytest.fixture(scope="module")
def refs(world):
    """Merged-weight golden tokens, computed ONCE: the thing the
    batched-BGMV path must reproduce exactly."""
    model, params, (t1, c1), (t2, c2) = world
    base = _engine(model, params).generate(P0, SP)
    m1 = _engine(model, merge_lora(params, t1, c1)).generate(P0, SP)
    m2 = _engine(model, merge_lora(params, t2, c2)).generate(P1, SP)
    assert m1 != base and m2 != base[:len(m2)]  # adapters really steer
    return base, m1, m2


# --- mixed-adapter golden parity --------------------------------------------


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
@pytest.mark.parametrize("spec", ["off", "ngram"])
def test_mixed_adapter_parity(world, refs, layout, spec):
    """base + t1 (rank bucket 2) + t2 (bucket 4) in one batch: every
    stream matches its merged-weight reference, and the heterogeneous
    decode steps stay ONE jitted dispatch."""
    model, params, *_ = world
    base_ref, m1_ref, m2_ref = refs
    kw = dict(kv_layout=layout)
    if spec == "ngram":
        kw.update(speculative_k=3)
    eng = _engine(model, params, adapter_registry=_registry(world), **kw)
    r0 = eng.submit(P0, SP)
    r1 = eng.submit(P0, SP, adapter="t1")
    r2 = eng.submit(P1, SP, adapter="t2")
    eng.step()                               # admission (prefill dispatches)
    while eng.step():
        if not eng.slot_prefill and any(eng.slot_adapter):
            # mixed adapters + adapter-none slots share one program
            assert eng.dispatch_meter.last_step == 1
    o0, o1, o2 = r0.result(), r1.result(), r2.result()
    assert o0 == base_ref
    assert o1 == m1_ref
    assert o2 == m2_ref
    # adapter pins dropped at finish: registry is drainable again
    reg = eng.adapter_registry
    assert all(v == 0 for v in reg.stats()["refcounts"].values())
    assert reg.stats()["tenant_tokens"] == {"t1": len(o1), "t2": len(o2)}


@pytest.mark.parametrize("fused", [True, False])
def test_adapters_chunk_beside_base_decoding(world, refs, fused):
    """(PR 28) The paged prefill loop picks each chunking row's adapter
    out of the slot plane: two adapters chunk-prefill while a base row
    decodes, in the same fused dispatch and (``mixed_step`` off) in the
    chunk-only program beside a decode dispatch, and every stream still
    matches its merged-weight engine."""
    model, params, *_ = world
    base_ref, m1_ref, m2_ref = refs
    eng = _engine(model, params, adapter_registry=_registry(world),
                  kv_layout="paged", chunked_prefill=4, mixed_step=fused)
    r0 = eng.submit(P0, SP)
    while r0.n_generated < 2:
        eng.step()
    r1 = eng.submit(P0, SP, adapter="t1")      # 4 chunks of 4
    r2 = eng.submit(P1, SP, adapter="t2")      # 3 chunks of 4
    eng.step()
    assert len(eng.slot_prefill) == 2
    assert (eng.mixed_blocks >= 1) == fused
    assert eng.dispatch_meter.last_step == (1 if fused else 2)
    while eng.step():
        pass
    assert r0.result() == base_ref
    assert r1.result() == m1_ref
    assert r2.result() == m2_ref
    assert eng.prefill_chunk_rows == 4 + 4 + 3     # r0's chunks too
    eng.stop()


def test_unknown_adapter_rejected_at_submit(world):
    model, params, *_ = world
    eng = _engine(model, params, adapter_registry=_registry(world))
    with pytest.raises(ValueError, match="unknown adapter"):
        eng.submit(P0, SP, adapter="nope")
    bare = _engine(model, params)
    with pytest.raises(ValueError, match="no adapter_registry"):
        bare.submit(P0, SP, adapter="t1")


def test_adapter_handle_pins_name(world, refs):
    """AdapterHandle (the OpenAI-surface view) injects its adapter on
    submit and proxies everything else to the shared engine."""
    model, params, *_ = world
    eng = _engine(model, params, adapter_registry=_registry(world))
    h = AdapterHandle(eng, "t1")
    r = h.submit(P0, SP)
    while eng.step():
        pass
    assert r.result() == refs[1]
    assert h.dispatch_meter is eng.dispatch_meter   # __getattr__ delegation


# --- registry lifecycle: hot-load, LRU evict, refcounts ---------------------


def test_registry_byte_budget_lru_evict(world):
    """Loading past max_bytes evicts the least-recently-used idle
    adapter; its bank row returns to the bucket free list and the byte
    ledger drops to exactly the survivor's payload."""
    model, params, (t1, c1), (t2, c2) = world
    probe = AdapterRegistry(params)
    probe.register_tree("t1", t1, c1)
    b1 = probe.stats()["bytes_loaded"]
    probe.register_tree("t2", t2, c2)
    b2 = probe.stats()["bytes_loaded"] - b1

    reg = AdapterRegistry(params, max_bytes=max(b1, b2))
    reg.register_tree("t1", t1, c1)
    reg.register_tree("t2", t2, c2)          # must push t1 out
    s = reg.stats()
    assert s["loaded"] == 1 and "t2" in reg and "t1" not in reg
    assert s["bytes_loaded"] == b2
    assert s["evictions_total"] == 1
    # t1's rank-2 row is free again; re-registering reuses it
    reg.evict("t2")
    reg.register_tree("t1", t1, c1)
    s = reg.stats()
    assert s["bytes_loaded"] == b1
    # row 0 of each bucket is the reserved all-zeros no-adapter row, so
    # exactly ONE adapter-occupied row remains across both buckets
    assert sum((b["cap"] - 1) - b["free"]
               for b in s["buckets"].values()) == 1


def test_registry_refuses_evicting_busy_adapter(world):
    model, params, (t1, c1), (t2, c2) = world
    reg = AdapterRegistry(params)
    reg.register_tree("t1", t1, c1)
    reg.acquire("t1")
    with pytest.raises(RuntimeError, match="in-flight"):
        reg.evict("t1")
    with pytest.raises(RuntimeError, match="busy"):
        reg.register_tree("t1", t1, c1)      # hot-swap needs a drain too
    # byte pressure cannot shed a busy adapter either
    busy_budget = AdapterRegistry(params,
                                  max_bytes=reg.stats()["bytes_loaded"])
    busy_budget.register_tree("t1", t1, c1)
    busy_budget.acquire("t1")
    with pytest.raises(RuntimeError, match="budget exhausted"):
        busy_budget.register_tree("t2", t2, c2)
    reg.release("t1")
    assert reg.evict("t1") is True
    assert reg.stats()["loaded"] == 0 and reg.stats()["bytes_loaded"] == 0


def test_registry_churn_zero_leaks(world):
    """Register/evict churn across both rank buckets: every row back on
    the free lists, byte ledger at zero, swap time monotonic."""
    model, params, (t1, c1), (t2, c2) = world
    reg = AdapterRegistry(params)
    for i in range(4):
        reg.register_tree(f"a{i}", t1, c1)
        reg.register_tree(f"b{i}", t2, c2)
    for i in range(4):
        assert reg.evict(f"a{i}") and reg.evict(f"b{i}")
    s = reg.stats()
    assert s["loaded"] == 0 and s["bytes_loaded"] == 0
    # every row except each bucket's reserved zero row 0 is free again
    assert all(b["free"] == b["cap"] - 1 for b in s["buckets"].values())
    assert s["loads_total"] == 8 and s["evictions_total"] == 8
    assert s["swap_seconds_total"] > 0


def test_recycled_row_carries_no_stale_delta(world, refs):
    """Evicting t1 and loading t2 into the recycled row must not leak
    t1's factors through bank keys t2 doesn't target (rows are zeroed
    on reuse)."""
    model, params, (t1, c1), (t2, c2) = world
    # same rank bucket for both so the row really is recycled
    c2b = LoRAConfig(r=2, alpha=float(c2.alpha) * 1.5,
                     target_patterns=c2.target_patterns)
    t2b = _noisy_b(init_lora(params, c2b, jax.random.PRNGKey(3)), 4)
    reg = AdapterRegistry(params)
    reg.register_tree("t1", t1, c1)          # targets q_proj + mlp
    reg.evict("t1")
    reg.register_tree("t2", t2b, c2b)        # targets q_proj only
    eng = _engine(model, params, adapter_registry=reg)
    got = eng.generate(P1, SP, adapter="t2")
    ref = _engine(model, merge_lora(params, t2b, c2b)).generate(P1, SP)
    assert got == ref


# --- preemption under an adapter (paged) ------------------------------------


def test_preemption_resume_exact_under_adapter(world):
    """Pool sized for ~2 of 3 requests with adapters pinned: preemption
    fires, the recompute-resume re-stamps the slot's adapter, and every
    stream matches its unconstrained merged-weight reference. Zero
    leaked pages after the cache clears, refcounts drain to zero."""
    model, params, (t1, c1), (t2, c2) = world
    sp = SamplingParams(greedy=True, max_tokens=40)
    prompts = [[(j * 3 + i) % 64 for i in range(20)] for j in range(3)]
    adapters = ["t1", None, "t2"]
    t = _engine(model, params, adapter_registry=_registry(world),
                kv_layout="paged", kv_pool_tokens=96, prefix_cache=True)
    rs = [t.submit(p, sp, adapter=a) for p, a in zip(prompts, adapters)]
    while t.step():
        pass
    outs = [r.result() for r in rs]
    assert t.preemptions > 0
    free = {
        "t1": _engine(model, merge_lora(params, t1, c1), kv_layout="paged"),
        None: _engine(model, params, kv_layout="paged"),
        "t2": _engine(model, merge_lora(params, t2, c2), kv_layout="paged"),
    }
    for p, a, out, r in zip(prompts, adapters, outs, rs):
        assert r.finish_reason in ("length", "stop")
        assert out == free[a].generate(p, sp)
    t.prefix_cache.clear()
    t.paged.pool.check_leaks(0)
    assert all(v == 0
               for v in t.adapter_registry.stats()["refcounts"].values())


# --- prefix-cache isolation across adapters ---------------------------------


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_prefix_cache_isolated_per_adapter(world, layout):
    """Same prompt under base, t1, t2: no cross-adapter hit (their KV
    differs — a shared entry would corrupt tokens); resubmitting under
    the SAME adapter does hit its own entry and stays byte-identical."""
    model, params, *_ = world
    # long enough for the paged index's full-page granularity (page 16)
    pfx = [(i * 5 + 2) % 64 for i in range(40)]
    eng = _engine(model, params, adapter_registry=_registry(world),
                  kv_layout=layout, prefix_cache=True)
    first = eng.generate(pfx, SP, adapter="t1")
    h0 = eng.prefix_cache.hits
    eng.generate(pfx, SP)                    # base: same tokens, ns 0
    eng.generate(pfx, SP, adapter="t2")      # other tenant
    assert eng.prefix_cache.hits == h0       # no cross-namespace hits
    again = eng.generate(pfx, SP, adapter="t1")
    assert eng.prefix_cache.hits == h0 + 1   # own namespace hits
    assert again == first


# --- gateway per-tenant fairness --------------------------------------------


def _quota_gateway(**kw):
    # upstream is never contacted: admission rejects before forwarding
    router = Router([Upstream("http://127.0.0.1:9", "m1", group="chat")])
    kw.setdefault("retry_policy", RetryPolicy(backoff_s=0.01))
    kw.setdefault("health_check_interval_s", 0)
    return Gateway(router, **kw)


def test_gateway_tenant_quota_429():
    """Token-bucket exhaustion: debiting actual completion tokens past
    the quota turns the NEXT request into a 429 without touching the
    upstream; the refill window restores admission."""
    gw = _quota_gateway(tenant_quotas={"chat": 10.0},
                        tenant_quota_window_s=1000.0)
    assert gw._tenant_admit("chat")
    gw._tenant_debit("chat", 15)             # actual usage overdraws (15>10)
    body = {"model": "chat",
            "messages": [{"role": "user", "content": "hello"}]}
    status, resp = gw.handle_completion(body)
    assert status == 429
    assert resp["error"]["type"] == "tenant_quota_exhausted"
    snap = gw._tenant_snapshot()
    assert snap["tokens"]["chat"] == 15
    assert snap["rejections"]["chat"] == 1
    assert snap["balance"]["chat"] <= 0.0
    # unmetered tenants are never throttled
    assert gw._tenant_admit("other")


def test_gateway_tenant_weight_scales_capacity():
    """weight multiplies a tenant's bucket: 2x weight admits 2x the
    tokens before the 429 kicks in."""
    gw = _quota_gateway(tenant_quotas={"gold": 10.0, "bronze": 10.0},
                        tenant_weights={"gold": 2.0},
                        tenant_quota_window_s=1000.0)
    assert gw._tenant_capacity("gold") == 20.0
    assert gw._tenant_capacity("bronze") == 10.0
    gw._tenant_debit("gold", 15)
    gw._tenant_debit("bronze", 15)
    assert gw._tenant_admit("gold")          # 5 tokens of headroom left
    assert not gw._tenant_admit("bronze")    # overdrawn


def test_gateway_tenant_goodput_split():
    """Debits carry the goodput verdict so the per-tenant SLO split
    (gateway_tenant_goodput_tokens_total{tenant,slo}) accumulates."""
    gw = _quota_gateway(tenant_quotas={"chat": 100.0})
    gw._tenant_debit("chat", 10, violated=False)
    gw._tenant_debit("chat", 5, violated=True)
    gw._tenant_debit("chat", 3, violated=None)   # goodput disabled
    snap = gw._tenant_snapshot()
    assert snap["goodput"]["chat"] == {"ok": 10, "violated": 5}
    assert snap["tokens"]["chat"] == 18


# --- tensor-parallel leg -----------------------------------------------------


@pytest.mark.skipif(envcaps.host_device_count() < 2,
                    reason=envcaps.tp_devices_reason(2))
def test_tp2_mixed_adapter_parity(world, refs):
    """Factor banks shard with the base weights' rule (serving-tp rule
    table); a mixed base+t1+t2 batch at tp=2 stays byte-identical to
    the single-chip merged references."""
    model, params, *_ = world
    base_ref, m1_ref, m2_ref = refs
    strat = S.tensor_parallel(model=2, data=1)
    mesh = strat.build_mesh(jax.devices()[:2])
    sharded = shard_params_for_serving(params, strat, mesh)
    reg = _registry(world, mesh=mesh)
    eng = _engine(model, sharded, mesh=mesh, adapter_registry=reg)
    assert eng.tp == 2
    r0 = eng.submit(P0, SP)
    r1 = eng.submit(P0, SP, adapter="t1")
    r2 = eng.submit(P1, SP, adapter="t2")
    while eng.step():
        pass
    assert r0.result() == base_ref
    assert r1.result() == m1_ref
    assert r2.result() == m2_ref


# --- the adapters.py shim + bench artifact ----------------------------------


def test_build_adapter_engines_registry_vs_legacy(world, tmp_path, caplog):
    """serve/adapters.py default: ONE shared engine behind AdapterHandle
    views. Per-adapter engine kwargs force the legacy merged-weight
    engine-per-adapter path — kept, but warned (it pays N x base HBM)."""
    import logging

    from llm_in_practise_tpu.ckpt import checkpoint as ckpt_lib
    from llm_in_practise_tpu.serve.adapters import build_adapter_engines

    model, params, (t1, c1), _ = world
    ckpt_lib.save_named(str(tmp_path), t1, "adapter",
                        metadata={"lora_config": c1.to_dict()})
    modules = {"tuned": str(tmp_path)}
    kw = dict(max_slots=2, cache_len=64, cache_dtype=jnp.float32)

    handles = build_adapter_engines(model, params, modules, **kw)
    assert isinstance(handles["tuned"], AdapterHandle)
    assert "tuned" in handles["tuned"].adapter_registry

    with caplog.at_level(logging.WARNING, logger="serve.adapters"):
        legacy = build_adapter_engines(
            model, params, modules, engine_kw_for=lambda name: {}, **kw)
    assert not isinstance(legacy["tuned"], AdapterHandle)
    assert legacy["tuned"].adapter_registry is None
    assert any("legacy engine-per-adapter" in r.message
               for r in caplog.records)


REPO = __import__("os").path.dirname(
    __import__("os").path.dirname(__import__("os").path.abspath(__file__)))


def test_bench_multi_lora_artifact_gates():
    """The checked-in BENCH_MULTI_LORA artifact meets the acceptance
    criteria: the full N in {1, 4, 16} ladder on one shared trace,
    per-adapter golden parity at EVERY rung, the mixed-adapter
    1-dispatch/step probe, flat base bytes, and a savings multiple
    over the merged-engine world that grows with the adapter count."""
    import json
    import os

    with open(os.path.join(REPO, "BENCH_MULTI_LORA_r11.json")) as f:
        artifact = json.load(f)
    assert [leg["n_adapters"] for leg in artifact["legs"]] == [1, 4, 16]
    base = {leg["weight_memory"]["base_param_bytes"]
            for leg in artifact["legs"]}
    assert len(base) == 1                    # base HBM flat across N
    for leg in artifact["legs"]:
        assert leg["parity"]["ok"] is True
        assert leg["parity"]["checked"] == leg["n_adapters"]
        assert leg["dispatch_probe"]["dispatches_per_step"] == 1
        assert leg["dispatch_probe"]["mixed_adapter_steps"] > 0
        assert (leg["weight_memory"]["per_adapter_fraction_of_base"]
                <= artifact["max_per_adapter_fraction"])
        assert leg["trace_replay"]["output_tok_per_s"] > 0
        assert leg["registry"]["tenant_tokens_total"] > 0
    savings = [leg["weight_memory"]["savings_x"]
               for leg in artifact["legs"]]
    assert savings == sorted(savings) and savings[-1] > 4.0


@pytest.mark.slow
def test_multi_lora_bench_smoke(tmp_path):
    """End-to-end smoke of the bench harness itself (tiny counts)."""
    from tools.multi_lora_bench import main

    artifact = main(quick=True, out=str(tmp_path / "ml.json"))
    assert [leg["n_adapters"] for leg in artifact["legs"]] == [1, 4]
