"""Paged KV cache (serve/paged_kv.py + the engine's paged path).

The acceptance bar of ROADMAP item 2: golden-token equality between
``kv_layout="paged"`` and the contiguous layout across every serving
composition — the fused mixed step, speculation,
the disaggregated handoff (local AND TCP), and a copy-on-write
partial-prefix hit — plus the bookkeeping invariants the block-table
world introduces: zero leaked page refcounts after admit/finish/shed
churn, preemption-by-recompute producing byte-identical streams, and
the API layer's 422 for prompts that can never fit the pool.
"""

import http.client
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
from llm_in_practise_tpu.serve.engine import InferenceEngine, SamplingParams
from llm_in_practise_tpu.serve.paged_kv import (
    PagePool,
    PagePoolExhausted,
    lane_whole,
    pages_for,
    stored_by_pages,
)
from llm_in_practise_tpu.serve.prefix_cache import PagedPrefixIndex


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
    kw.setdefault("chunked_prefill", 8)
    return InferenceEngine(model, params, **kw)


SHORT = ([3, 1, 4, 1, 5, 9], [2, 7, 1, 8, 2, 8])
LONG = [(i * 7 + 3) % 64 for i in range(40)]   # 5 chunks of 8
PROMPT = [(i * 7 + 5) % 64 for i in range(37)]  # non-page-aligned


# --- the pool's physical form -----------------------------------------------


@pytest.mark.parametrize("tails,by_pages", [
    ([(576,)], True),                   # a latent row: one vector
    ([(16,), (16,)], True),
    ([(8, 128), (8, 128)], False),      # k / v rows: a head axis
    ([(2, 16), (2, 16)], False),        # whatever the head's width
    ([(576,), (8, 128)], False),        # one pool, one form
    ([], False),
])
def test_storage_rule_reads_the_rows_shape(tails, by_pages):
    assert stored_by_pages(tails) is by_pages
    assert [lane_whole(w) for w in (1, 128, 576, 640)] == [128, 128, 640,
                                                           640]


def test_kv_pool_keeps_the_flat_form_and_its_indices(model_params):
    """A ``k`` / ``v`` pool is what it was: flat ``(rows, heads, dim)``
    buffers, the view's index is ``gather_idx``'s pool rows, nothing
    counts pages, and step records carry no ``view_pages``."""
    model, params = model_params
    e = _engine(model, params, kv_layout="paged")
    pg = e.paged
    assert pg.form == "rows"
    rows = pg.pool.num_pages * 16
    for layer in pg.kv:
        assert {k: b.shape for k, b in layer.items()} == {
            "k": (rows, 2, 16), "v": (rows, 2, 16)}
    e.generate(PROMPT, SamplingParams(greedy=True, max_tokens=4))
    pages = pg.pool.alloc(3)
    pg.map_shared(2, list(pages))
    want = np.zeros((4, 64), np.int32)          # unmapped: the trash page
    want[:] = np.arange(64) % 16
    want[2, :48] = (np.repeat(pages, 16) * 16 + np.tile(np.arange(16), 3))
    np.testing.assert_array_equal(pg.gather_idx(64), want)
    np.testing.assert_array_equal(pg.view_idx(64), want)
    np.testing.assert_array_equal(pg.view_idx(64, slots=[2, 0]),
                                  want[[2, 0]])
    assert e.view_pages_gathered == 0
    assert not any("view_pages" in r for r in e.steptrace.records(limit=50))
    snap = e.debug_kv()
    assert snap["buffers"] == {
        key: {"form": "rows", "row_bytes": 2 * 16 * 4} for key in "kv"}
    pg.release_slot(2)
    pg.pool.check_leaks(0)


# --- PagePool unit ----------------------------------------------------------


def test_page_pool_alloc_free_refcounts():
    pool = PagePool(num_pages=9, page_size=16)
    assert pool.capacity == 8 and pool.free_pages == 8
    a = pool.alloc(3)
    assert len(a) == 3 and 0 not in a          # trash page never allocated
    pool.share(a[:2])
    assert pool.shared_pages == 2
    pool.release(a)                            # drops slot refs
    assert pool.free_pages == 6                # 2 still index-held
    pool.release(a[:2])
    pool.check_leaks(0)
    assert pool.free_pages == 8


def test_page_pool_exhaustion_and_reclaim_hook():
    freed = []

    pool = PagePool(num_pages=4, page_size=16)
    assert pool.try_alloc(5) is None and pool.alloc_failures == 1
    with pytest.raises(PagePoolExhausted):
        pool.alloc(5)
    held = pool.alloc(3)

    def reclaim(n):
        take = held[:n]
        del held[:n]
        freed.extend(take)
        pool.release(take)
        return len(take)

    pool.reclaim = reclaim
    got = pool.try_alloc(2)                    # forces the reclaim hook
    assert got is not None and len(got) == 2 and len(freed) == 2


def test_pages_for():
    assert pages_for(0, 16) == 0
    assert pages_for(1, 16) == 1
    assert pages_for(16, 16) == 1
    assert pages_for(17, 16) == 2


# --- PagedPrefixIndex unit --------------------------------------------------


def test_page_index_chain_lookup_and_cap():
    pool = PagePool(num_pages=16, page_size=4)
    idx = PagedPrefixIndex(pool, min_prefix=4)
    toks = list(range(12))                     # 3 full pages
    pages = pool.alloc(3)
    assert idx.register(toks, pages) == 3
    # full prompt = the chain itself: hit capped at (len-1)//P pages so
    # the engine always recomputes the last position's logits
    hit = idx.lookup(toks)
    assert len(hit) == 2 and hit == pages[:2]
    pool.release(hit)
    # diverging third page: chain match stops after 2
    hit = idx.lookup(toks[:8] + [99, 98, 97, 96, 1, 2])
    assert len(hit) == 2
    pool.release(hit)
    # no match on first page
    assert idx.lookup([50] * 12) == []
    assert idx.misses == 1 and idx.hits == 2


def test_page_index_eviction_cascades_and_releases():
    pool = PagePool(num_pages=16, page_size=4)
    idx = PagedPrefixIndex(pool, min_prefix=4)
    toks = list(range(12))
    pages = pool.alloc(3)
    idx.register(toks, pages)
    pool.release(pages)                        # only the index holds them
    assert pool.free_pages == 15 - 3
    # evicting one reference cascades: the LRU root entry takes its
    # whole descendant chain (orphans could never match again)
    assert idx.evict_pages(1) == 3
    assert idx.n_entries == 0
    pool.check_leaks(0)


def test_page_index_budget_eviction():
    pool = PagePool(num_pages=32, page_size=4)
    idx = PagedPrefixIndex(pool, max_tokens=8, min_prefix=4)  # 2 entries
    a, b = pool.alloc(2), pool.alloc(2)
    idx.register(list(range(8)), a)
    pool.release(a)
    idx.register([9, 9, 9, 9] + list(range(4)), b)
    pool.release(b)
    assert idx.n_entries <= 2
    pool.check_leaks(idx.n_entries)


# --- golden parity ----------------------------------------------------------


def _run_mixed_load(eng):
    sp = SamplingParams(greedy=True, max_tokens=24)
    h = [eng.submit(p, sp) for p in SHORT]
    eng.step()
    hl = eng.submit(LONG, SamplingParams(greedy=True, max_tokens=8))
    while eng.step():
        pass
    return [r.result() for r in (*h, hl)]


def test_parity_mixed_step(model_params):
    """Paged vs contiguous under the fused mixed step: identical greedy
    tokens, the fused path really ran, and the drained pool leaks no
    page references."""
    model, params = model_params
    paged = _engine(model, params, kv_layout="paged")
    contig = _engine(model, params)
    assert _run_mixed_load(paged) == _run_mixed_load(contig)
    assert paged.mixed_blocks > 0
    paged.paged.pool.check_leaks(0)


def test_parity_sequential_mixed_off(model_params):
    model, params = model_params
    paged = _engine(model, params, kv_layout="paged", mixed_step=False)
    contig = _engine(model, params, mixed_step=False)
    assert _run_mixed_load(paged) == _run_mixed_load(contig)
    assert paged.mixed_blocks == 0


def test_parity_speculative(model_params):
    """Speculation composes in BOTH layouts and the verify path's
    accepted bursts emit identical tokens."""
    model, params = model_params
    prompt = [1, 2, 3, 1, 2, 3, 1, 2]
    sp = SamplingParams(greedy=True, max_tokens=20)
    outs = []
    for kw in ({"kv_layout": "paged"}, {}):
        e = _engine(model, params, speculative_k=3, **kw)
        outs.append(e.generate(prompt, sp))
        assert e.spec_accepted > 0      # the spec path really ran
    assert outs[0] == outs[1]


def test_parity_one_shot_no_chunking(model_params):
    """The batched one-shot admission path (no chunked prefill) page-
    scatters bucket rows; tokens match the contiguous insert."""
    model, params = model_params
    sp = SamplingParams(greedy=True, max_tokens=12)
    paged = _engine(model, params, kv_layout="paged",
                    chunked_prefill=None)
    contig = _engine(model, params, chunked_prefill=None)
    for eng in (paged, contig):
        hs = [eng.submit(p, sp) for p in (*SHORT, PROMPT)]
        while eng.step():
            pass
        eng._outs = [h.result() for h in hs]
    assert paged._outs == contig._outs
    paged.paged.pool.check_leaks(0)


# --- copy-on-write prefix sharing -------------------------------------------


def test_cow_partial_prefix_hit(model_params):
    """A second prompt sharing 2 of the first prompt's pages reuses
    those PHYSICAL pages (no copies, refcount > 1 while both live) and
    still emits exactly the cold-engine tokens."""
    model, params = model_params
    sp = SamplingParams(greedy=True, max_tokens=12)
    e = _engine(model, params, kv_layout="paged", prefix_cache=True)
    base = [(i * 5 + 1) % 64 for i in range(40)]
    out1 = e.generate(base, sp)
    shared = base[:36] + [60, 61]
    out2 = e.generate(shared, sp)
    assert e.prefix_cache.hits == 1
    assert e.prefix_cache.tokens_saved == 32   # 2 pages of 16
    cold = _engine(model, params)
    assert cold.generate(base, sp) == out1
    assert cold.generate(shared, sp) == out2
    # index still holds the shared pages; clearing returns everything
    e.prefix_cache.clear()
    e.paged.pool.check_leaks(0)


def test_cow_shared_pages_refcounted_while_running(model_params):
    """Mid-flight: admit a sharer while the index pins the prefix pages
    — the matched pages carry refcount >= 2 (slot + index), and
    shared_pages shows up in /debug/kv."""
    model, params = model_params
    e = _engine(model, params, kv_layout="paged", prefix_cache=True,
                chunked_prefill=None)
    base = [(i * 5 + 1) % 64 for i in range(40)]
    e.generate(base, SamplingParams(greedy=True, max_tokens=4))
    h = e.submit(base[:36] + [60, 61],
                 SamplingParams(greedy=True, max_tokens=30))
    e.step()                                   # admit: pages shared now
    assert e.paged.pool.shared_pages >= 2
    snap = e.debug_kv()
    assert snap["pages_shared"] >= 2
    while e.step():
        pass
    h.result()
    e.prefix_cache.clear()
    e.paged.pool.check_leaks(0)


def test_cow_fork_on_shared_write(model_params):
    """The defensive fork: force a write window onto a shared page and
    check the writer gets a private copy (refcounts drop back, the
    sharer's page is untouched)."""
    model, params = model_params
    e = _engine(model, params, kv_layout="paged")
    pool = e.paged.pool
    pages = pool.alloc(2)
    e.paged.map_shared(0, list(pages))         # slot 0 maps them
    pool.share(pages)                          # a phantom second reader
    before = [np.asarray(layer["k"][pages[1] * 16: pages[1] * 16 + 16])
              for layer in e.paged.kv]
    e._paged_cow_fork(0, 20, 4)                # window inside page 1
    forked = int(e.paged.block_tables[0, 1])
    assert forked != pages[1]
    assert pool.refcount(pages[1]) == 1        # phantom reader only
    assert pool.refcount(forked) == 1
    for layer, snap in zip(e.paged.kv, before):
        np.testing.assert_array_equal(
            np.asarray(layer["k"][forked * 16: forked * 16 + 16]), snap)
    e.paged.release_slot(0)
    pool.release(pages)
    pool.check_leaks(0)


# --- disaggregated handoff --------------------------------------------------


def _handoff_roundtrip(model, params, store, claim):
    from llm_in_practise_tpu.serve.disagg import new_handoff_id

    sp = SamplingParams(greedy=True, max_tokens=16)
    pre = _engine(model, params, kv_layout="paged", role="prefill",
                  handoff=store)
    hid = new_handoff_id()
    h = pre.submit(PROMPT, SamplingParams(max_tokens=1), handoff_id=hid)
    while pre.step():
        pass
    h.result()
    assert h.finish_reason == "handoff"
    pre.paged.pool.check_leaks(0)              # handoff freed the slot
    host = claim(hid)
    assert host is not None
    # page-wise wire entry: ceil(37/16)*16 rows, NOT the pow2 bucket 64
    assert host.page_size == 16 and host.bucket == 48
    dec = _engine(model, params, kv_layout="paged", role="decode")
    r = dec.submit(PROMPT, sp, kv_entry=host)
    while dec.step():
        pass
    out = r.result()
    assert dec.kv_admitted == 1 and dec.local_prefills == 0
    return out


def test_handoff_local_parity(model_params):
    from llm_in_practise_tpu.serve.disagg import LocalHandoff

    model, params = model_params
    store = LocalHandoff()
    out = _handoff_roundtrip(model, params, store, store.claim)
    both = _engine(model, params)
    assert out == both.generate(PROMPT,
                                SamplingParams(greedy=True, max_tokens=16))


def test_handoff_tcp_parity(model_params):
    """Full TCP roundtrip through KVPoolServer hput/hclaim: the wire
    manifest preserves page_size, the server accounts pinned pages, and
    the claimed tokens equal role=both."""
    from llm_in_practise_tpu.serve.disagg import RemoteHandoff
    from llm_in_practise_tpu.serve.kv_pool import KVPoolServer

    model, params = model_params
    server = KVPoolServer(min_prefix=4).start()
    try:
        store = RemoteHandoff(server.address, namespace="m")
        seen_pages = []

        def claim(hid):
            seen_pages.append(server.handoff_pages)
            return store.claim(hid)

        out = _handoff_roundtrip(model, params, store, claim)
        assert seen_pages == [3]               # ceil(37/16) pinned pages
        assert server.handoff_pages == 0       # claim released them
        both = _engine(model, params)
        assert out == both.generate(
            PROMPT, SamplingParams(greedy=True, max_tokens=16))
    finally:
        server.stop()


def test_paged_entry_into_contiguous_engine(model_params):
    """Cross-layout: a page-aligned handoff entry seeds a CONTIGUOUS
    decode replica (one release of mixed fleets)."""
    from llm_in_practise_tpu.serve.disagg import LocalHandoff, new_handoff_id

    model, params = model_params
    store = LocalHandoff()
    pre = _engine(model, params, kv_layout="paged", role="prefill",
                  handoff=store)
    hid = new_handoff_id()
    h = pre.submit(PROMPT, SamplingParams(max_tokens=1), handoff_id=hid)
    while pre.step():
        pass
    h.result()
    host = store.claim(hid)
    # wire width stays page-aligned; the contiguous consumer pads the
    # device upload to the next pow2 so its shape-traced insert keeps a
    # bounded compile set (review finding)
    from llm_in_practise_tpu.serve.kv_pool import (
        effective_bucket,
        entry_to_device,
    )

    assert host.bucket == 48 and effective_bucket(host) == 64
    dev = entry_to_device(host)
    assert dev.bucket == 64 and dev.rows[0]["k"].shape[1] == 64
    dec = _engine(model, params, role="decode")
    sp = SamplingParams(greedy=True, max_tokens=16)
    r = dec.submit(PROMPT, sp, kv_entry=host)
    while dec.step():
        pass
    assert dec.kv_admitted == 1
    assert r.result() == _engine(model, params).generate(PROMPT, sp)


# --- tiering ----------------------------------------------------------------


def test_tier_hit_scatters_into_pages(model_params):
    """kv-pool write-through from a paged engine, then a FRESH paged
    engine hits the host tier: the row entry page-scatters and the
    suffix continues exactly."""
    from llm_in_practise_tpu.serve.kv_pool import HostKVPool, TieredKV

    model, params = model_params
    sp = SamplingParams(greedy=True, max_tokens=12)
    tier = TieredKV(HostKVPool(), None, offload_on_put=True)
    warm = _engine(model, params, kv_layout="paged", prefix_cache=True,
                   kv_pool=tier)
    warm.generate(PROMPT, sp)
    entry = tier.host_pool.lookup(PROMPT)
    assert entry is not None and entry.page_size == 16
    assert entry.bucket == 48                  # page-aligned, not pow2
    fresh = _engine(model, params, kv_layout="paged", prefix_cache=True,
                    kv_pool=tier)
    out = fresh.generate(PROMPT + [7, 8], sp)
    assert out == _engine(model, params).generate(PROMPT + [7, 8], sp)


# --- admission, preemption, churn -------------------------------------------


def test_preemption_resume_exact_streams(model_params):
    """Pool sized for ~2 of 3 requests: preemption must fire, every
    stream still completes with EXACTLY the unconstrained tokens (the
    recompute-resume path neither drops nor re-samples)."""
    model, params = model_params
    sp = SamplingParams(greedy=True, max_tokens=40)
    prompts = [[(j * 3 + i) % 64 for i in range(20)] for j in range(3)]
    t = _engine(model, params, kv_layout="paged", kv_pool_tokens=96,
                prefix_cache=True)
    rs = [t.submit(p, sp) for p in prompts]
    while t.step():
        pass
    outs = [r.result() for r in rs]
    assert t.preemptions > 0
    free = _engine(model, params, kv_layout="paged")
    for p, out, r in zip(prompts, outs, rs):
        assert r.finish_reason in ("length", "stop")
        assert out == free.generate(p, sp)
    t.prefix_cache.clear()
    t.paged.pool.check_leaks(0)


def test_churn_zero_leaked_refcounts(model_params):
    """N admit/finish/shed/preempt cycles, then drain: every page is
    back on the free list once the index is cleared — the refcount
    invariant the block-table world lives or dies by."""
    model, params = model_params
    e = _engine(model, params, kv_layout="paged", kv_pool_tokens=128,
                prefix_cache=True, max_queue=4)
    rng = np.random.RandomState(0)
    handles = []
    for cycle in range(6):
        for j in range(6):
            p = [int(x) for x in rng.randint(0, 64, size=10 + 4 * j)]
            handles.append(e.submit(
                p, SamplingParams(greedy=True,
                                  max_tokens=int(rng.randint(1, 24)))))
        while e.step():
            pass
    for h in handles:
        h.result()                             # incl. queue_full sheds
    assert e.stats.requests_shed > 0           # max_queue really bit
    held = e.prefix_cache.n_entries
    e.paged.pool.check_leaks(held)             # only index refs remain
    e.prefix_cache.clear()
    e.paged.pool.check_leaks(0)


def test_tier_hit_near_cache_len_rejected_not_crashed(model_params):
    """Review regression: a partial tier entry whose suffix bucket
    overshoots cache_len (no chunking) must be FILTERED by the paged
    usable() — not crash the engine loop in _paged_width."""
    from llm_in_practise_tpu.serve.kv_pool import HostKVPool, TieredKV

    model, params = model_params
    sp = SamplingParams(greedy=True, max_tokens=4)
    tier = TieredKV(HostKVPool(min_prefix=16), None, offload_on_put=True)
    warm = _engine(model, params, kv_layout="paged", prefix_cache=True,
                   kv_pool=tier, cache_len=128, chunked_prefill=None)
    seed = [(i * 3 + 2) % 64 for i in range(120)]
    warm.generate(seed[:120], sp)
    assert tier.host_pool.n_entries == 1
    cold = _engine(model, params, kv_layout="paged", prefix_cache=True,
                   kv_pool=tier, cache_len=128, chunked_prefill=None)
    cold.prefix_cache.clear()                  # force the tier path
    prompt = seed[:120] + [60, 61, 62, 63, 60, 61]   # 126: rem=6 won't fit
    out = cold.generate(prompt, sp)
    ref = _engine(model, params, cache_len=128,
                  chunked_prefill=None).generate(prompt, sp)
    assert out == ref


def test_bare_host_pool_as_kv_pool(model_params):
    """Review regression: kv_pool=HostKVPool() (no TieredKV facade) is
    a supported configuration — the paged lookup must not pass it the
    TieredKV-only device kwarg."""
    from llm_in_practise_tpu.serve.kv_pool import HostKVPool

    model, params = model_params
    e = _engine(model, params, kv_layout="paged",
                kv_pool=HostKVPool(min_prefix=16))
    sp = SamplingParams(greedy=True, max_tokens=6)
    out = e.generate(PROMPT, sp)
    assert out == _engine(model, params).generate(PROMPT, sp)
    assert e.is_alive()


def test_page_index_deep_chain_eviction_iterative():
    """Review regression: evicting the root of a ~1200-entry chain (one
    long-context conversation) must not hit the recursion limit."""
    pool = PagePool(num_pages=1302, page_size=4)
    idx = PagedPrefixIndex(pool, max_tokens=1 << 30, min_prefix=4)
    n = 1200
    toks = [int(x) for x in np.arange(4 * n) % 64]
    pages = pool.alloc(n)
    assert idx.register(toks, pages) == n
    pool.release(pages)
    assert idx.evict_pages(1) == n             # whole chain cascades
    pool.check_leaks(0)


def test_blocked_admission_restashes_handoff_entry(model_params):
    """Review regression: a dry-pool requeue of a request carrying a
    claimed (consume-once) handoff entry must stash the entry back —
    the retry direct-inserts instead of paying a local prefill."""
    from llm_in_practise_tpu.serve.disagg import LocalHandoff, new_handoff_id

    model, params = model_params
    store = LocalHandoff()
    pre = _engine(model, params, kv_layout="paged", role="prefill",
                  handoff=store)
    hid = new_handoff_id()
    h = pre.submit(PROMPT, SamplingParams(max_tokens=1), handoff_id=hid)
    while pre.step():
        pass
    h.result()
    host = store.claim(hid)
    dec = _engine(model, params, kv_layout="paged", role="decode",
                  kv_pool_tokens=96, max_slots=2)   # 6 pages only
    blocker = dec.submit([(i * 3) % 64 for i in range(60)],
                         SamplingParams(greedy=True, max_tokens=30))
    dec.step()                                  # blocker takes 4+ pages
    r = dec.submit(PROMPT, SamplingParams(greedy=True, max_tokens=8),
                   kv_entry=host)               # needs 3 pages: blocked
    while dec.step():
        pass
    blocker.result()
    out = r.result()
    assert dec.preemptions == 0                 # admission never preempts
    assert dec.kv_admitted == 1                 # consumed exactly once
    # exactly ONE local prefill: the blocker (a plain submit on a
    # decode replica) — the handed-off request added none, i.e. its
    # entry survived the dry-pool requeue
    assert dec.local_prefills == 1
    ref = _engine(model, params).generate(
        PROMPT, SamplingParams(greedy=True, max_tokens=8))
    assert out == ref


def test_too_large_and_debug_kv_http(model_params):
    """API layer: a prompt that can never fit 422s at submit with the
    page math; GET /debug/kv serves the pool snapshot."""
    from llm_in_practise_tpu.serve.api import OpenAIServer

    class Tok:
        def encode(self, text):
            return list(text.encode()[:160])

        def decode(self, ids):
            return bytes(int(i) % 256 for i in ids).decode(
                "utf-8", "replace")

    model, params = model_params
    e = _engine(model, params, kv_layout="paged", kv_pool_tokens=64)
    srv = OpenAIServer(e, Tok(), model_name="paged-test")
    e.start()
    port = srv.serve(host="127.0.0.1", port=0, background=True)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("POST", "/v1/chat/completions", json.dumps({
            "model": "paged-test",
            "messages": [{"role": "user", "content": "x" * 150}],
            "max_tokens": 4,
        }), {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 422, body
        assert body["error"]["code"] == "prompt_too_large"
        assert body["error"]["detail"]["pages_capacity"] == 4
        assert (body["error"]["detail"]["pages_needed"]
                > body["error"]["detail"]["pages_capacity"])
        conn.close()
        # a small prompt still serves
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("POST", "/v1/chat/completions", json.dumps({
            "model": "paged-test",
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 4, "temperature": 0.0,
        }), {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        resp.read()
        conn.close()
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/debug/kv")
        resp = conn.getresponse()
        snap = json.loads(resp.read())
        assert resp.status == 200
        assert snap["layout"] == "paged" and snap["pages_total"] == 4
        assert "refcount_histogram" in snap and "fragmentation" in snap
        assert "block_table_pages_per_slot" in snap
        conn.close()
        # the paged metric families render
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
        for fam in ("llm_kv_pages", "llm_kv_pages_total",
                    "llm_kv_preemptions_total",
                    "llm_kv_rejected_too_large_total"):
            assert fam in text, fam
        assert 'llm_kv_rejected_too_large_total 1' in text
        conn.close()
    finally:
        srv.shutdown()


def test_contiguous_debug_kv(model_params):
    model, params = model_params
    e = _engine(model, params)
    snap = e.debug_kv()
    assert snap["layout"] == "contiguous"
    assert snap["kv_tokens_reserved"] == 4 * 192


def test_more_slots_than_contiguous_capacity(model_params):
    """The concurrency unlock: 8 slots over a pool that contiguous
    layout maths out at ~2.6 slots (same bytes) — short requests all
    run CONCURRENTLY and complete."""
    model, params = model_params
    e = _engine(model, params, kv_layout="paged", max_slots=8,
                kv_pool_tokens=512, chunked_prefill=None)
    sp = SamplingParams(greedy=True, max_tokens=8)
    hs = [e.submit([j + 1, j + 2, j + 3, j + 4], sp) for j in range(8)]
    e.step()                                   # one admission pass
    assert sum(r is not None for r in e.slot_req) == 8
    while e.step():
        pass
    assert all(len(h.result()) == 8 for h in hs)
    e.paged.pool.check_leaks(0)
