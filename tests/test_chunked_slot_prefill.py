"""Direct-to-slot chunked prefill: correctness under interleaved decode.

Round 3 rewrote chunked prefill to write each chunk's KV straight into
the reserved engine slot instead of a per-prefill full-length mini cache
(at 8B with an 8K context that mini was 1.2 GiB per in-flight prefill —
the long-context OOM). The subtlety: while a slot is mid-prefill, other
dispatches (single-step decode, speculation) write garbage rows into it
at its drifting device index. Correctness rests on the
overwrite-before-attend invariant — every garbage row is overwritten by
the chunk that owns its range (or by real decode, in order) before any
query can attend it. These tests pin that invariant from the outside:
chunked output under heavy interleaving must equal unchunked output,
including the prefix-store/reuse path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_in_practise_tpu.models.qwen3 import Qwen3, qwen3_config
from llm_in_practise_tpu.serve.engine import InferenceEngine, SamplingParams


@pytest.fixture(scope="module")
def models():
    cfg = qwen3_config(vocab_size=128, compute_dtype="float32")
    pu = Qwen3(cfg).init(jax.random.PRNGKey(0),
                         jnp.ones((1, 8), jnp.int32))["params"]
    return Qwen3(cfg), pu


def _rng_prompt(n, seed=7):
    return list(map(int, np.random.default_rng(seed).integers(0, 128, n)))


def test_chunked_equals_oneshot_under_decode_load(models):
    model, params = models
    long_prompt = _rng_prompt(70)
    sp = SamplingParams(greedy=True, max_tokens=10)

    ref_eng = InferenceEngine(model, params, max_slots=2, cache_len=160)
    ref_eng.start()
    ref = ref_eng.submit(long_prompt, sp).result()
    ref_eng.stop()

    # chunked, with an active decode stream interleaving garbage writes
    eng = InferenceEngine(model, params, max_slots=2, cache_len=160,
                          chunked_prefill=16)
    eng.start()
    load = eng.submit(_rng_prompt(5, seed=1),
                      SamplingParams(greedy=True, max_tokens=60))
    out = eng.submit(long_prompt, sp).result()
    load.result()
    eng.stop()
    assert out == ref


def test_chunked_prefix_store_and_reuse(models):
    """The chunked path stores its prefix from the slot rows; a repeat
    prompt must hit it and produce identical output."""
    mu, pu = models
    long_prompt = _rng_prompt(60)
    sp = SamplingParams(greedy=True, max_tokens=8)
    eng = InferenceEngine(mu, pu, max_slots=2, cache_len=160,
                          chunked_prefill=16, prefix_cache=True)
    eng.start()
    first = eng.submit(long_prompt, sp).result()
    h0 = eng.prefix_cache.hits
    again = eng.submit(long_prompt + [3, 4],
                       SamplingParams(greedy=True, max_tokens=8)).result()
    assert eng.prefix_cache.hits > h0
    # the reused prefix must reproduce the unchunked reference
    ref_eng = InferenceEngine(mu, pu, max_slots=2, cache_len=160)
    ref_eng.start()
    ref = ref_eng.submit(long_prompt + [3, 4],
                         SamplingParams(greedy=True, max_tokens=8)).result()
    ref_eng.stop()
    eng.stop()
    assert again == ref and len(first) == 8


def test_chunked_with_speculative_interleave(models):
    """Speculation writes k+1 rows into every slot per verify dispatch —
    the reserved slot's garbage must still be overwritten before use."""
    mu, pu = models
    long_prompt = _rng_prompt(70)
    sp = SamplingParams(greedy=True, max_tokens=10)
    ref_eng = InferenceEngine(mu, pu, max_slots=2, cache_len=160)
    ref_eng.start()
    ref = ref_eng.submit(long_prompt, sp).result()
    ref_eng.stop()
    eng = InferenceEngine(mu, pu, max_slots=2, cache_len=160,
                          chunked_prefill=16, speculative_k=3)
    eng.start()
    load = eng.submit([7, 8, 9, 7, 8, 9, 7, 8],
                      SamplingParams(greedy=True, max_tokens=40))
    out = eng.submit(long_prompt, sp).result()
    load.result()
    eng.stop()
    assert out == ref


def test_many_concurrent_chunked_prefills(models):
    """Several prompts mid-prefill at once: the shared-transient design
    must keep each one's rows isolated in its own slot."""
    mu, pu = models
    prompts = [_rng_prompt(50 + 8 * i, seed=i) for i in range(4)]
    sp = SamplingParams(greedy=True, max_tokens=6)
    refs = []
    for p in prompts:
        e = InferenceEngine(mu, pu, max_slots=1, cache_len=160)
        e.start()
        refs.append(e.submit(p, sp).result())
        e.stop()
    eng = InferenceEngine(mu, pu, max_slots=4, cache_len=160,
                          chunked_prefill=16, prefill_budget=2)
    eng.start()
    outs = [eng.submit(p, sp) for p in prompts]
    outs = [r.result() for r in outs]
    eng.stop()
    assert outs == refs


def test_batched_multi_slot_chunks_match_isolated(models):
    """Round 5: concurrent chunked prefills advance in ONE batched
    dispatch (engine._chunk_batch_fn). Exactness bar: three long
    prompts prefilling simultaneously (including a pow2 padding row,
    since 3 pads to 4) must generate exactly what each does alone."""
    model, params = models
    prompts = [_rng_prompt(60 + 7 * i, seed=20 + i) for i in range(3)]
    sp = SamplingParams(greedy=True, max_tokens=8)

    refs = []
    for p in prompts:
        eng = InferenceEngine(model, params, max_slots=1, cache_len=160,
                              chunked_prefill=16)
        eng.start()
        refs.append(eng.submit(p, sp).result())
        eng.stop()

    eng = InferenceEngine(model, params, max_slots=4, cache_len=160,
                          chunked_prefill=16)
    # no background thread: submit all three, then step — guarantees the
    # three prefills are in flight together so the batched path runs
    handles = [eng.submit(p, sp) for p in prompts]
    eng.step()                       # admission reserves all three slots
    assert len(eng.slot_prefill) == 3
    while eng.step():
        pass
    outs = [h.result() for h in handles]
    assert outs == refs


# --- paged layout: the chunk program computes only the rows that chunk ------
#
# (PR 28) Nothing decodes when the rows start: the chunk-only program
# (``_paged_chunk_fn``'s loop) carries them, one of them from a prefix
# hit. Reference: each prompt ALONE on a one-slot contiguous engine,
# whose chunks go through the one-row ``_chunk_slot_fn``.

_ALONE: dict = {}
SHARED = _rng_prompt(32, seed=99)          # two full pages of 16


def _prompt(i):
    body = _rng_prompt(100 + 8 * i, seed=40 + i)
    return SHARED + body[32:] if i == 1 else body


def _alone(model, params, i):
    """(tokens, KV rows of slot 0) of prompt ``i`` by itself."""
    if i not in _ALONE:
        eng = InferenceEngine(model, params, max_slots=1, cache_len=160,
                              chunked_prefill=16,
                              cache_dtype=jnp.float32)
        out = eng.generate(_prompt(i), SamplingParams(greedy=True,
                                                      max_tokens=6))
        rows = [{k: np.asarray(v)[0] for k, v in layer.items()
                 if k != "index"} for layer in eng.cache]
        eng.stop()
        _ALONE[i] = (out, rows)
    return _ALONE[i]


@pytest.mark.parametrize("k", [1, 2, 3, 5])
def test_paged_chunk_rows_match_one_row_path(models, k):
    """k rows among 8 slots chunk together with no row decoding, each
    at another ``done`` (arrivals a step apart, the second from a
    prefix hit): tokens equal each prompt's own one-row run exactly,
    the rows' KV to float32's last bits, and the device computed one
    row a chunk for them, not 8."""
    mu, pu = models
    eng = InferenceEngine(mu, pu, max_slots=8, cache_len=160,
                          chunked_prefill=16, kv_layout="paged",
                          prefix_cache=True, cache_dtype=jnp.float32)
    eng.generate(SHARED + [5, 6, 7], SamplingParams(greedy=True,
                                                    max_tokens=2))
    sp = SamplingParams(greedy=True, max_tokens=6)
    handles = []
    for i in range(k):
        handles.append(eng.submit(_prompt(i), sp))
        eng.step()
        assert eng.mixed_blocks == 0           # nothing decodes yet
    assert len(eng.slot_prefill) == k
    assert len({st["done"] for st in eng.slot_prefill.values()}) == k
    if k > 1:
        assert handles[1].cache_outcome == "partial"
    assert eng.prefill_chunk_row_slots == eng.prefill_chunk_rows
    while eng.slot_prefill:
        eng.step()
    snap = {}
    for s, req in enumerate(eng.slot_req):
        if req is not None:
            n = int(eng.slot_len[s])
            flat = eng.paged.row_gather_idx(s, n)[0]
            snap[req.uid] = (n, [{key: np.asarray(buf)[flat]
                                  for key, buf in layer.items()}
                                 for layer in eng.paged.kv])
    while eng.step():
        pass
    eng.stop()          # returns the pool's bytes to the process's HBM ledger
    compared = 0
    for i, h in enumerate(handles):
        out, rows = _alone(mu, pu, i)
        assert h.result() == out
        if h.uid in snap:
            n, got = snap[h.uid]
            for a, b in zip(got, rows):
                for key in a:
                    # float32 through another program shape (one row
                    # of a W-wide view against a slot slice): values of
                    # order 1 over <= 256-term sums, a few ulps
                    np.testing.assert_allclose(a[key], b[key][:n],
                                               rtol=1e-5, atol=1e-5)
            compared += 1
    assert compared >= 1
