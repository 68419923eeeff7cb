"""Continuous-batching engine correctness: interleaved slots must reproduce
single-request greedy decoding exactly (per-slot cache positions + masks)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_in_practise_tpu.infer.generate import generate
from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
from llm_in_practise_tpu.serve.engine import InferenceEngine, SamplingParams


def _tiny_model(rng):
    cfg = GPTConfig(
        vocab_size=64, seq_len=128, n_layer=2, n_head=2, embed_dim=32,
        dropout=0.0, pos_embedding="rope",
    )
    model = GPT(cfg)
    params = model.init(rng, jnp.ones((1, 8), jnp.int32))["params"]
    return model, params


def _ref_greedy(model, params, prompt, n):
    out = generate(
        model, params, jnp.asarray([prompt], jnp.int32),
        max_new_tokens=n, greedy=True, cache_len=128, cache_dtype=jnp.float32,
    )
    return list(np.asarray(out[0, len(prompt):]))


def test_fp8_kv_cache_serves(rng):
    """fp8 (e4m3) KV storage — half of bf16's KV HBM — must decode
    cleanly: right lengths, in-vocab tokens, quantization noise only."""
    model, params = _tiny_model(rng)
    engine = InferenceEngine(
        model, params, max_slots=2, cache_len=128,
        cache_dtype=jnp.float8_e4m3fn,
    )
    assert engine.cache[0]["k"].dtype == jnp.float8_e4m3fn
    out = engine.generate(list(range(1, 17)),
                          SamplingParams(greedy=True, max_tokens=12))
    assert len(out) == 12
    assert all(0 <= t < 64 for t in out)
    # storage really is 1 byte/element (vs 4 for the f32 reference cache)
    ref = InferenceEngine(model, params, max_slots=2, cache_len=128,
                          cache_dtype=jnp.float32)
    assert engine.cache[0]["k"].nbytes * 4 == ref.cache[0]["k"].nbytes


# every decode path the engine has: a row's token a step, or a verified
# burst of drafts, against either cache
PATHS = pytest.mark.parametrize("layout, spec_k", [
    ("contiguous", None), ("contiguous", 3), ("paged", None), ("paged", 3)])


@pytest.mark.parametrize("layout, cache_len, reason", [
    ("contiguous", 128, "length"),
    # 4 prompt rows + 8 tokens' rows fill a cache of 12: the row ends there
    ("contiguous", 12, "cache"), ("paged", 12, "cache")])
def test_single_request_matches_generate(rng, layout, cache_len, reason):
    model, params = _tiny_model(rng)
    engine = InferenceEngine(
        model, params, max_slots=4, cache_len=cache_len,
        cache_dtype=jnp.float32, kv_layout=layout, kv_page_size=4,
    )
    prompt = [1, 5, 9, 13]
    req = engine.submit(prompt, SamplingParams(greedy=True, max_tokens=10))
    while engine.step():
        pass
    ref = _ref_greedy(model, params, prompt, 10)
    assert req.finish_reason == reason
    assert req.result() == ref[:cache_len - len(prompt)], (req.result(), ref)


@PATHS
def test_interleaved_requests_match_isolated(rng, layout, spec_k):
    model, params = _tiny_model(rng)
    engine = InferenceEngine(
        model, params, max_slots=4, cache_len=128, cache_dtype=jnp.float32,
        kv_layout=layout, speculative_k=spec_k,
    )
    prompts = [[1, 2, 3], [7, 8, 9, 10, 11], [20], [30, 31]]
    reqs = [
        engine.submit(p, SamplingParams(greedy=True, max_tokens=8))
        for p in prompts
    ]
    while engine.step():
        pass
    for p, r in zip(prompts, reqs):
        got = r.result()
        ref = _ref_greedy(model, params, p, 8)
        assert got == ref, (p, got, ref)
        assert r.finish_reason == "length"
        assert r.ttft_s is not None


@PATHS
def test_slot_reuse_after_finish(rng, layout, spec_k):
    """More requests than slots: later requests recycle freed slots
    cleanly, and a slot free while a request waits is taken by the very
    next step."""
    model, params = _tiny_model(rng)
    engine = InferenceEngine(
        model, params, max_slots=2, cache_len=128, cache_dtype=jnp.float32,
        kv_layout=layout, speculative_k=spec_k,
    )
    prompts = [[i, i + 1, i + 2] for i in range(1, 11, 2)]  # 5 requests, 2 slots
    reqs = [
        engine.submit(p, SamplingParams(greedy=True, max_tokens=6))
        for p in prompts
    ]
    busy = True
    while busy:
        free = [s for s in range(2) if engine.slot_req[s] is None]
        owed = min(len(free), engine.pending.qsize())
        busy = engine.step()
        assert sum(engine.slot_req[s] is not None for s in free) >= owed
    for p, r in zip(prompts, reqs):
        assert r.result() == _ref_greedy(model, params, p, 6), p


def test_background_thread_streaming(rng):
    model, params = _tiny_model(rng)
    engine = InferenceEngine(
        model, params, max_slots=2, cache_len=128, cache_dtype=jnp.float32
    )
    engine.start()
    try:
        req = engine.submit([3, 4, 5], SamplingParams(greedy=True, max_tokens=5))
        streamed = list(req)  # iterator blocks until FINISH
        assert streamed == _ref_greedy(model, params, [3, 4, 5], 5)
    finally:
        engine.stop()


def test_qwen3_serves_on_engine(rng):
    """The HF-family model must run on the engine (shared cache API)."""
    from llm_in_practise_tpu.models.qwen3 import Qwen3, qwen3_config

    cfg = qwen3_config(vocab_size=64, max_seq_len=64)
    model = Qwen3(cfg)
    params = model.init(rng, jnp.ones((1, 8), jnp.int32))["params"]
    engine = InferenceEngine(
        model, params, max_slots=2, cache_len=128, cache_dtype=jnp.float32
    )
    assert engine.cache_len == 64  # capped at the RoPE table length
    got = engine.generate([1, 2, 3], SamplingParams(greedy=True, max_tokens=6))
    ref = list(np.asarray(generate(
        model, params, jnp.asarray([[1, 2, 3]], jnp.int32),
        max_new_tokens=6, greedy=True, cache_dtype=jnp.float32,
    )[0, 3:]))
    assert got == ref


@PATHS
def test_eos_stops_generation(rng, layout, spec_k):
    """EOS ends a row mid-stream, and the request waiting for its slot
    holds it two steps after the stream closed at the latest (a paged
    row's last program is read a step after it was issued)."""
    model, params = _tiny_model(rng)
    ref = _ref_greedy(model, params, [1, 2, 3], 10)
    eos = ref[3]  # force eos at the 4th generated token
    engine = InferenceEngine(
        model, params, max_slots=1, cache_len=128, cache_dtype=jnp.float32,
        eos_id=eos, kv_layout=layout, speculative_k=spec_k,
    )
    req = engine.submit([1, 2, 3], SamplingParams(greedy=True, max_tokens=10))
    nxt = engine.submit([7, 8, 9], SamplingParams(greedy=True, max_tokens=2))
    while req.finish_time is None:
        engine.step()
    assert req.result() == ref[:3]
    assert req.finish_reason == "stop"
    engine.step()
    engine.step()
    assert engine.slot_req[0] is nxt or nxt.finish_time is not None
    while engine.step():
        pass
    want = _ref_greedy(model, params, [7, 8, 9], 2)
    assert nxt.result() == (want[:want.index(eos)] if eos in want else want)


def test_prefix_cache_exactness_and_hits(rng):
    """APC parity: cached-prefix decode must equal cold decode exactly
    (full hit, partial hit), with hit accounting."""
    from llm_in_practise_tpu.serve.prefix_cache import PrefixCache

    model, params = _tiny_model(rng)
    pc = PrefixCache(min_prefix=8)
    engine = InferenceEngine(
        model, params, max_slots=2, cache_len=128, cache_dtype=jnp.float32,
        prefix_cache=pc,
    )
    prompt = list(range(2, 26))  # 24 tokens >= min_prefix
    sp = SamplingParams(greedy=True, max_tokens=8)

    cold = engine.generate(prompt, sp)
    assert pc.misses == 1 and pc.hits == 0

    # identical prompt -> full hit, same tokens, no new prefill
    warm = engine.generate(prompt, sp)
    assert warm == cold
    assert pc.full_hits == 1

    # extended prompt -> partial hit (suffix prefill), equals cold reference
    longer = prompt + [30, 31, 32, 33, 34]
    warm_ext = engine.generate(longer, sp)
    assert pc.hits == 2
    ref = _ref_greedy(model, params, longer, 8)
    assert warm_ext == ref, (warm_ext, ref)

    # a fresh engine without the cache agrees on the original prompt
    engine2 = InferenceEngine(
        model, params, max_slots=2, cache_len=128, cache_dtype=jnp.float32,
    )
    assert engine2.generate(prompt, sp) == cold


def test_prefix_cache_lru_eviction():
    from llm_in_practise_tpu.serve.prefix_cache import PrefixCache, PrefixEntry

    pc = PrefixCache(max_tokens=40, min_prefix=4)
    for start in (0, 100, 200):
        ids = list(range(start, start + 16))
        pc.put(ids, PrefixEntry(length=16, bucket=16, rows=[],
                                last_logits=None))
    assert pc.cached_tokens <= 40  # oldest evicted
    assert pc.lookup(list(range(0, 16))) is None        # evicted
    assert pc.lookup(list(range(200, 216))) is not None  # newest kept


def test_prefix_cache_overflow_falls_back_to_cold(rng):
    """A cached prefix whose suffix bucket would overflow cache_len must be
    rejected (clamped scatter would corrupt the prefix KV)."""
    from llm_in_practise_tpu.serve.prefix_cache import PrefixCache

    model, params = _tiny_model(rng)
    pc = PrefixCache(min_prefix=8)
    engine = InferenceEngine(
        model, params, max_slots=1, cache_len=128, cache_dtype=jnp.float32,
        prefix_cache=pc,
    )
    sp = SamplingParams(greedy=True, max_tokens=4)
    prefix = [(i % 60) + 1 for i in range(100)]
    engine.generate(prefix, sp)                      # caches 100-token prefix
    # 20-token suffix -> bucket 32; 100 + 32 > 128 -> prefix unusable
    longer = prefix + [(i % 60) + 1 for i in range(20)]
    got = engine.generate(longer, sp)
    ref = _ref_greedy(model, params, longer[-126:], 4)
    assert got == ref, (got, ref)


def test_chunked_prefill_matches_oneshot(rng):
    """enable_chunked_prefill parity: chunked prompt ingestion must produce
    identical greedy outputs, also when combined with the prefix cache."""
    from llm_in_practise_tpu.serve.prefix_cache import PrefixCache

    model, params = _tiny_model(rng)
    sp = SamplingParams(greedy=True, max_tokens=6)
    prompt = [(i * 7) % 60 + 1 for i in range(50)]

    baseline = InferenceEngine(
        model, params, max_slots=2, cache_len=128, cache_dtype=jnp.float32)
    ref = baseline.generate(prompt, sp)

    chunked = InferenceEngine(
        model, params, max_slots=2, cache_len=128, cache_dtype=jnp.float32,
        chunked_prefill=16)
    got = chunked.generate(prompt, sp)
    assert got == ref, (got, ref)

    # chunked + prefix cache: extension of a cached prompt, still exact
    pc = PrefixCache(min_prefix=8)
    engine = InferenceEngine(
        model, params, max_slots=2, cache_len=128, cache_dtype=jnp.float32,
        chunked_prefill=16, prefix_cache=pc)
    assert engine.generate(prompt, sp) == ref
    longer = prompt + [(i * 3) % 60 + 1 for i in range(40)]
    got_ext = engine.generate(longer, sp)
    ref_ext = _ref_greedy(model, params, longer, 6)
    assert got_ext == ref_ext, (got_ext, ref_ext)
    assert pc.hits >= 1


def test_chunked_prefill_interleaves_with_decode(rng):
    """While a long prompt chunk-prefills, an already-running request keeps
    producing tokens (the whole point of chunked prefill)."""
    model, params = _tiny_model(rng)
    engine = InferenceEngine(
        model, params, max_slots=2, cache_len=128, cache_dtype=jnp.float32,
        chunked_prefill=8)
    short = engine.submit([1, 2, 3], SamplingParams(greedy=True, max_tokens=30))
    engine.step()  # admits + starts decoding the short request
    long_prompt = [(i % 60) + 1 for i in range(64)]
    long_req = engine.submit(long_prompt,
                             SamplingParams(greedy=True, max_tokens=4))
    for _ in range(4):  # chunks of 8 over 64 tokens: still prefilling
        engine.step()
    assert short.n_generated > 1          # decode progressed during prefill
    assert long_req.first_token_time is None  # long prompt not done yet
    while engine.step():
        pass
    assert long_req.finish_reason is not None
    assert _ref_greedy(model, params, long_prompt, 4) == list(long_req)


def test_chunked_prefill_overflow_safe(rng):
    """Misaligned chunk sizes whose padded span would cross cache_len must
    fall back to one-shot prefill, not clamp-corrupt the KV."""
    import pytest

    model, params = _tiny_model(rng)
    sp = SamplingParams(greedy=True, max_tokens=4)
    # chunk 48 over a 126-token prompt: span ceil(126/48)*48 = 144 > 128
    engine = InferenceEngine(
        model, params, max_slots=1, cache_len=128, cache_dtype=jnp.float32,
        chunked_prefill=48)
    prompt = [(i % 60) + 1 for i in range(126)]
    got = engine.generate(prompt, sp)
    assert got == _ref_greedy(model, params, prompt, 4)

    with pytest.raises(ValueError, match="chunked_prefill"):
        InferenceEngine(model, params, max_slots=1, cache_len=128,
                        chunked_prefill=0)


def test_tensor_parallel_serving_matches_single_device(rng, devices):
    """vLLM --tensor-parallel-size parity: the engine over a TP-sharded
    mesh must reproduce single-device greedy decoding exactly."""
    from llm_in_practise_tpu.parallel import strategy as S
    from llm_in_practise_tpu.serve.engine import shard_params_for_serving

    model, params = _tiny_model(rng)
    prompt = [1, 5, 9, 13, 21, 34]
    sp = SamplingParams(greedy=True, max_tokens=8)
    ref = InferenceEngine(
        model, params, max_slots=2, cache_len=128, cache_dtype=jnp.float32,
    ).generate(prompt, sp)

    strat = S.tensor_parallel(model=2, data=1)
    mesh = strat.build_mesh(devices[:2])
    sharded = shard_params_for_serving(params, strat, mesh)
    engine = InferenceEngine(
        model, sharded, max_slots=2, cache_len=128, cache_dtype=jnp.float32,
        mesh=mesh,
    )
    got = engine.generate(prompt, sp)
    assert got == ref, (got, ref)
    # params really are distributed over both devices
    kernel = sharded["block_0"]["attn"]["q_proj"]["kernel"]
    assert len(kernel.sharding.device_set) == 2


def test_batched_admission_matches_isolated(rng):
    """Several pending requests admitted in one step share batched prefill
    dispatches (grouped by bucket, pow2 sub-batches); every output must
    equal the request's isolated single-slot greedy decode, and prefix
    entries must still be stored per request."""
    model, params = _tiny_model(rng)
    prompts = [
        [1, 5, 9, 13],                 # bucket 16
        [2, 4, 6, 8, 10, 12],          # bucket 16
        [7, 3] * 5,                    # bucket 16
        list(range(1, 20)),            # bucket 32
        [9, 9, 1],                     # bucket 16
    ]
    refs = [_ref_greedy(model, params, p, 8) for p in prompts]
    engine = InferenceEngine(
        model, params, max_slots=8, cache_len=128,
        cache_dtype=jnp.float32, prefix_cache=True,
    )
    sp = SamplingParams(greedy=True, max_tokens=8)
    reqs = [engine.submit(p, sp) for p in prompts]  # all pending together
    while engine.step():
        pass
    assert [r.result() for r in reqs] == refs
    # per-request APC entries survived the batched path: resubmitting a
    # cacheable (>= min_prefix tokens) prompt is a full-prefix hit
    hits_before = engine.prefix_cache.hits
    again = engine.submit(prompts[3], sp)
    while engine.step():
        pass
    assert again.result() == refs[3]
    assert engine.prefix_cache.hits == hits_before + 1


def test_batched_admission_dedups_duplicate_prompts(rng):
    """Identical cacheable prompts in one admission burst share ONE
    prefill: the duplicates defer until the batch stores its prefix entry
    and then insert as full-prefix hits (intra-burst APC reuse)."""
    model, params = _tiny_model(rng)
    prompt = list(range(1, 21))                 # 20 tokens >= min_prefix
    refs = _ref_greedy(model, params, prompt, 6)
    engine = InferenceEngine(
        model, params, max_slots=4, cache_len=128,
        cache_dtype=jnp.float32, prefix_cache=True,
    )
    sp = SamplingParams(greedy=True, max_tokens=6)
    reqs = [engine.submit(prompt, sp) for _ in range(4)]
    while engine.step():
        pass
    assert [r.result() for r in reqs] == [refs] * 4
    assert engine.prefix_cache.hits >= 3        # 3 duplicates reused
