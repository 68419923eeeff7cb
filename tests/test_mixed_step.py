"""Fused mixed-batch engine step (serve/mixed_step.py).

Run apart, a mixed-load step pays TWO device dispatches (chunk +
decode). The fused step runs the prefill chunk and the decode in ONE
dispatch. These tests pin:

- token-exactness: fused vs. sequential (``mixed_step=False``) produce
  identical greedy tokens AND identical cache contents mid-flight;
- dispatch accounting: exactly 1 engine-program dispatch per ``step()``
  under simultaneous prefill+decode (the new ``DispatchMeter``), vs.
  >= 2 on the sequential path;
- every active decoder gains its token in the step that chunks;
- a speculative engine keeps speculating beside a prefill, outputs
  unchanged.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
from llm_in_practise_tpu.serve.engine import InferenceEngine, SamplingParams


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
LONG = [(i * 7 + 3) % 64 for i in range(40)]   # 40 tokens -> 5 chunks of 8


def _run_mixed_load(eng):
    """Deterministic mixed load, manually stepped: two short prompts
    decode while a long prompt chunk-prefills."""
    sp = SamplingParams(greedy=True, max_tokens=24)
    h = [eng.submit(p, sp) for p in SHORT]
    eng.step()                      # admit both, first decode
    hl = eng.submit(LONG, SamplingParams(greedy=True, max_tokens=8))
    while eng.step():
        pass
    return [r.result() for r in (*h, hl)]


def test_fused_matches_sequential_tokens(model_params):
    model, params = model_params
    fused = _engine(model, params)                      # mixed_step default ON
    seq = _engine(model, params, mixed_step=False)
    out_f = _run_mixed_load(fused)
    out_s = _run_mixed_load(seq)
    assert out_f == out_s
    assert fused.mixed_blocks > 0                       # fused path really ran
    assert seq.mixed_blocks == 0


def test_fused_matches_sequential_cache_contents(model_params):
    """Lockstep-step both engines mid-flight and compare every slot's
    VALID cache rows (up to each row's host-tracked length) — the fused
    program must write the same KV the sequential dispatches write."""
    model, params = model_params
    sp = SamplingParams(greedy=True, max_tokens=30)
    engines = [_engine(model, params),
               _engine(model, params, mixed_step=False)]
    for eng in engines:
        for p in SHORT:
            eng.submit(p, sp)
        eng.step()
        eng.submit(LONG, SamplingParams(greedy=True, max_tokens=8))
        for _ in range(3):                    # mid-prefill, mid-decode
            eng.step()
    a, b = engines
    assert a.slot_prefill and b.slot_prefill  # comparison is mid-prefill
    assert np.array_equal(a.slot_len, b.slot_len)
    assert np.array_equal(a.slot_last_token, b.slot_last_token)
    assert {s: st["done"] for s, st in a.slot_prefill.items()} \
        == {s: st["done"] for s, st in b.slot_prefill.items()}
    valid = a.slot_len.copy()
    for s, st in a.slot_prefill.items():
        valid[s] = st["done"]
    for la, lb in zip(a.cache, b.cache):
        for key in la:
            if key == "index":
                continue
            for s in range(a.max_slots):
                v = int(valid[s])
                if v == 0:
                    continue
                np.testing.assert_allclose(
                    np.asarray(la[key])[s, :v],
                    np.asarray(lb[key])[s, :v],
                    rtol=1e-5, atol=1e-5, err_msg=f"{key} slot {s}")


def test_one_dispatch_per_step_under_mixed_load(model_params):
    """The acceptance bar: 1 long prompt mid-chunked-prefill + 2 active
    decoders => exactly ONE device dispatch per step(), and every
    decoder takes its token in it."""
    model, params = model_params
    eng = _engine(model, params)
    sp = SamplingParams(greedy=True, max_tokens=64)
    h = [eng.submit(p, sp) for p in SHORT]
    eng.step()                                # admission + first decode
    assert all(r.first_token_time is not None for r in h)
    hl = eng.submit(LONG, SamplingParams(greedy=True, max_tokens=8))
    steps_mixed = 0
    while hl.first_token_time is None:
        gen_before = [r.n_generated for r in h]
        eng.step()
        steps_mixed += 1
        assert steps_mixed < 12, "long prompt never activated"
        if eng.slot_prefill:                  # still mid-prefill after step
            # ONE dispatch covered chunk + decode
            assert eng.dispatch_meter.last_step == 1
            # every active decoder gained its token this step
            assert [r.n_generated for r in h] \
                == [g + 1 for g in gen_before]
    assert steps_mixed >= 2                   # prefill really interleaved
    assert eng.mixed_blocks >= steps_mixed - 1


def test_sequential_path_pays_two_dispatches(model_params):
    """The counterfactual the meter exists to show: with the fused step
    off, a mixed-load step costs >= 2 dispatches."""
    model, params = model_params
    eng = _engine(model, params, mixed_step=False)
    sp = SamplingParams(greedy=True, max_tokens=64)
    eng.submit(SHORT[0], sp)
    eng.step()
    eng.submit(LONG, SamplingParams(greedy=True, max_tokens=8))
    eng.step()
    assert eng.slot_prefill                   # mid-prefill
    assert eng.dispatch_meter.last_step >= 2


def test_decode_only_step_is_one_dispatch(model_params):
    """Sanity on the meter itself: a pure-decode step is one dispatch;
    the fused path adds prefill without adding a second."""
    model, params = model_params
    eng = _engine(model, params)
    eng.submit(SHORT[0], SamplingParams(greedy=True, max_tokens=64))
    eng.step()                                # admit (prefill dispatches)
    eng.step()                                # pure decode
    assert eng.dispatch_meter.last_step == 1
    assert eng.dispatch_meter.total > 1       # admission was counted too


def test_speculative_composes_with_a_prefill(model_params):
    """A verify step yields 1+accepted tokens per dispatch — strictly
    more than the fused step's one — so speculation keeps running while
    prompts prefill and the fused path stays out of the way. Outputs
    stay exact."""
    model, params = model_params

    def run(eng):
        # repetitive load prompt => the ngram drafter has material
        h = eng.submit([7, 8, 9, 7, 8, 9, 7, 8],
                       SamplingParams(greedy=True, max_tokens=30))
        eng.step()
        hl = eng.submit(LONG, SamplingParams(greedy=True, max_tokens=8))
        while eng.step():
            pass
        return [h.result(), hl.result()]

    ref = _engine(model, params)
    out_ref = run(ref)
    spec = _engine(model, params, speculative_k=3)
    out_spec = run(spec)
    assert out_spec == out_ref
    assert spec.mixed_blocks == 0            # fused path never engaged
    assert spec.spec_proposed > 0            # spec really ran


def test_spec_draft_miss_falls_through_to_plain_decode(model_params):
    """A speculative engine whose drafter finds nothing this step (no
    repeating structure) runs the plain decode in its place. Outputs
    stay exact vs the plain engine."""
    model, params = model_params
    prompt = [7, 23, 41, 3, 58, 11, 30, 9, 44, 17]   # no n-grams repeat
    sp = SamplingParams(greedy=True, max_tokens=20)
    ref = _engine(model, params, chunked_prefill=None).generate(prompt, sp)
    spec = _engine(model, params, chunked_prefill=None, speculative_k=3)
    assert spec.generate(prompt, sp) == ref
    # draft misses fell through to plain decodes: fewer verify rounds
    # than tokens
    assert spec.spec_rounds < len(ref)


def test_mixed_step_respects_cache_tail_fallback(model_params):
    """A decoder butting against the cache end makes the fused dispatch
    infeasible (its dead chunk-write window would scatter-clamp over
    attended KV): the engine must fall back to sequential dispatches —
    logged, token-exact — not corrupt the tail."""
    model, params = model_params
    outs = []
    engines = {}
    for mixed in (False, True):
        eng = engines[mixed] = _engine(model, params, cache_len=64,
                                       mixed_step=mixed)
        a = eng.submit(SHORT[0], SamplingParams(greedy=True,
                                                max_tokens=100))
        guard = 0
        while a.n_generated < 50:             # ride slot_len to 64 - 8
            eng.step()
            guard += 1
            assert guard < 60
        b = eng.submit(LONG[:20], SamplingParams(greedy=True,
                                                 max_tokens=4))
        while eng.step():
            pass
        assert a.finish_reason == "cache"     # really hit the tail
        outs.append((a.result(), b.result()))
    assert outs[0] == outs[1]
    # the fused engine really took the explicit fallback near the tail
    assert engines[True]._mixed_fallbacks_logged


# --- paged layout: the prefill half computes only the rows that chunk -------
#
# (PR 28) ``_paged_chunk_fn`` loops over the mid-prefill rows, one a
# trip, with a traced trip count, inside the chunk program and inside
# the fused mixed step. Reference: the same schedule on a
# CONTIGUOUS engine with the fused step off — the slot-plane
# ``batched_chunk`` body and a separate decode dispatch, code this
# change does not touch.

PAGED = dict(max_slots=8, chunked_prefill=8)
SEED_PROMPT = [(i * 5 + 2) % 64 for i in range(40)]   # 2 full pages of 16


def _long(i):
    return [(j * (3 + 2 * i) + i) % 64 for j in range(44 + 6 * i)]


def paged_rows(eng, slot, n):
    """Rows ``[0, n)`` of ``slot``, per layer and key, out of the pool."""
    flat = eng.paged.row_gather_idx(slot, n)[0]
    return [{k: np.asarray(buf)[flat] for k, buf in layer.items()}
            for layer in eng.paged.kv]


def contiguous_rows(eng, slot, n):
    return [{k: np.asarray(buf)[slot, :n] for k, buf in layer.items()
             if k != "index"} for layer in eng.cache]


def run_schedule(eng, k, n_decoding, *, prefix_hit):
    """``n_decoding`` short prompts decode; ``k`` long prompts arrive one
    engine step apart (so every row has another ``done``), the second
    one starting on a prefix hit where the engine has the pages.
    Returns (handles, their tokens, snapshot of every live row once all
    decode)."""
    if prefix_hit:
        eng.generate(SEED_PROMPT, SamplingParams(greedy=True, max_tokens=2))
    hs = [eng.submit(p, SamplingParams(greedy=True, max_tokens=60))
          for p in SHORT[:n_decoding]]
    eng.step()
    for i in range(k):
        prompt = _long(i)
        if i == 1:
            prompt = SEED_PROMPT[:32] + prompt     # two shared pages
        hs.append(eng.submit(prompt, SamplingParams(greedy=True,
                                                    max_tokens=24)))
        eng.step()
    most = 0
    while eng.slot_prefill:
        most = max(most, len(eng.slot_prefill))
        eng.step()
    assert most == k or k == 1
    rows = paged_rows if eng.paged is not None else contiguous_rows
    live = {h.uid: (s, int(eng.slot_len[s]))
            for s, r in enumerate(eng.slot_req) if r is not None
            for h in hs if h is r}
    snap = {uid: (n, rows(eng, s, n)) for uid, (s, n) in live.items()}
    while eng.step():
        pass
    eng.stop()          # returns the engine's bytes to the HBM ledger
    return hs, [h.result() for h in hs], snap


def assert_same_rows(snap_a, snap_b, uids_a, uids_b):
    compared = 0
    for ua, ub in zip(uids_a, uids_b):
        if ua not in snap_a or ub not in snap_b:
            continue
        (na, la), (nb, lb) = snap_a[ua], snap_b[ub]
        n = min(na, nb)      # a prefix hit activates a few steps sooner
        for a, b in zip(la, lb):
            for key in a:
                # float32, another batch shape (one row a trip against
                # the 8-row plane): the last bit may differ (measured
                # 6e-8 absolute), nothing more. Tokens are exact.
                np.testing.assert_allclose(a[key][:n], b[key][:n],
                                           rtol=1e-5, atol=1e-6)
        compared += 1
    assert compared >= 1


_REF_RUNS: dict = {}


@pytest.mark.parametrize("k,n_decoding", [(1, 2), (2, 2), (3, 2), (5, 2),
                                          (3, 1), (5, 1)])
def test_paged_rows_match_sequential(model_params, k, n_decoding):
    """k rows chunk beside decoding rows among 8 slots (fused mixed
    step): greedy tokens equal the contiguous sequential path's
    exactly, and every live row's KV to float32's last bits."""
    model, params = model_params
    paged = _engine(model, params, kv_layout="paged", prefix_cache=True,
                    **PAGED)
    hp, out_p, snap_p = run_schedule(paged, k, n_decoding, prefix_hit=True)
    if (k, n_decoding) not in _REF_RUNS:
        ref = _engine(model, params, mixed_step=False, **PAGED)
        _REF_RUNS[k, n_decoding] = run_schedule(ref, k, n_decoding,
                                                prefix_hit=False)
        assert ref.mixed_blocks == 0
    hr, out_r, snap_r = _REF_RUNS[k, n_decoding]
    assert out_p == out_r
    assert paged.mixed_blocks > 0
    if k > 1:                                  # a row began on a hit
        assert hp[n_decoding + 1].cache_outcome == "partial"
    assert_same_rows(snap_p, snap_r, [h.uid for h in hp],
                     [h.uid for h in hr])
    assert not paged._mixed_fallbacks_logged
    # the device computed exactly the rows that chunked
    assert paged.prefill_chunk_row_slots == paged.prefill_chunk_rows


@pytest.mark.parametrize("fused", [False, True])
def test_shared_page_under_chunk_window_forks(model_params, fused):
    """The defensive COW fork inside a chunk dispatch: a page under a
    mid-prefill row's NEXT chunk window (and, fused, under the decoding
    row's next token) gains a phantom reader. The dispatch must fork
    the page and hand the program the forked pool — the fork rebinds
    the donated ``paged.kv`` — and every token must equal the unshared
    run's, through the chunk-only program and through the fused step."""
    model, params = model_params
    sp = SamplingParams(greedy=True, max_tokens=12)
    outs = []
    for share in (False, True):
        eng = _engine(model, params, kv_layout="paged", **PAGED)
        hs = []
        if fused:
            hs.append(eng.submit(SHORT[0], sp))
            eng.step()
        hs.append(eng.submit(LONG, sp))
        eng.step()                             # the first chunk of 5
        (slot, st), = eng.slot_prefill.items()
        assert 0 < st["done"] < len(LONG)
        if share:
            pool, bt = eng.paged.pool, eng.paged.block_tables
            at = {slot: st["done"]}
            if fused:
                at.update((s, int(eng.slot_len[s]))
                          for s, r in enumerate(eng.slot_req)
                          if r is hs[0])
            held = {s: int(bt[s, pos // eng.paged.page_size])
                    for s, pos in at.items()}
            pool.share(list(held.values()))
        eng.step()
        assert eng.mixed_blocks == 2 * int(fused)
        # one engine program, plus one page copy a fork
        assert eng.dispatch_meter.last_step == 1 + (len(held) if share
                                                    else 0)
        if share:
            for s, page in held.items():       # forked, sharer untouched
                assert int(bt[s, at[s] // eng.paged.page_size]) != page
                assert pool.refcount(page) == 1
            pool.release(list(held.values()))
        while eng.step():
            pass
        outs.append([h.result() for h in hs])
        eng.paged.pool.check_leaks(0)
        eng.stop()
    assert outs[0] == outs[1]


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_decode_row_near_cache_end(model_params, layout):
    """A decode row past ``cache_len - chunk``: the contiguous fused
    step falls back to two dispatches (its dead chunk write would
    clamp over attended KV), the paged one has no such write and stays
    ONE dispatch. Tokens equal the sequential path's either way."""
    model, params = model_params
    outs, engines = [], {}
    for mixed in (False, True):
        eng = engines[mixed] = _engine(
            model, params, cache_len=64, mixed_step=mixed,
            kv_layout=layout)
        a = eng.submit(SHORT[0], SamplingParams(greedy=True,
                                                max_tokens=100))
        while a.n_generated < 52:             # slot_len 57 > 64 - 8
            eng.step()
        b = eng.submit(LONG[:20], SamplingParams(greedy=True,
                                                 max_tokens=4))
        fused_steps = 0
        while eng.step():
            if eng.slot_prefill and a.finish_reason is None:
                fused_steps += eng.dispatch_meter.last_step == 1
        assert a.finish_reason == "cache"
        outs.append((a.result(), b.result()))
        eng.stop()
    assert outs[0] == outs[1]
    fused = engines[True]
    if layout == "paged":
        assert not fused._mixed_fallbacks_logged
        assert fused.mixed_blocks >= 2
    else:
        assert fused._mixed_fallbacks_logged == {
            "decode row lacks the chunk dead-write window"}
        assert fused.mixed_blocks == 0


# --- counts (never times) of the paged prefill loop -------------------------


def test_chunk_row_counters_and_one_compile(model_params):
    """One row chunking among 8 slots books ONE computed row a chunk,
    not 8; a k = 1 step and a k = 5 step at one view width share ONE
    compiled program; the fused step stays one dispatch; the chunk
    dispatch's ledger pulse is a one-row view."""
    from llm_in_practise_tpu.obs.hbm import get_ledger

    model, params = model_params
    eng = _engine(model, params, kv_layout="paged", **PAGED)
    sp = SamplingParams(greedy=True, max_tokens=80)
    eng.submit(SHORT[0], sp)
    eng.step()
    # k = 1 beside a decoding row: fused, one dispatch, one row a chunk
    eng.submit(_long(0), sp)                     # 44 tokens: 6 chunks
    eng.step()
    assert eng.dispatch_meter.last_step == 1 and eng.mixed_blocks == 1
    assert (eng.prefill_chunk_rows, eng.prefill_chunk_row_slots) == (1, 1)
    rec = eng.steptrace.records(limit=1)[-1]
    assert (rec["chunk_rows"], rec["chunk_row_slots"]) == (1, 1)
    while eng.slot_prefill:
        eng.step()
    assert eng.prefill_chunk_rows == eng.prefill_chunk_row_slots == 6
    # k = 5 at the same view width (192 = cache_len): nothing compiles
    built = eng.compile_meter.compile_events
    for i in range(5):
        eng.submit(_long(i), sp)
    eng.step()
    assert len(eng.slot_prefill) == 5
    assert eng.dispatch_meter.last_step == 1
    assert eng.compile_meter.compile_events == built
    assert eng.prefill_chunk_rows == eng.prefill_chunk_row_slots == 6 + 5
    # the chunk-only program: its transient view is ONE row's
    alone = _engine(model, params, kv_layout="paged", **PAGED)
    alone.submit(_long(3), sp)
    alone.step()
    assert alone.slot_prefill and alone.mixed_blocks == 0
    W = alone._paged_width(8)
    tv = get_ledger().snapshot()["accounts"]["transient_view"]
    assert tv["last_pulse_bytes"] == alone.paged.view_bytes(W, 1)
    assert tv["last_pulse_bytes"] < alone.paged.view_bytes(W)
    eng.stop()
    alone.stop()
