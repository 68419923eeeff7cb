"""Phi-4-mini-flash through the serving engine: recurrent layers' state
held by slot beside window rings (8 rows here), ONE paged layer whose pages
the cross layers read where they lie, chunked prefill beside a decoding row in fused mixed
steps against the plain reference, the state's discipline (idle slots, a
reused slot, preemption), the cross-decoder's skip counted and exact, the
byte rates, and what the engine refuses; tiny sizes on the CPU."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import phi4flash as ref
from llm_in_practise_tpu.models import phi4flash as pf
from llm_in_practise_tpu.serve.engine import InferenceEngine, SamplingParams

GREEDY = SamplingParams(temperature=0.0, greedy=True, max_tokens=8)
SLOTS, PROMPTS = 4, (37, 70, 9)


def _engine(cfg, params, **kw):
    opts = dict(max_slots=SLOTS, cache_len=128, kv_layout="paged",
                kv_page_size=8, chunked_prefill=16,
                cache_dtype=jnp.float32)
    opts.update(kw)
    return InferenceEngine(pf.Phi4Flash(cfg), params, **opts)


def _state(eng, slot):
    """A slot's by-slot buffers (states, tails, rings), on the host."""
    return [np.asarray(buf[slot]) for layer, bounded in zip(
        eng.paged.kv, eng.paged.by_slot) if bounded
        for buf in layer.values()]


@pytest.fixture(scope="module")
def model():
    cfg = pf.phi4flash_config(compute_dtype="float32")
    return cfg, pf.random_params(cfg, 3, jnp.float32, std=0.1)


@pytest.fixture(scope="module")
def served(model):
    """A 37-token prompt decodes while a 70-token one chunks beside it in
    fused mixed steps (5 chunks, the last padded: 6 of 16) and a 9-token
    one (shorter than a chunk) is admitted through the chunk program too:
    page boundaries (8), ring wraps (8), chunk boundaries (16). One slot
    stays idle; every slot's state is read before."""
    cfg, params = model
    # a pool that holds this scenario and the cell's probes, and not four
    # rows of 80 tokens (the preemption test's)
    eng = _engine(cfg, params, kv_pool_tokens=288)
    # what a last tenant might have left in every slot
    eng.paged.kv = jax.tree.map(lambda a: jnp.full_like(a, 2.0),
                                eng.paged.kv)
    before = [_state(eng, s) for s in range(SLOTS)]
    eng.start()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(4, cfg.vocab_size, n).tolist() for n in PROMPTS]
    eng.step_stats.capture = []
    with jax.default_matmul_precision("highest"):
        first = eng.submit(prompts[0], GREEDY)
        head = first.next_item()
        rest = [eng.submit(p, GREEDY) for p in prompts[1:]]
        tokens = [[head] + first.result()] + [r.result() for r in rest]
    with eng._lock:     # the last step books its statistics at its end
        captured, eng.step_stats.capture = eng.step_stats.capture, None
        records = eng.steptrace.records(limit=200)
    yield types.SimpleNamespace(
        cfg=cfg, params=params, eng=eng, prompts=prompts, tokens=tokens,
        captured=captured, records=records, before=before,
        handles=[first] + rest)
    eng.stop()


def test_logits_and_tokens_are_the_references(served):
    """Last-position logits as the chunk / mixed program returned them, and
    8 greedy tokens teacher-forced through the float32 reference (no
    cache, ring, chunk or skip): over whatever the slots held before."""
    reference = ref.Reference(ref.geometry(served.cfg))
    assert {"chunk", "mixed", "decode"} <= {c["kind"]
                                            for c in served.captured}
    ended_in = {}
    for prompt, tokens, h in zip(served.prompts, served.tokens,
                                 served.handles):
        assert len(tokens) == 8
        with jax.default_matmul_precision("highest"):
            want = reference.logits(served.params, prompt + tokens[:-1],
                                    last=8)
        assert ref.token_margins(want, tokens)[
            "worst_margin_over_std"] < 1e-4
        got = next(c for c in served.captured
                   if h.uid in c["uids"].values()
                   and [s for s, u in c["uids"].items() if u == h.uid][0]
                   in c["last_logits"])
        slot = [s for s, u in got["uids"].items() if u == h.uid][0]
        ended_in[len(prompt)] = got["kind"]
        assert ref.logit_error(got["last_logits"][slot], want[0])[
            "max_over_std"] < 1e-4
    assert ended_in[70] == "mixed"      # beside the decoding row


def test_idle_and_mid_prefill_slots_keep_their_state(served):
    """Every decode and mixed step ran over the whole slot plane: the slot
    no request used holds what it held, bit for bit."""
    used = {s for c in served.captured for s in c["uids"]}
    idle, = set(range(SLOTS)) - used
    for before, after in zip(served.before[idle], _state(served.eng, idle)):
        np.testing.assert_array_equal(before, after)
    st = served.eng.step_stats
    assert 0 < st.ssm_state_rows_advanced < st.ssm_state_rows_held


def test_cross_decoder_skip_is_counted(served):
    """Rows through the cross-decoder = the decode plane's rows + ONE a
    prompt (its last position), never a chunk's tokens; the self-decoder
    sees every real prompt token."""
    st, rec = served.eng.step_stats, served.records
    assert st.cross_decoder_prefill_rows == len(PROMPTS)
    assert st.ssm_scan_tokens == st.prefill_chunk_tokens == sum(PROMPTS)
    decode_rows = sum(r.get("ssm_state_rows_held", 0) for r in rec)
    assert st.cross_decoder_rows == decode_rows + len(PROMPTS)
    assert st.self_decoder_rows == decode_rows + sum(PROMPTS)
    chunks = [r for r in rec if "cross_decoder_prefill_rows" in r]
    assert all(r["cross_decoder_prefill_rows"] <= 2 for r in chunks)
    assert any(r["cross_decoder_prefill_rows"] == 0 for r in chunks)
    # true lengths x the 2 layers that read the pages (full + 1 cross)
    dec = [r for r in rec if "shared_kv_rows_attended" in r]
    assert all(r["shared_kv_rows_attended"]
               == 2 * r["global_tokens_attended"] for r in dec)
    assert st.load is None and served.eng.routing_load is None


def _jitted(fn):
    """The ``jax.jit`` under the engine's meters."""
    while not hasattr(fn, "_cache_size"):
        fn = fn.__wrapped__
    return fn


def test_decode_reads_the_pages_where_they_lie(served):
    """No view of the paged layer in a decode step: the step books the
    rows its readers COPIED (each live row's length up to whole blocks:
    a slot's 16 pages here, one block), the pages x 2 readers, and
    gathers nothing; one decode executable served every length; a mixed
    step still gathers its chunk row's one-row view."""
    eng, st = served.eng, served.eng.step_stats
    pg = eng.paged
    assert pg.in_place == [False, False, True] and eng._reads_pages
    assert st.page_block == pg.pages_per_slot == 16
    dec = [r for r in served.records if "shared_kv_rows_attended" in r]
    lengths = {r["global_tokens_attended"] for r in dec}
    assert len(lengths) > 8         # steps at many lengths
    for r in dec:
        live = r["ssm_state_rows_advanced"]         # one token a step
        assert r["global_view_tokens"] == live * 16 * pg.page_size
        assert r["shared_kv_pages_read"] == live * 16 * 2
        chunked = r.get("prefill_chunk_capacity", 0) // 16
        # a chunk row's one-row view, whole pages; nothing for the plane
        assert (r["view_pages"] > 0) == (chunked > 0)
    assert st.global_view_tokens >= sum(r["global_view_tokens"] for r in dec)
    assert _jitted(eng._pg_decode)._cache_size() == 1


def test_a_decode_row_lands_in_its_page_and_nowhere_else(served):
    """The decode program itself, on a copy of the pool: slot 1 decodes
    at position 9 (its second page, row 1); slot 2 is mid-prefill and
    slots 0 and 3 idle: their rows go to the trash page. No write-back
    pass follows, so anything else that changed would show."""
    eng, pg = served.eng, served.eng.paged
    size = pg.page_size
    table = np.zeros((SLOTS, pg.pages_per_slot), np.int32)
    table[1, :2] = (5, 9)
    table[2, :1] = 7
    index = np.array([0, 9, 3, 0], np.int32)
    sidx = np.array([[0], [9 * size + 1], [3], [0]], np.int32)
    with eng._lock:
        before = {k: np.asarray(v) for k, v in pg.kv[2].items()}
        pool = jax.tree.map(jnp.copy, pg.kv)        # the program donates
        built = _jitted(eng._pg_decode)._cache_size()
        _, _, new, *_ = eng._pg_decode(
            eng.params, pool, jnp.asarray(table), jnp.asarray(index),
            jnp.asarray(sidx), jnp.zeros((SLOTS,), jnp.int32),
            jnp.full((SLOTS,), -1, jnp.int32), jax.random.PRNGKey(0),
            jnp.asarray(eng._temperature), jnp.asarray(eng._top_k),
            jnp.asarray(eng._top_p), jnp.ones((SLOTS,), bool))
        assert _jitted(eng._pg_decode)._cache_size() == built
    trash = {(0, r) for r in range(size)}
    for key, old in before.items():
        changed = {tuple(at) for at in np.argwhere(
            (np.asarray(new[2][key]) != old).any(axis=-1))}
        assert (9, 1) in changed and changed <= trash | {(9, 1)}, key


def test_stores_bytes_and_metrics(served):
    from llm_in_practise_tpu.serve.api import OpenAIServer

    eng, pg = served.eng, served.eng.paged
    # three entries: the 3 states, the 2 rings, the ONE layer that grows
    assert pg.by_slot == [True, True, False]
    assert pg.recurrent == [True, False, False]
    assert pg.ring_rows == 8 and pg.form == "pages"
    # 3 states (16 x 128 float32 + a 3-row tail) and 2 rings of 8 rows
    state = 3 * (16 * 128 + 3 * 128) * 4
    assert pg.recurrent_state_bytes == SLOTS * state
    assert pg.slot_bytes == state + 2 * 8 * 64 * 4
    snap = eng.debug_kv()["slot_state"]
    assert (snap["layers"], snap["recurrent_layers"],
            snap["paged_layers"]) == (2, 1, 1)
    assert snap["recurrent_bytes"] == pg.recurrent_state_bytes
    assert snap["buffers"]["ssm"] == {"shape": [SLOTS, 3, 16, 128]}
    text = OpenAIServer(eng, tokenizer=None,
                        model_name="m").registry.render()
    for name in ("llm_kv_recurrent_state_bytes", "llm_ssm_scan_tokens_total",
                 "llm_ssm_state_rows_advanced_total",
                 "llm_ssm_state_rows_held_total",
                 "llm_self_decoder_rows_total",
                 "llm_cross_decoder_rows_total",
                 "llm_cross_decoder_prefill_rows_total",
                 "llm_shared_kv_rows_attended_total",
                 "llm_shared_kv_pages_read_total",
                 "llm_window_rows_attended_total",
                 "llm_global_view_tokens_total"):
        assert f"\n{name}" in text, name
    assert "llm_moe_layer_passes_total" not in text


def test_a_page_copy_leaves_the_slots_alone(served):
    """The copy-on-write fork of a shared page (no feature that shares
    pages is accepted beside layers held by slot, so nothing reaches it
    today) copies a PAGE: in a layer held by slot the same indices would
    name slots, and slot 1's state would become slot 3's."""
    eng = served.eng
    with eng._lock:
        pool = eng.paged.kv
        new = eng._paged_page_copy_fn(pool, jnp.int32(3), jnp.int32(1))
        for old, got, bounded in zip(pool, new, eng.paged.by_slot):
            for key in old:
                same = np.array_equal(np.asarray(old[key]),
                                      np.asarray(got[key]))
                if bounded:
                    assert same, key
                else:
                    np.testing.assert_array_equal(got[key][1], old[key][3])


def test_a_state_is_not_a_cache_of_its_size(model):
    """``cache_kinds`` at a cache length equal to an axis of the state
    (3 stacked layers): the recurrent entry stays by slot (its buffers
    never follow ``max_len``)."""
    from llm_in_practise_tpu.serve import paged_kv

    cfg, _ = model
    _, by_slot, recurrent = paged_kv.cache_kinds(
        pf.Phi4Flash(cfg), jnp.float32, 3)
    assert recurrent == [True, False, False]
    # (a ring of min(3, window) rows follows a cache of 3, as ever)
    assert by_slot == [True, False, False]
    assert paged_kv.kv_row_bytes(pf.Phi4Flash(cfg), jnp.float32) == (
        (16 + 16 + 32) * 4)


@pytest.fixture(scope="module")
def observed(served):
    """The benchmark cell's own probes on the engine above
    (``serve_recurrent_cell.probe``): a short probe decodes while a long
    one chunks beside it, two fillers hold the other slots and decode all
    through."""
    from benchmark.runners import serve_recurrent_cell as cell

    sv = types.SimpleNamespace(engine=served.eng, cfg=served.cfg,
                               params=served.params,
                               geom=ref.geometry(served.cfg))
    with jax.default_matmul_precision("highest"):
        seen = cell.probe(sv, (37, 70), 11, fillers=(9, 40))
    return cell, sv, seen


@pytest.mark.parametrize("fault", [
    None, "lambda_learned", "subln", "gmu_memory", "memory_shift", "d_skip",
    "window_mask", "conv_break", "slots_crossed"])
def test_the_cells_check_passes_sound_and_fails_each_fault(observed, fault):
    """``serve_recurrent_cell.judge`` on what the probes observed: every
    slot was live, every store of every caching layer lies on the
    reference's, and the same observation judged against a reference
    with ONE form left out, or with the probes' slots crossed, breaks a
    limit (the chip's limits: a float32 toy lies far inside them)."""
    cell, sv, seen = observed
    assert seen["slots_live"] == SLOTS and len(seen["fillers"]) == 2
    forms = dict(cell.faults(sv), conv_break=16)
    geom = None if fault in (None, "slots_crossed") else dict(
        sv.geom, **{fault: forms[fault]})
    with jax.disable_jit(), jax.default_matmul_precision("highest"):
        out = cell.judge(sv, seen, geom, crossed=fault == "slots_crossed")
    if fault is None:
        assert out["ok"], out
        worst = out["worst"]
        assert max(worst["state_error"] + worst["tail_error"]
                   + worst["rows_error"]) < 1e-4
        assert worst["page_row_worst"] < 1e-4
        assert worst["filler_slow_state_error"] < 1e-4
        assert worst["max_over_std"] < 1e-4
    else:
        assert not out["ok"] and out["limits_failed"], out


def test_preemption_reuse_and_recompute_keep_the_tokens(served):
    """A pool for three of four requests: preemption fires, the requeued
    request recomputes from position 0 into a slot whose state, tails and
    rings hold another tenant's, and every stream is the reference's."""
    cfg, params, eng = served.cfg, served.params, served.eng
    sp = SamplingParams(temperature=0.0, greedy=True, max_tokens=60)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(4, cfg.vocab_size, 20).tolist()
               for _ in range(SLOTS)]
    reference = ref.Reference(ref.geometry(cfg))
    before = eng.preemptions
    with jax.default_matmul_precision("highest"):
        handles = [eng.submit(p, sp) for p in prompts]
        streams = [h.result() for h in handles]
        assert eng.preemptions > before
        for prompt, tokens in zip(prompts, streams):
            assert len(tokens) == 60
            want = reference.logits(params, prompt + tokens[:-1], last=60)
            assert ref.token_margins(want, tokens)[
                "worst_margin_over_std"] < 1e-4
    eng.paged.pool.check_leaks(0)


@pytest.mark.parametrize("what, match", [
    ("adapter_registry", "multi-LoRA"), ("kv_pool", "tiered KV"),
    ("session_store", "session store"), ("handoff", "disaggregated"),
    ("prefix_cache", "prefix cache"), ("speculative_k", "speculative"),
    ("mesh", "mesh")])
def test_every_refusal_names_its_feature(served, what, match):
    from llm_in_practise_tpu.serve.step_stats import StepStats

    eng = served.eng
    StepStats.check_engine(eng, "a model with layers held by slot")
    was = getattr(eng, what)
    setattr(eng, what, object())
    try:
        with pytest.raises(ValueError, match=match) as err:
            StepStats.check_engine(eng, "a model with layers held by slot")
        assert "layers held by slot" in str(err.value)
    finally:
        setattr(eng, what, was)


def test_the_contiguous_layout_is_refused(model):
    cfg, params = model
    with pytest.raises(ValueError, match="contiguous"):
        _engine(cfg, params, kv_layout="contiguous")
