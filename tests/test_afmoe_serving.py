"""Arcee Trinity (models/afmoe.py) through the serving engine: a (dense)
window layer's ring held by slot beside the paged (routed) global layer, the ring's part
of a chunk through the kernel (a window of 24 over chunks of 16 here),
chunked prefill and the fused mixed step against the plain reference, the
new counters by hand, what the engine refuses, and the benchmark's check
against wrong equations; ONE toy engine on the CPU serves them all."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import afmoe as ref
from benchmark.runners import serve_window_ring_cell as cell
from llm_in_practise_tpu.models import afmoe as am
from llm_in_practise_tpu.ops import swa_attention as swa
from llm_in_practise_tpu.serve.engine import InferenceEngine, SamplingParams

GREEDY = SamplingParams(temperature=0.0, greedy=True, max_tokens=12)
SLOTS, RING, CHUNK = 2, 24, 16


def _engine(cfg, params, **kw):
    opts = dict(max_slots=SLOTS, cache_len=64, kv_layout="paged",
                kv_page_size=8, chunked_prefill=CHUNK,
                cache_dtype=jnp.float32)
    opts.update(kw)
    return InferenceEngine(am.Afmoe(cfg), params, **opts)


@pytest.fixture(scope="module")
def served():
    """A 20-token prompt (two chunks, the last padded) decodes while a
    40-token one chunks beside it in fused mixed steps (three chunks; its
    ring of 24 wraps). The window outlasts the chunk, and the ring's part
    of every chunk goes through the kernel: a toy ring fits the dense
    corner, so the threshold is lowered for this module."""
    was, swa.RING_CORNER_MAX = swa.RING_CORNER_MAX, 0
    cfg = am.afmoe_config(
        compute_dtype="float32", n_layer=2, n_dense_layers=1, window=RING,
        window_layers=(True, False), n_routed_experts=8,
        experts_held=4, expert_offset=2, n_experts_per_tok=2, max_seq_len=64)
    params = am.random_params(cfg, 3, jnp.float32, std=0.2)
    eng = _engine(cfg, params)
    eng.start()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(4, cfg.vocab_size, n).tolist() for n in (20, 40)]
    eng.step_stats.capture = []
    with jax.default_matmul_precision("highest"):
        first = eng.submit(prompts[0], GREEDY)
        head = first.next_item()
        second = eng.submit(prompts[1], GREEDY)
        tokens = [[head] + first.result(), second.result()]
    with eng._lock:     # the last step books its statistics at its end
        captured, eng.step_stats.capture = eng.step_stats.capture, None
        records = eng.steptrace.records(limit=200)
    yield types.SimpleNamespace(cfg=cfg, params=params, eng=eng,
                                prompts=prompts, tokens=tokens,
                                captured=captured, records=records,
                                reference=ref.Reference(ref.geometry(cfg)))
    eng.stop()
    swa.RING_CORNER_MAX = was


def test_the_two_kinds_of_layer_are_read_off_the_template(served):
    pg = served.eng.paged
    assert pg.by_slot == [True, False] and pg.ring_rows == RING
    # a global row is one vector (2 heads x 16), stored by pages
    assert pg.form == "pages"
    assert pg.slot_state_bytes == SLOTS * 2 * RING * 2 * 16 * 4
    assert served.eng.debug_kv()["slot_state"]["buffers"] == {
        "k": {"shape": [SLOTS, RING, 2, 16]},
        "v": {"shape": [SLOTS, RING, 2, 16]}}


def test_engine_tokens_and_prefill_logits_are_the_references(served):
    """Teacher-forced through the float32 reference (no cache, no ring):
    every emitted token is the reference's own best, and each prompt's
    last-position logits, as the chunk / mixed program returned them, are
    the reference's."""
    assert {"chunk", "mixed", "decode"} <= {
        c["kind"] for c in served.captured}
    firsts = []
    with jax.default_matmul_precision("highest"):
        for prompt, tokens in zip(served.prompts, served.tokens):
            assert len(tokens) == 12
            want, _ = served.reference.logits(
                served.params, prompt + tokens[:-1], last=len(tokens))
            assert ref.token_margins(want, tokens)[
                "worst_margin_over_std"] < 1e-4
            firsts.append(want[0])      # the prompt's last position
    finished = [c for c in served.captured if c["last_logits"]]
    assert [c["kind"] for c in finished] == ["chunk", "mixed"]
    for c, want in zip(finished, firsts):
        (logits,) = c["last_logits"].values()
        assert ref.logit_error(logits, want)["max_over_std"] < 1e-3


def test_the_new_counters_by_hand(served):
    st, records = served.eng.step_stats, served.records
    lens = [len(p) for p in served.prompts]
    chunks = [r for r in records if "prefill_chunk_tokens" in r]
    trips = sum(-(-n // CHUNK) for n in lens)
    assert sum(r["prefill_chunk_tokens"] for r in chunks) == sum(lens)
    assert sum(r["prefill_chunk_capacity"] for r in chunks) == trips * CHUNK
    assert (st.prefill_chunk_tokens, st.prefill_chunk_capacity) == (
        sum(lens), trips * CHUNK)
    # the band at a ring of 24, and the key rows a chunk reads of it
    assert st.prefill_band_pairs == sum(
        sum(min(i + 1, RING) for i in range(n)) for n in lens)
    assert st.prefill_band_keys_read == sum(
        min(CHUNK, n - done) + min(done, RING - 1)
        for n in lens for done in range(0, n, CHUNK))
    assert st.prefill_global_pairs == sum(n * (n + 1) // 2 for n in lens)
    # every decode (and mixed step's decode half) reads every slot's ring
    dec = [r for r in records if "window_ring_rows_read" in r]
    assert dec and all(r["window_ring_rows_read"] == SLOTS * RING
                       and 0 < r["window_rows_attended"]
                       <= r["window_ring_rows_read"] for r in dec)
    assert st.window_ring_rows_read == len(dec) * SLOTS * RING
    # the global layer is GATHERED (no ``reads_pages`` here): every slot x
    # the pow2 view width, whatever the rows' lengths
    assert not any(served.eng.paged.in_place)
    assert all(r["global_view_tokens"] in (SLOTS * 32, SLOTS * 64)
               and "shared_kv_pages_read" not in r for r in dec)
    # 11 tokens each after the first: a row at length n attends min(n, 24)
    assert st.window_rows_attended == sum(
        min(n + t, RING) for n in lens for t in range(1, 12))


def test_metrics_name_the_new_counters(served):
    from llm_in_practise_tpu.serve.api import OpenAIServer

    text = OpenAIServer(served.eng, tokenizer=None,
                        model_name="m").registry.render()
    for name in ("llm_window_ring_rows_read_total",
                 "llm_window_rows_attended_total",
                 "llm_prefill_chunk_tokens_total",
                 "llm_prefill_chunk_capacity_total",
                 "llm_global_view_tokens_total",
                 "llm_kv_window_state_bytes", "llm_moe_layer_passes_total"):
        assert f"\n{name}" in text, name


@pytest.mark.parametrize("what, match", [
    ("adapter_registry", "multi-LoRA"), ("kv_pool", "tiered KV"),
    ("session_store", "session store"), ("handoff", "disaggregated"),
    ("prefix_cache", "prefix cache"), ("speculative_k", "speculative"),
    ("mesh", "mesh")])
def test_the_engine_refuses_what_it_refuses_for_rings_by_slot(served, what,
                                                              match):
    """``StepStats.check_engine`` on the built engine with one feature
    switched on: each is refused by its own name."""
    from llm_in_practise_tpu.serve.step_stats import StepStats

    eng = served.eng
    StepStats.check_engine(eng, "a model with layers held by slot")
    was = getattr(eng, what)
    setattr(eng, what, object())
    try:
        with pytest.raises(ValueError, match=match):
            StepStats.check_engine(eng, "a model with layers held by slot")
    finally:
        setattr(eng, what, was)


def test_the_contiguous_layout_is_refused(served):
    with pytest.raises(ValueError, match="contiguous"):
        _engine(served.cfg, served.params, kv_layout="contiguous")


@pytest.mark.parametrize("wrong", [
    None, {"gate": False},
    {"rotary": (True, True)}, {"rotary": (False, False)},
    {"norms": ref.NORMS[:1] + ref.NORMS[2:]}, {"window": RING - 1},
], ids=lambda w: "right" if w is None else "-".join(w))
def test_the_cells_check_fails_a_wrong_variant(served, wrong):
    """benchmark/runners/serve_window_ring_cell.py::check, as the chip
    runs it (a padded probe decodes while a longer one chunks beside it in
    mixed steps), on the toy engine: engine and reference of one mind
    pass; give the reference one wrong equation over the same weights and
    the check fails, by the logits."""
    geom = dict(ref.geometry(served.cfg), **(wrong or {}))
    sv = types.SimpleNamespace(engine=served.eng, cfg=served.cfg,
                               params=served.params, geom=geom)
    with jax.disable_jit(False), jax.default_matmul_precision("highest"):
        out = cell.check(sv, (20, 48), 7)
    assert out["ok"] is (wrong is None), out
    assert out["long_probe_ended_in_a_mixed_step"]
    if wrong is None:
        # 16 judged positions a probe (the padded probe's first is its
        # last REAL token's), one routed layer
        assert out["routing"]["pairs"] == 16 + 16
        assert out["worst"]["max_over_std"] < 1e-3
    else:
        assert out["worst"]["rms_over_std"] > 2 * ref.LOGIT_RMS_TOL, out
