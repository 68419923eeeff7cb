"""MiMo-V2 through the serving engine: window layers held by slot beside
the paged global layers (a ring of 8 rows a slot here), chunked prefill
and the fused mixed step against the plain reference, preemption, the two
byte rates, what the engine refuses, and the benchmark's check against
three wrong equations; tiny sizes on the CPU."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mimo_v2 as ref
from benchmark.runners import serve_hybrid_cell as cell
from llm_in_practise_tpu.models import mimo_v2 as mm
from llm_in_practise_tpu.models import qwen3
from llm_in_practise_tpu.obs.hbm import get_ledger
from llm_in_practise_tpu.serve import paged_kv
from llm_in_practise_tpu.serve.engine import InferenceEngine, SamplingParams

GREEDY = SamplingParams(temperature=0.0, greedy=True, max_tokens=12)


def _engine(cfg, params, **kw):
    opts = dict(max_slots=4, cache_len=128, kv_layout="paged",
                kv_page_size=8, chunked_prefill=16,
                cache_dtype=jnp.float32)
    opts.update(kw)
    return InferenceEngine(mm.MiMoV2(cfg), params, **opts)


@pytest.fixture(scope="module")
def model():
    cfg = mm.mimo_v2_config(compute_dtype="float32", experts_held=4,
                            expert_offset=4)
    return cfg, mm.random_params(cfg, 3, jnp.float32, std=0.2)


@pytest.fixture(scope="module")
def served(model):
    """A 37-token prompt decodes while a 70-token one chunks beside it in
    fused mixed steps, and a 9-token one (shorter than a chunk) is
    admitted through the chunk program too: page boundaries (8), ring
    wraps (8), chunk boundaries (16)."""
    cfg, params = model
    eng = _engine(cfg, params)
    eng.start()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(4, cfg.vocab_size, n).tolist()
               for n in (37, 70, 9)]
    eng.step_stats.capture = []
    with jax.default_matmul_precision("highest"):
        first = eng.submit(prompts[0], GREEDY)
        head = first.next_item()
        rest = [eng.submit(p, GREEDY) for p in prompts[1:]]
        tokens = [[head] + first.result()] + [r.result() for r in rest]
    with eng._lock:     # the last step books its statistics at its end
        captured, eng.step_stats.capture = eng.step_stats.capture, None
        records = eng.steptrace.records(limit=200)
    yield types.SimpleNamespace(cfg=cfg, params=params, eng=eng,
                                prompts=prompts, tokens=tokens,
                                captured=captured, records=records)
    eng.stop()


def test_engine_tokens_are_the_references_greedy_tokens(served):
    """Teacher-forced through the float32 reference (no cache, no ring):
    every emitted token is the reference's own best."""
    reference = ref.Reference(ref.geometry(served.cfg))
    kinds = {c["kind"] for c in served.captured}
    assert {"chunk", "mixed", "decode"} <= kinds
    for prompt, tokens in zip(served.prompts, served.tokens):
        assert len(tokens) == 12
        with jax.default_matmul_precision("highest"):
            want, _ = reference.logits(served.params, prompt + tokens[:-1],
                                       last=len(tokens))
        assert ref.token_margins(want, tokens)[
            "worst_margin_over_std"] < 1e-4


def test_prefill_logits_are_the_references(served):
    reference = ref.Reference(ref.geometry(served.cfg))
    finished = [c for c in served.captured if c["last_logits"]]
    assert any(c["kind"] == "mixed" for c in finished)
    seen = 0
    for c in finished:
        for slot, logits in c["last_logits"].items():
            uid = c["uids"][slot]
            prompt = served.prompts[uid - min(
                u for k in served.captured for u in k["uids"].values())]
            with jax.default_matmul_precision("highest"):
                want, _ = reference.logits(served.params, prompt)
            err = ref.logit_error(logits, want[0])
            assert err["max_over_std"] < 1e-3, err
            seen += 1
    assert seen == 3


def test_step_records_count_both_kinds_of_layer(served):
    eng, st = served.eng, served.eng.step_stats
    assert st.ring_rows == 8
    dec = [r for r in served.records if "window_rows_attended" in r]
    assert dec and all(
        r["window_rows_attended"] <= r["global_tokens_attended"]
        <= r["global_view_tokens"] for r in dec)
    # a ring never holds more than its rows a slot
    assert all(r["window_rows_attended"] <= 8 * eng.max_slots for r in dec)
    chunks = [r for r in served.records if "prefill_band_pairs" in r]
    assert sum(r["prefill_band_pairs"] for r in chunks) == sum(
        sum(min(i + 1, 8) for i in range(len(p))) for p in served.prompts)
    assert sum(r["prefill_global_pairs"] for r in chunks) == sum(
        len(p) * (len(p) + 1) // 2 for p in served.prompts)
    assert "latent_tokens_attended" not in dec[0]
    # one attended / view pair, under the name that fits the cache
    assert st.load.layer_passes > 0 and not hasattr(st, "latent_view_tokens")
    assert st.global_view_tokens >= sum(r["global_view_tokens"] for r in dec)


def _jitted(fn):
    """The ``jax.jit`` under the engine's meters."""
    while not hasattr(fn, "_cache_size"):
        fn = fn.__wrapped__
    return fn


def test_decode_reads_the_global_layers_pages_where_they_lie(served):
    """The class declares ``reads_pages``: no view of the two global
    layers in a decode step. The step books the rows ONE layer's reader
    copied (each live row's length up to whole blocks: a slot's 16 pages
    here, one block) and the pages x 2 layers, and gathers nothing; ONE
    decode executable served every length; a mixed step still gathers its
    chunk row's one-row view, and only a chunk row's dispatch pulses the
    ledger's ``transient_view``."""
    eng, st = served.eng, served.eng.step_stats
    pg = eng.paged
    assert pg.in_place == [True, False, False, True] and eng._reads_pages
    assert st.page_block == pg.pages_per_slot == 16
    dec = [r for r in served.records if "window_rows_attended" in r]
    assert len({r["global_tokens_attended"] for r in dec}) > 8
    for r in dec:
        live, rest = divmod(r["global_view_tokens"], 16 * pg.page_size)
        assert 1 <= live <= eng.max_slots and rest == 0
        assert r["global_tokens_attended"] <= r["global_view_tokens"]
        assert r["global_pages_read"] == live * 16 * 2
        assert "shared_kv_pages_read" not in r
        chunked = r.get("prefill_chunk_capacity", 0) // 16
        # a chunk row's one-row view, whole pages; nothing for the plane
        assert (r["view_pages"] > 0) == (chunked > 0)
    assert st.global_pages_read == sum(r["global_pages_read"] for r in dec)
    assert _jitted(eng._pg_decode)._cache_size() == 1
    # one more short prompt: its ONE chunk trip pulses the ledger's
    # transient view, its decode steps do not
    pulses = lambda: get_ledger().snapshot()["accounts"][  # noqa: E731
        "transient_view"]["pulses"]
    before = pulses()
    assert len(eng.submit(served.prompts[2], dataclasses.replace(
        GREEDY, max_tokens=5)).result()) == 5
    assert pulses() - before == 1


def test_a_chunk_rows_view_is_no_narrower_than_a_gathered_engines(served):
    """The 70-token prompt chunks beside the 37-token one alone: its view
    in a mixed step is its own ``done`` + a chunk up to a power of two AND
    no narrower than the decoding row's length + a chunk gives (64), the
    narrowest a gathered engine builds there, so its first chunks (16 and
    32 wide by themselves) build no mixed program of their own."""
    eng = served.eng
    with eng._lock:
        seen = eng.steptrace.records(limit=1)[-1]["seq"]
    lead = eng.submit(served.prompts[0], GREEDY)
    lead.next_item()
    eng.submit(served.prompts[1],
               dataclasses.replace(GREEDY, max_tokens=2)).result()
    lead.result()
    with eng._lock:
        mixed = [r for r in eng.steptrace.records(limit=60)
                 if r["seq"] > seen and "global_pages_read" in r
                 and r["view_pages"]]
    # 70 tokens: chunks at done = 0 .. 64; one row's view of 8-row pages
    assert [r["view_pages"] * 8 for r in mixed] == [64, 64, 64, 64, 128]


class _Gathered(mm.MiMoV2):
    """The same model on the gathered path: a decode program gets a pow2
    view of every global layer."""
    reads_pages = False


def test_pages_in_place_give_the_gathered_paths_tokens(served):
    """The 9-token prompt again, alone, through an engine whose decode
    GATHERS (``reads_pages`` False): the same greedy tokens and the same
    last-position logits as the rows read in place gave."""
    twin = InferenceEngine(_Gathered(served.cfg), served.params, max_slots=4,
                           cache_len=128, kv_layout="paged", kv_page_size=8,
                           chunked_prefill=16, cache_dtype=jnp.float32)
    assert not any(twin.paged.in_place) and not twin._reads_pages
    twin.step_stats.capture = []
    with jax.default_matmul_precision("highest"):
        handle = twin.submit(served.prompts[2], GREEDY)
        while twin.step():
            pass
    assert handle.result() == served.tokens[2]
    (want,) = [c["last_logits"] for c in twin.step_stats.capture
               if c["last_logits"]]
    first = min(u for c in served.captured for u in c["uids"].values())
    (got,) = [logits for c in served.captured
              for slot, logits in c["last_logits"].items()
              if c["uids"][slot] == first + 2]
    np.testing.assert_allclose(got, next(iter(want.values())), atol=1e-4)
    # a gathered decode's view is every slot x a pow2 width
    dec = [r for r in twin.steptrace.records(limit=50)
           if "window_rows_attended" in r]
    assert dec and all(r["global_view_tokens"] in (4 * 16, 4 * 32)
                       and "global_pages_read" not in r for r in dec)
    twin.stop()


def test_metrics_and_debug_name_both_stores(served):
    from llm_in_practise_tpu.serve.api import OpenAIServer

    eng = served.eng
    snap = eng.debug_kv()
    assert snap["slot_state"] == {
        "layers": 2, "paged_layers": 2,
        "recurrent_layers": 0, "recurrent_bytes": 0,
        "recurrent_ledger_account": "kv.recurrent_state",
        "ledger_account": "kv.window_state",
        "slot_bytes": 2 * 8 * 4 * (24 + 16) * 4,
        "bytes": 4 * 2 * 8 * 4 * (24 + 16) * 4,
        "buffers": {"k": {"shape": [4, 8, 4, 24]},
                    "v": {"shape": [4, 8, 4, 16]}}}
    # a global layer's row is one vector (2 heads x 24), stored by pages
    # padded to whole lane tiles
    assert snap["buffers"]["k"] == {"form": "pages", "row_bytes": 128 * 4}
    text = OpenAIServer(eng, tokenizer=None,
                        model_name="m").registry.render()
    for name in ("llm_kv_window_state_bytes", "llm_kv_global_pool_bytes",
                 "llm_window_rows_attended_total",
                 "llm_global_tokens_attended_total",
                 "llm_global_view_tokens_total",
                 "llm_global_pages_read_total",
                 "llm_moe_layer_passes_total"):
        assert f"\n{name}" in text, name
    assert "llm_latent_tokens_attended_total" not in text


@pytest.mark.parametrize("cache_len", [64, 128, 256])
def test_window_state_bytes_do_not_change_with_cache_len(model, cache_len):
    cfg, params = model
    ledger = get_ledger()
    before = ledger.snapshot()["accounts"].get(
        "kv.window_state", {}).get("bytes", 0)
    eng = _engine(cfg, params, cache_len=cache_len)
    pg = eng.paged
    assert pg.by_slot == [False, True, True, False]
    assert pg.slot_state_bytes == 4 * 2 * 8 * 4 * (24 + 16) * 4
    # the global layers only: k and v rows of 48 and 32, stored as 128
    assert pg.form == "pages" and pg.row_bytes == 2 * 2 * 128 * 4
    assert pg.pool_bytes == (4 * cache_len + 8) * pg.row_bytes
    assert paged_kv.kv_row_bytes(mm.MiMoV2(cfg), jnp.float32) == (
        2 * 2 * (24 + 16) * 4)
    assert pg.view_bytes(64) == 64 * 4 * pg.row_bytes
    assert pg.fits_ever(4 * cache_len) and not pg.fits_ever(
        4 * cache_len + 9)
    after = ledger.snapshot()["accounts"]["kv.window_state"]["bytes"]
    assert after - before == pg.slot_state_bytes
    eng.stop()
    assert ledger.snapshot()["accounts"]["kv.window_state"][
        "bytes"] == before


def test_a_model_without_such_layers_builds_todays_pools():
    """The choice is read off the cache's shapes: a dense GQA model has
    no layer held by slot, one rate, and the flat pool of its own
    rows."""
    cfg = qwen3.qwen3_config(vocab_size=64, hidden_size=32, n_layer=2,
                             n_head=4, n_kv_head=2, head_dim=16,
                             intermediate_size=64, max_seq_len=64)
    pg = paged_kv.PagedKV(qwen3.Qwen3(cfg), max_slots=2, cache_len=64,
                          page_size=8, pool_tokens=128, dtype=jnp.bfloat16)
    assert pg.by_slot == [False, False] and pg.slot_state_bytes == 0
    assert pg.ring_rows == 0 and pg.form == "rows"
    assert pg.kv[0]["k"].shape == (136, 2, 16)
    pg.close()
    assert paged_kv.stored_by_pages([(576,)]) and not (
        paged_kv.stored_by_pages([(4, 192), (4, 128)]))


def test_keys_of_192_are_served_from_whole_lane_rows():
    """The published key width: the model's global rows are one vector
    of heads x 192 (384 here, whole lane tiles) and stored by pages as
    they are; a ring keeps its (heads, 192) rows; the tokens are the
    reference's."""
    cfg = mm.mimo_v2_config(
        compute_dtype="float32", head_dim=192, v_head_dim=128,
        n_head=4, n_kv_head=2, swa_n_kv_head=2, hidden_size=32,
        experts_held=4)
    params = mm.random_params(cfg, 1, jnp.float32, std=0.2)
    eng = _engine(cfg, params)
    assert eng.paged.form == "pages"
    assert eng.paged.kv[0]["k"].shape[1:] == (8, 384)
    assert eng.paged.kv[0]["v"].shape[1:] == (8, 256)
    assert eng.paged.kv[1]["k"].shape == (4, 8, 2, 192)     # a ring: as is
    rng = np.random.default_rng(4)
    prompt = rng.integers(4, cfg.vocab_size, 21).tolist()
    with jax.default_matmul_precision("highest"):
        handle = eng.submit(prompt, GREEDY)
        while eng.step():
            pass
        tokens = handle.result()
        want, _ = ref.Reference(ref.geometry(cfg)).logits(
            params, prompt + tokens[:-1], last=len(tokens))
    assert ref.token_margins(want, tokens)["worst_margin_over_std"] < 1e-4
    eng.stop()


def test_preemption_and_resume_keep_the_tokens(model):
    """A pool for two of three requests: preemption fires, the requeued
    request recomputes into its slot's ring over whatever the last tenant
    left there, and every stream is the reference's."""
    cfg, params = model
    eng = _engine(cfg, params, kv_pool_tokens=96)
    sp = SamplingParams(temperature=0.0, greedy=True, max_tokens=40)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(4, cfg.vocab_size, 20).tolist()
               for _ in range(3)]
    reference = ref.Reference(ref.geometry(cfg))
    with jax.default_matmul_precision("highest"):
        handles = [eng.submit(p, sp) for p in prompts]
        while eng.step():
            pass
        assert eng.preemptions > 0
        for prompt, h in zip(prompts, handles):
            tokens = h.result()
            assert len(tokens) == 40
            want, _ = reference.logits(params, prompt + tokens[:-1],
                                       last=40)
            assert ref.token_margins(want, tokens)[
                "worst_margin_over_std"] < 1e-4
    eng.paged.pool.check_leaks(0)
    eng.stop()


def test_engine_refuses_what_the_model_cannot_meet(model):
    cfg, params = model
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",))
    for kw, match in (({"mesh": mesh}, "mesh"),
                      ({"kv_layout": "contiguous"}, "contiguous"),
                      ({"speculative_k": 2}, "speculative"),
                      ({"prefix_cache": True}, "prefix cache")):
        with pytest.raises(ValueError, match=match):
            _engine(cfg, params, **kw)


@pytest.mark.parametrize("what, match", [
    ("adapter_registry", "multi-LoRA"), ("kv_pool", "tiered KV"),
    ("session_store", "session store"), ("handoff", "disaggregated"),
    ("prefix_cache", "prefix cache"), ("speculative_k", "speculative"),
    ("mesh", "mesh")])
def test_every_refusal_names_its_feature(model, what, match):
    """``StepStats.check_engine`` on a built engine with one feature
    switched on: each is refused by its own name, for a model with
    layers held by slot."""
    from llm_in_practise_tpu.serve.step_stats import StepStats

    cfg, params = model
    eng = _engine(cfg, params)
    StepStats.check_engine(eng, "a model with layers held by slot")
    was = getattr(eng, what)
    setattr(eng, what, object())
    with pytest.raises(ValueError, match=match) as err:
        StepStats.check_engine(eng, "a model with layers held by slot")
    assert "layers held by slot" in str(err.value)
    setattr(eng, what, was)
    eng.stop()


@dataclasses.dataclass(frozen=True)
class _Wrong:
    name: str
    config: dict
    drop_sink: bool = False


@pytest.fixture(scope="module")
def sharp():
    """The toy model of the check's test, served in bf16: the query / key
    projections scaled up so that attention is not flat, the sinks raised
    so that they take real mass, as learned ones do."""
    cfg = mm.mimo_v2_config(
        compute_dtype="bfloat16", experts_held=4, hidden_size=128,
        n_layer=3, hybrid_layer_pattern=(0, 1, 1),
        moe_layer_freq=(0, 1, 1))

    def sharper(path, a):
        name = jax.tree_util.keystr(path)
        if "q_proj" in name or "k_proj" in name:
            return a * 8
        return a + 2 if "attention_sink_bias" in name else a

    params = jax.tree_util.tree_map_with_path(
        sharper, mm.random_params(cfg, 5, jnp.bfloat16))
    eng = _engine(cfg, params, cache_dtype=jnp.bfloat16, cache_len=256,
                  kv_page_size=16)
    eng.start()
    yield types.SimpleNamespace(cfg=cfg, params=params, eng=eng)
    eng.stop()


@pytest.mark.parametrize("wrong", [
    None, _Wrong("band of 7", {"window": 7}),
    _Wrong("band of 9", {"window": 9}),
    _Wrong("no sink", {}, drop_sink=True),
    _Wrong("rotary on all dimensions", {"partial_rotary_factor": 1.0}),
], ids=lambda w: "right" if w is None else w.name)
def test_the_cells_check_fails_a_wrong_variant(sharp, wrong):
    """benchmark/runners/serve_hybrid_cell.py::check, as the chip runs
    it, at toy size in bf16: engine and reference of one mind pass; let
    the two differ by one equation (the reference is given a band off by
    one, no sink, or rotary on every dimension, over the same weights)
    and the check fails. One engine serves all five: what the check must
    tell apart is the same whichever side holds the fault."""
    cfg, params = sharp.cfg, sharp.params
    if wrong is not None:
        cfg = cfg.replace(**wrong.config)
        if wrong.drop_sink:
            params = jax.tree_util.tree_map_with_path(
                lambda path, a: jnp.full_like(a, -1e9)
                if "attention_sink_bias" in jax.tree_util.keystr(path)
                else a, params)
    sv = types.SimpleNamespace(engine=sharp.eng, cfg=sharp.cfg,
                               params=params, geom=ref.geometry(cfg))
    out = cell.check(sv, {"prompt_tokens": {"min": 32, "max": 200}}, 7)
    assert out["ok"] is (wrong is None), out
    assert out["prompt_tokens"] == [32, 96]
    if wrong is not None:   # by the logits, not by a technicality
        assert out["worst"]["rms_over_std"] > 2 * ref.LOGIT_RMS_TOL, out
