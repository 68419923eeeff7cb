"""LFM2-MoE through the serving engine: the conv layers' two-row tails held
by slot beside the attention layer's pages read where they lie, routed
experts counted on the device, chunked prefill beside a decoding row in
fused mixed steps against the plain reference, the tail's discipline (idle
slots, a reused slot, preemption), the counters' names, the byte rates, what
the engine refuses, and the benchmark cell's check against each planted
fault; ONE engine for the file, tiny sizes on the CPU."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_moe as ref
from llm_in_practise_tpu.models import lfm2_moe as lm
from llm_in_practise_tpu.serve import paged_kv
from llm_in_practise_tpu.serve.engine import InferenceEngine, SamplingParams

GREEDY = SamplingParams(temperature=0.0, greedy=True, max_tokens=8)
SLOTS, PROMPTS = 4, (37, 70, 9)


def _engine(cfg, params, **kw):
    opts = dict(max_slots=SLOTS, cache_len=128, kv_layout="paged",
                kv_page_size=8, chunked_prefill=16,
                cache_dtype=jnp.float32, kv_pool_tokens=288)
    opts.update(kw)
    return InferenceEngine(lm.Lfm2Moe(cfg), params, **opts)


def _tails(eng, slot):
    return [np.asarray(layer["conv"][slot]) for layer in eng.paged.kv
            if "conv" in layer]


@pytest.fixture(scope="module")
def model():
    cfg = lm.lfm2_moe_config(compute_dtype="float32")
    return cfg, lm.random_params(cfg, 3, jnp.float32, std=0.1)


@pytest.fixture(scope="module")
def served(model):
    """A 37-token prompt decodes while a 70-token one chunks beside it in
    fused mixed steps (5 chunks, the last padded: 6 of 16) and a 9-token
    one (shorter than a chunk) is admitted through the chunk program too.
    One slot stays idle; every slot's tails are read before."""
    cfg, params = model
    eng = _engine(cfg, params)
    # what a last tenant might have left in every slot
    eng.paged.kv = jax.tree.map(lambda a: jnp.full_like(a, 2.0),
                                eng.paged.kv)
    before = [_tails(eng, s) for s in range(SLOTS)]
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


def _ended(served, h):
    """``(the program that ended h's prompt, h's slot)``."""
    for c in served.captured:
        slot = next((s for s, u in c["uids"].items() if u == h.uid), None)
        if slot is not None and slot in c["last_logits"]:
            return c, slot
    raise AssertionError("no program ended the prompt")


@pytest.mark.parametrize("which", range(len(PROMPTS)))
def test_logits_tokens_tails_and_routes_are_the_references(served, which):
    """Last-position logits as the chunk / mixed program returned them, 8
    greedy tokens teacher-forced through the float32 reference, the tails
    the slot holds at the end and the routed sets the programs chose at the
    judged positions: over whatever the slots held before."""
    from benchmark.runners import serve_conv_moe_cell as cell

    reference = ref.Reference(ref.geometry(served.cfg))
    prompt, tokens, h = (served.prompts[which], served.tokens[which],
                         served.handles[which])
    assert len(tokens) == 8
    stores = {}
    with jax.default_matmul_precision("highest"):
        want = reference.logits(served.params, prompt + tokens[:-1], last=8,
                                stores=stores)
    assert ref.token_margin(want, tokens) < 1e-4
    got, slot = _ended(served, h)
    assert ref.logit_error(got["last_logits"][slot], want[0])[
        "max_over_std"] < 1e-4
    assert got["kind"] == ("mixed" if len(prompt) == 70 else "chunk")
    for mine, theirs in zip(_tails(served.eng, slot), stores["tail"]):
        assert ref.store_error(mine, theirs) < 1e-5
    # (a decode program issued ahead of the last token may follow)
    routes = cell.routes_of(served.captured, h.uid)[:, :8]
    assert routes.shape == (4, 8, 2)
    for mine, (ids, _) in zip(routes, stores["route"]):
        assert (np.sort(mine, -1) == np.sort(ids, -1)).all()


def test_idle_slots_keep_their_tails_and_rows_are_counted(served):
    """Every decode and mixed step ran over the whole slot plane: the slot
    no request used holds what it held, bit for bit; the counters carry
    the state's own name."""
    used = {s for c in served.captured for s in c["uids"]}
    idle, = set(range(SLOTS)) - used
    for before, after in zip(served.before[idle], _tails(served.eng, idle)):
        np.testing.assert_array_equal(before, after)
    st = served.eng.step_stats
    assert 0 < st.conv_state_rows_advanced < st.conv_state_rows_held
    assert st.conv_state_bytes == served.eng.paged.recurrent_state_bytes
    assert not hasattr(st, "ssm_state_rows_advanced")


def test_the_counters_family_follows_from_the_template(served):
    """No ring and no latent row: the attention layer's pages book
    ``global_*`` (not ``latent_*``), the pages read in place
    ``global_pages_read``; the routed layers count every expert."""
    eng, st = served.eng, served.eng.step_stats
    assert (st.attended_key, st.view_key, st.pairs_key, st.pages_key) == (
        "global_tokens_attended", "global_view_tokens",
        "prefill_global_pairs", "global_pages_read")
    assert st.state == "conv" and not st.shared and not st.ring_rows
    assert st.prefill_chunk_tokens == sum(PROMPTS)
    assert st.load.n_experts == 8 and eng.routing_load is st.load
    dec = [r for r in served.records if "conv_state_rows_advanced" in r]
    for r in dec:
        live = r["conv_state_rows_advanced"]
        assert r["conv_state_rows_held"] == SLOTS
        # one attention layer reads the pages: a slot's 16, one block
        assert r["global_pages_read"] == live * 16
        assert r["global_view_tokens"] == live * 16 * eng.paged.page_size
        # 4 routed layers, the whole plane's rows x top-2
        assert r["moe_layer_passes"] % 4 == 0
    assert any(r["moe_assignments_held"] == 4 * SLOTS * 2 for r in dec)


STEP_STATS_NAMES = {
    "deepseek_v3": ("latent_tokens_attended", "latent_view_tokens",
                    "prefill_qk_pairs", None),
    "mimo_v2": ("global_tokens_attended", "global_view_tokens",
                "prefill_global_pairs", None),
    "afmoe": ("global_tokens_attended", "global_view_tokens",
              "prefill_global_pairs", None),
    "phi4flash": ("global_tokens_attended", "global_view_tokens",
                  "prefill_global_pairs", "ssm"),
}


@pytest.mark.parametrize("family", STEP_STATS_NAMES)
def test_the_older_models_counter_names_are_unchanged(family):
    """The four models that had step statistics keep their counters'
    names, now read off the cache template."""
    import importlib

    from llm_in_practise_tpu.serve.step_stats import StepStats

    mod = importlib.import_module(f"llm_in_practise_tpu.models.{family}")
    cfg = getattr(mod, f"{family}_config")(compute_dtype="float32")
    core = {"deepseek_v3": "DeepSeekV3", "mimo_v2": "MiMoV2",
            "afmoe": "Afmoe", "phi4flash": "Phi4Flash"}[family]
    model = getattr(mod, core)(cfg)
    paged = paged_kv.PagedKV(model, max_slots=2, cache_len=32, page_size=8,
                             pool_tokens=64, dtype=jnp.float32)
    try:
        eng = types.SimpleNamespace(
            paged=paged, mesh=None, speculative_k=None, draft_model=None,
            adapter_registry=None, kv_pool=None, session_store=None,
            role="both", handoff=None, prefix_cache=None, max_slots=2)
        st = StepStats(eng, model)
    finally:
        paged.close()
    attended, view, pairs, state = STEP_STATS_NAMES[family]
    assert (st.attended_key, st.view_key, st.pairs_key, st.state) == (
        attended, view, pairs, state)
    assert bool(st.ring_rows) == (family != "deepseek_v3")
    assert bool(st.shared) == (family == "phi4flash")
    if family == "phi4flash":
        assert (st.advanced_key, st.held_key, st.pages_key) == (
            "ssm_state_rows_advanced", "ssm_state_rows_held",
            "shared_kv_pages_read")
        assert st.ssm_scan_tokens == 0


def test_cache_kinds_stores_bytes_and_metrics(served, model):
    from llm_in_practise_tpu.obs.hbm import get_ledger
    from llm_in_practise_tpu.serve.api import OpenAIServer

    cfg, _ = model
    _, by_slot, recurrent = paged_kv.cache_kinds(lm.Lfm2Moe(cfg),
                                                 jnp.float32, 2)
    # a cache of 2 positions is as long as the tail: still a state
    conv = [True, True, False, True, True, True]
    assert by_slot == conv and recurrent == conv
    eng, pg = served.eng, served.eng.paged
    assert pg.by_slot == conv and pg.recurrent == conv
    assert pg.in_place == [not c for c in conv] and eng._reads_pages
    assert pg.ring_rows == 0 and pg.form == "pages"
    assert pg.recurrent_state_bytes == SLOTS * 5 * 2 * 64 * 4
    assert pg.slot_state_bytes == pg.recurrent_state_bytes
    assert paged_kv.kv_row_bytes(lm.Lfm2Moe(cfg), jnp.float32) == 2 * 32 * 4
    accounts = get_ledger().snapshot()["accounts"]
    assert accounts["kv.recurrent_state"]["bytes"] >= pg.recurrent_state_bytes
    snap = eng.debug_kv()["slot_state"]
    assert (snap["layers"], snap["recurrent_layers"],
            snap["paged_layers"]) == (5, 5, 1)
    assert snap["recurrent_ledger_account"] == "kv.recurrent_state"
    text = OpenAIServer(eng, tokenizer=None,
                        model_name="m").registry.render()
    for name in ("llm_kv_recurrent_state_bytes",
                 "llm_conv_state_rows_advanced_total",
                 "llm_conv_state_rows_held_total",
                 "llm_global_tokens_attended_total",
                 "llm_global_pages_read_total",
                 "llm_moe_layer_passes_total",
                 "llm_moe_experts_touched_total"):
        assert f"\n{name}" in text, name
    assert "llm_ssm_scan_tokens_total" not in text
    assert "llm_latent_tokens_attended_total" not in text


def test_a_reused_slot_starts_from_a_zero_tail(served):
    """The same prompt again, in slots whose last tenants left their
    tails: the same tokens."""
    with jax.default_matmul_precision("highest"):
        again = [served.eng.submit(p, GREEDY) for p in served.prompts]
        tokens = [r.result() for r in again]
    assert tokens == served.tokens


def test_preemption_recomputes_the_prompt(served):
    """Four rows that outgrow the pool together (4 x 96 tokens against 288):
    a later one is preempted, its prompt recomputed from position 0 (a zero
    tail), and every answer is what the same request gives alone."""
    eng, cfg = served.eng, served.cfg
    rng = np.random.default_rng(5)
    prompts = [rng.integers(4, cfg.vocab_size, 40).tolist() for _ in range(4)]
    many = SamplingParams(temperature=0.0, greedy=True, max_tokens=56)
    with jax.default_matmul_precision("highest"):
        alone = [eng.submit(p, many).result() for p in prompts]
        before = eng.preemptions
        together = [eng.submit(p, many) for p in prompts]
        together = [r.result() for r in together]
    assert eng.preemptions > before
    assert together == alone


REFUSALS = {
    "contiguous": (dict(kv_layout="contiguous"), "kv_layout='contiguous'"),
    "prefix cache": (dict(prefix_cache=True), "prefix cache"),
    "speculation": (dict(speculative_k=2), "speculative decoding"),
    "adapters": (dict(adapter_registry=object()), "multi-LoRA"),
    "tiered KV": (dict(kv_pool=object()), "tiered KV"),
    "sessions": (dict(session_store=object()), "session store"),
    "handoff": (dict(role="prefill"), "disaggregated"),
    "mesh": (dict(mesh=object()), "device mesh"),
}


@pytest.mark.parametrize("what", REFUSALS)
def test_the_engine_refuses_by_name(served, what):
    """What a model with routed layers and a state held by slot cannot
    meet, each by its name (``StepStats.check_engine`` on the engine's own
    attributes, one changed)."""
    from llm_in_practise_tpu.serve.step_stats import StepStats

    changed, name = REFUSALS[what]
    eng = served.eng
    fields = {k: getattr(eng, k) for k in (
        "paged", "mesh", "speculative_k", "draft_model", "adapter_registry",
        "kv_pool", "session_store", "role", "handoff", "prefix_cache")}
    StepStats.check_engine(types.SimpleNamespace(**fields))     # as built
    if "kv_layout" in changed:
        changed = {"paged": None}
    with pytest.raises(ValueError, match=name):
        StepStats.check_engine(types.SimpleNamespace(**{**fields, **changed}),
                               "a model with layers held by slot")


@pytest.fixture(scope="module")
def observed(served):
    """The benchmark cell's own probes on the engine above
    (``serve_conv_moe_cell.probe``): a short probe decodes while a long one
    chunks beside it, two fillers hold the other slots and decode all
    through."""
    from benchmark.runners import serve_conv_moe_cell as cell

    sv = types.SimpleNamespace(engine=served.eng, cfg=served.cfg,
                               params=served.params,
                               geom=ref.geometry(served.cfg))
    with jax.default_matmul_precision("highest"):
        seen = cell.probe(sv, (37, 70), 11, fillers=(9, 40))
    return cell, sv, seen


@pytest.mark.parametrize("fault", [None, "routes_free", "conv_break",
                                   "pad_advance", "bias_in_weights",
                                   "kv_dtype", "slots_crossed"])
def test_the_cells_check_passes_sound_and_fails_each_fault(observed, fault):
    """``serve_conv_moe_cell.judge`` on what the probes observed: every slot
    was live, every store lies on the reference's (with the program's routed
    sets forced at the judged positions and, ``routes_free``, without: a
    float32 toy flips none), the rows reach past the prompt, and the same
    observation judged against a reference with ONE planted fault, with its
    K/V rows in e4m3, or with the probes' tails crossed breaks a limit (the
    chip's limits: a float32 toy lies far inside them). The selection bias
    added to the weights is judged at a hundredth of the chip's limits: the
    toy's ONE attention layer lies before every routed layer, so the median
    row that catches it on the chip does not see it here, and the tails it
    moves by 1-3% are two positions."""
    cell, sv, seen = observed
    assert seen["slots_live"] == SLOTS and "filler" in seen
    # the prompt's rows and its tokens' (the last token's program finds the
    # pages gone back)
    for p in seen["probes"]:
        assert len(p["pages"][0][0]) >= len(p["prompt"]) + 14
    geom = None if fault in (None, "routes_free", "slots_crossed") else dict(
        sv.geom, **{fault: dict(cell.faults(sv), conv_break=16)[fault]})
    sound = fault in (None, "routes_free")
    tight = 0.01 if sound or fault == "bias_in_weights" else 1.0
    with jax.default_matmul_precision("highest"):
        out = cell.judge(sv, seen, geom, slack=tight,
                         forced=fault != "routes_free",
                         crossed=fault == "slots_crossed")
    if sound:
        assert out["ok"], out
        assert out["long_probe_ended_in_a_mixed_step"]
        assert out["routed_sets_flipped"] == 0 < out["routed_sets_judged"]
        assert max(out["worst"]["tail_error"]) < 1e-4
        assert max(out["worst"]["page_row_worst"]) < 1e-4
    else:
        assert not out["ok"] and out["limits_failed"], out
