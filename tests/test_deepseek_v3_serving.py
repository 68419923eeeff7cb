"""DeepSeek-V3 through the serving engine (paged latent pages, chunked
prefill, the fused mixed step), its step statistics, what the engine
refuses for it, and the benchmark's check against a wrong variant; tiny
sizes on the CPU."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import deepseek_v3 as ref
from benchmark.runners import serve_latent_cell as cell
from llm_in_practise_tpu.models import deepseek_v3 as dsv3
from llm_in_practise_tpu.serve.engine import InferenceEngine, SamplingParams
from llm_in_practise_tpu.serve.paged_kv import PagedKV, kv_row_bytes

GREEDY = SamplingParams(temperature=0.0, greedy=True, max_tokens=10)


def _engine(cfg, params, **kw):
    opts = dict(max_slots=4, cache_len=128, kv_layout="paged",
                chunked_prefill=16, cache_dtype=jnp.float32)
    opts.update(kw)
    return InferenceEngine(dsv3.DeepSeekV3(cfg), params, **opts)


@pytest.fixture(scope="module")
def served():
    """One engine; a 40-token prompt decodes while a 70-token one chunks
    beside it (fused mixed steps)."""
    cfg = dsv3.deepseek_v3_config(compute_dtype="float32", experts_held=8)
    params = dsv3.random_params(cfg, 3, jnp.float32)
    eng = _engine(cfg, params)
    eng.start()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(4, cfg.vocab_size, n).tolist() for n in (40, 70)]
    eng.step_stats.capture = []
    first = eng.submit(prompts[0], GREEDY)
    head = first.next_item()
    second = eng.submit(prompts[1], GREEDY)
    tokens = [[head] + first.result(), second.result()]
    with eng._lock:     # the last step books its statistics at its end
        captured, eng.step_stats.capture = eng.step_stats.capture, None
        records = eng.steptrace.records(limit=200)
    yield types.SimpleNamespace(cfg=cfg, params=params, eng=eng,
                                prompts=prompts, tokens=tokens,
                                captured=captured, records=records)
    eng.stop()


def _own_generate(cfg, params, prompt, n):
    """The model's own cached greedy generate, and its logits at the
    prompt's last position."""
    model = dsv3.DeepSeekV3(cfg)
    cache = model.init_cache(1, 128, dtype=jnp.float32)
    lg, cache = model.apply({"params": params}, jnp.asarray([prompt]),
                            cache=cache)
    last = np.asarray(lg[0, -1])
    out = [int(jnp.argmax(lg[0, -1]))]
    for _ in range(n - 1):
        lg, cache = model.apply({"params": params}, jnp.asarray([[out[-1]]]),
                                cache=cache)
        out.append(int(jnp.argmax(lg[0, -1])))
    return out, last


def test_engine_tokens_equal_the_models_own_cached_generate(served):
    assert served.eng.mixed_blocks >= 3     # one row chunked, one decoded
    for prompt, tokens in zip(served.prompts, served.tokens):
        want, _ = _own_generate(served.cfg, served.params, prompt, 10)
        assert tokens == want


def test_paged_prefill_logits_equal_the_contiguous_forward(served):
    """The last chunk of each prompt through the page pool: its
    last-position logits, as the program returned them."""
    ended = [c for c in served.captured if c["last_logits"]]
    assert [c["kind"] for c in ended] == ["chunk", "mixed"]
    for c, prompt in zip(ended, served.prompts):
        _, want = _own_generate(served.cfg, served.params, prompt, 1)
        (got,) = c["last_logits"].values()
        assert np.abs(got - want).max() < 1e-5


def test_step_records_and_counters(served):
    st = served.eng.step_stats
    recs = served.records
    for key, total in (("latent_tokens_attended", st.latent_tokens_attended),
                       ("view_tokens", st.latent_view_tokens),
                       ("moe_assignments_held", st.load.assignments),
                       ("moe_experts_touched", st.load.experts_touched),
                       ("moe_max_expert_load", st.load.max_load),
                       ("moe_layer_passes", st.load.layer_passes),
                       ("prefill_qk_pairs", st.prefill_qk_pairs)):
        assert sum(r.get(key, 0) for r in recs) == total > 0
    # 40 + 70 prompt tokens in chunks of 16: the causal pairs, by hand
    assert st.prefill_qk_pairs == 40 * 41 // 2 + 70 * 71 // 2
    assert st.prefill_keys_read == (16 + 32 + 40) + (16 + 32 + 48 + 64 + 70)
    # 2 routed layers; a decode step routes the whole 4-slot plane
    dec = [r for r in recs if r.get("moe_layer_passes") == 2
           and not r.get("chunk_rows")]
    assert dec and all(r["moe_experts_touched"] <= 2 * 8 for r in dec)
    assert served.eng.routing_load is st.load
    assert 0 < st.latent_tokens_attended < st.latent_view_tokens


def test_latent_pool_bytes_read_right(served):
    cfg, eng = served.cfg, served.eng
    row = cfg.n_layer * cfg.latent_dim * 4
    assert kv_row_bytes(dsv3.DeepSeekV3(cfg), jnp.float32) == row
    assert kv_row_bytes(dsv3.DeepSeekV3(cfg), jnp.bfloat16) == row // 2
    assert eng.paged.row_bytes == row
    assert eng.paged.pool_bytes == row * (eng.paged.pool.capacity + 1) * 16
    kv = eng.debug_kv()
    assert kv["page_bytes"] == row * 16
    # a latent has no head axis: every pool leaf replicates under a mesh
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("model",))
    for layer in PagedKV._pool_shardings(eng.paged.kv, mesh):
        assert all(s.spec == jax.sharding.PartitionSpec()
                   for s in layer.values())


def test_engine_refuses_what_the_model_cannot_meet():
    cfg = dsv3.deepseek_v3_config(compute_dtype="float32", experts_held=8)
    params = dsv3.random_params(cfg, 3, jnp.float32)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",))
    for kw, match in (({"mesh": mesh}, "mesh"),
                      ({"kv_layout": "contiguous"}, "contiguous"),
                      ({"speculative_k": 2}, "speculative")):
        with pytest.raises(ValueError, match=match):
            _engine(cfg, params, **kw)


@dataclasses.dataclass(frozen=True)
class _NoMSquared(dsv3.DeepSeekV3Config):
    """The wrong variant: YaRN's tables without the m^2 of the softmax
    scale."""

    @property
    def attention_scale(self):
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5


@pytest.mark.parametrize("wrong", [False, True])
def test_the_cells_check_fails_a_wrong_variant(wrong):
    """benchmark/runners/serve_latent_cell.py::check, as the chip runs
    it, at toy size: the right model passes, the same weights served
    WITHOUT m^2 fail (the query / key projections are scaled up so that
    attention is not flat: under N(0, 0.02) at width 128 every score is
    ~0 and no scale shows)."""
    kw = dict(compute_dtype="bfloat16", experts_held=8, hidden_size=128,
              yarn=(40.0, 32, 32.0, 1.0, 1.0, 1.0))
    right = dsv3.deepseek_v3_config(**kw)
    served_cfg = (_NoMSquared(**dataclasses.asdict(right)) if wrong
                  else right)
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a * 8 if any(
            k in jax.tree_util.keystr(path)
            for k in ("q_b_proj", "kv_a_proj", "kv_b_proj")) else a,
        dsv3.random_params(right, 5, jnp.bfloat16))
    eng = _engine(served_cfg, params, cache_dtype=jnp.bfloat16,
                  cache_len=256)
    eng.start()
    try:
        sv = types.SimpleNamespace(engine=eng, cfg=right, params=params,
                                   geom=ref.geometry(right))
        out = cell.check(sv, {"prompt_tokens": {"min": 32, "max": 200}}, 7)
    finally:
        eng.stop()
    assert out["ok"] is (not wrong), out
    if wrong:   # by the logits, not by a technicality
        assert out["worst"]["rms_over_std"] > 5 * ref.LOGIT_RMS_TOL, out
