"""A prompt's prefill ends in its first token (PR 32).

Every prefill program takes a row's final-norm hidden state at its last
real position BEFORE the output head (``models/layers.py``: the head runs
on one position a row, not on the chunk's width), and the paged chunk and
fused mixed programs sample the first token of every row whose prompt
they finish. These tests pin:

- the one-position head's logits equal the full head's row at
  ``lens - 1`` (same ``argmax``, float32 rounding of one sum apart), for
  every in-tree model family, tied and untied heads, bf16 and W8A16;
- greedy tokens of every serving path equal the tokens the tree before
  this change produced (recorded there by the same scenarios);
- a request whose logits the host reads first (a grammar's start state) or
  that has nothing to sample (a preemption resume) keeps the host path,
  and the counters say which path a prompt took;
- sampled first tokens follow the filtered softmax on both paths;
- a chunked prompt's finalisation dispatches nothing on the program path.

CPU, tiny models, a minute."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_in_practise_tpu.models import layers
from llm_in_practise_tpu.models.deepseek import DeepSeekLike, deepseeklike_config
from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
from llm_in_practise_tpu.models.qwen3 import Qwen3, Qwen3Config
from llm_in_practise_tpu.models.sdar_moe import SDARMoE, sdar_moe_config
from llm_in_practise_tpu.peft.lora import LoRAConfig, init_lora
from llm_in_practise_tpu.peft.qlora import quantize_base_lowmem
from llm_in_practise_tpu.quant import int8
from llm_in_practise_tpu.serve import constrain
from llm_in_practise_tpu.serve.engine import InferenceEngine, SamplingParams
from llm_in_practise_tpu.serve.multi_lora import AdapterRegistry
from llm_in_practise_tpu.serve.quantized import QuantizedModel

# ------------------------------------------------- (a) the one-position head

WIDTH = 32   # positions of the probe; lens 1, mid, full


def _qwen3(tie, weights):
    cfg = Qwen3Config(vocab_size=512, hidden_size=64, intermediate_size=128,
                      n_layer=2, n_head=4, n_kv_head=2, head_dim=16,
                      max_seq_len=64, tie_word_embeddings=tie,
                      compute_dtype="bfloat16")
    model = Qwen3(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    if weights == "bf16":
        return model, params
    packed = quantize_base_lowmem(params, fmt="int8")
    if not tie:
        # the packers leave the head alone; pack it here so the head-only
        # half runs through the same interceptor as the trunk's matmuls
        packed["lm_head"]["kernel"] = int8.quantize(
            params["lm_head"]["kernel"])
    return QuantizedModel(model), packed


def _gpt(tie):
    cfg = GPTConfig(vocab_size=96, seq_len=64, n_layer=2, n_head=2,
                    embed_dim=32, dropout=0.0, pos_embedding="rope",
                    tie_weights=tie)
    model = GPT(cfg)
    return model, model.init(jax.random.PRNGKey(0),
                             jnp.ones((1, 8), jnp.int32))["params"]


def _deepseek():
    model = DeepSeekLike(deepseeklike_config(
        96, seq_len=64, n_layer=2, n_head=4, embed_dim=64, n_experts=4,
        top_k=2, n_shared_experts=1, dropout=0.0, first_dense_layers=1))
    return model, model.init(jax.random.PRNGKey(0),
                             jnp.ones((1, 8), jnp.int32))["params"]


def _sdar():
    model = SDARMoE(sdar_moe_config())
    return model, model.init_params(jax.random.PRNGKey(0))


FAMILIES = {
    "qwen3-tied-bf16": lambda: _qwen3(True, "bf16"),
    "qwen3-untied-bf16": lambda: _qwen3(False, "bf16"),
    "qwen3-tied-w8a16": lambda: _qwen3(True, "w8a16"),
    "qwen3-untied-w8a16": lambda: _qwen3(False, "w8a16"),
    "gpt-tied": lambda: _gpt(True),
    "gpt-untied": lambda: _gpt(False),
    "deepseek": _deepseek,
    "sdar_moe": _sdar,
}


@pytest.fixture(scope="module")
def head_rows():
    """``family -> (full head's logits (3, WIDTH, vocab), one-position
    logits at each of LENS)``, one pair of compiles a family."""
    made = {}

    def get(family):
        if family not in made:
            model, params = FAMILIES[family]()
            vocab = model.config.vocab_size
            ids = jax.random.randint(jax.random.PRNGKey(1), (3, WIDTH), 0,
                                     vocab - 1)
            cache = model.init_cache(3, 64, dtype=jnp.float32)
            full, _ = jax.jit(lambda p: model.apply(
                {"params": p}, ids, deterministic=True, cache=cache))(params)
            one = jax.jit(lambda p, lens: layers.last_position_logits(
                model, p, ids, lens, cache)[0])
            made[family] = np.asarray(full), {
                n: np.asarray(one(params, jnp.full((3,), n, jnp.int32)))
                for n in LENS}
        return made[family]

    return get


LENS = (1, WIDTH // 2 + 1, WIDTH)


@pytest.mark.parametrize("n", LENS)
@pytest.mark.parametrize("family", FAMILIES)
def test_one_position_head_is_the_full_heads_row(head_rows, family, n):
    full, one = head_rows(family)
    want, got = full[:, n - 1, :], one[n]
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.array_equal(got.argmax(-1), want.argmax(-1))
    # the same products summed; the CPU backend's (1, H) x (H, V) kernel
    # may sum them in another order than its (L, H) x (H, V) one, so a
    # float32 logit can differ in its last bits (seen: 2e-8 at 0.1)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-6 * np.abs(want).max())


def test_a_model_that_cannot_split_is_refused_at_build():
    """No silent fallback to the wide head: the engine's build traces
    both halves once, abstractly."""
    model, params = _gpt(True)

    class Whole:
        config = model.config
        init_cache = staticmethod(model.init_cache)

        @staticmethod
        def apply(variables, idx, *, deterministic=True, cache=None):
            return model.apply(variables, idx, deterministic=deterministic,
                               cache=cache)

    with pytest.raises(ValueError, match="return_hidden"):
        InferenceEngine(Whole(), params, max_slots=2, cache_len=64)


# ------------------------------------------- (b) goldens of every serving path
# Recorded on the tree BEFORE this change (9f3cd9d) by these scenarios; the
# two layouts gave the same tokens there.

LONG3, LONG4 = 20, 29        # 3 and 4 chunks of 8, the last one partial
_A = [54, 4, 13, 15, 25, 25, 25, 24, 13, 15, 17, 4, 13, 15, 25, 24, 13, 15,
      34, 4, 13, 15, 34, 4]
_B = [2, 9, 54, 31, 56, 41, 12, 54, 31, 56, 41, 12, 54, 4, 13, 15, 25, 24,
      13, 15, 25, 24, 13, 15]
GOLDEN = {
    ("base", "oneshot"): [19, 39, 43, 11, 32, 19, 39, 43],
    ("base", "chunk3"): [4, 13, 15, 25, 24, 13, 15, 34],
    ("base", "chunk4"): [24, 13, 15, 34, 4, 13, 15, 34],
    ("base", "fused"): [_A, _B, [24, 13, 15, 34, 4, 13, 15, 34]],
    ("base", "two_dispatch"): [_A, _B, [24, 13, 15, 34, 4, 13, 15, 34]],
    ("lora", "oneshot"): [19, 39, 33, 25, 25, 25, 25, 24],
    ("lora", "chunk3"): [15, 51, 42, 2, 33, 25, 24, 41],
    ("lora", "chunk4"): [50, 19, 39, 17, 2, 33, 25, 24],
    ("lora", "fused"): [_A, _B, [50, 19, 39, 17, 2, 33, 25, 24]],
    ("lora", "two_dispatch"): [_A, _B, [50, 19, 39, 17, 2, 33, 25, 24]],
}
SHORT = ([3, 1, 4, 1, 5, 9], [2, 7, 1, 8, 2, 8])


def prompt(n):
    return [(i * 7 + 3) % 64 for i in range(n)]


@pytest.fixture(scope="module")
def world():
    cfg = GPTConfig(vocab_size=64, seq_len=192, n_layer=2, n_head=4,
                    embed_dim=32, dropout=0.0, pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    c1 = LoRAConfig(r=2, alpha=4.0, target_patterns=("attn/q_proj", "mlp"))
    key = jax.random.PRNGKey(2)
    t1 = {}
    for k, v in init_lora(params, c1, jax.random.PRNGKey(1)).items():
        key, sub = jax.random.split(key)
        t1[k] = {"a": v["a"],
                 "b": jax.random.normal(sub, v["b"].shape) * 0.3}
    return model, params, t1, c1


def engine_of(world, layout="paged", lora=False, **kw):
    model, params, t1, c1 = world
    if lora:
        kw["adapter_registry"] = AdapterRegistry(params)
        kw["adapter_registry"].register_tree("t1", t1, c1)
    return InferenceEngine(model, params, max_slots=4, cache_len=192,
                           cache_dtype=jnp.float32, kv_layout=layout, **kw)


def drain(eng):
    while eng.step():
        pass


def alone(eng, n, lora):
    r = eng.submit(prompt(n), SamplingParams(greedy=True, max_tokens=8),
                   **({"adapter": "t1"} if lora else {}))
    drain(eng)
    return r.result()


def mixed_load(eng, lora):
    """Two short prompts decode blocks of 4 while a long one chunks."""
    sp = SamplingParams(greedy=True, max_tokens=24)
    h = [eng.submit(p, sp) for p in SHORT]
    eng.step()
    hl = eng.submit(prompt(LONG4), SamplingParams(greedy=True, max_tokens=8),
                    **({"adapter": "t1"} if lora else {}))
    drain(eng)
    return [r.result() for r in (*h, hl)]


CHUNKED = dict(chunked_prefill=8)
SCENARIOS = {
    "oneshot": lambda w, lay, lo: alone(engine_of(w, lay, lo), 6, lo),
    "chunk3": lambda w, lay, lo: alone(
        engine_of(w, lay, lo, **CHUNKED), LONG3, lo),
    "chunk4": lambda w, lay, lo: alone(
        engine_of(w, lay, lo, **CHUNKED), LONG4, lo),
    "fused": lambda w, lay, lo: mixed_load(
        engine_of(w, lay, lo, **CHUNKED), lo),
    "two_dispatch": lambda w, lay, lo: mixed_load(
        engine_of(w, lay, lo, mixed_step=False, **CHUNKED), lo),
}


@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("adapter", ["base", "lora"])
@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_greedy_tokens_equal_the_parents(world, layout, adapter, scenario):
    got = SCENARIOS[scenario](world, layout, adapter == "lora")
    assert got == GOLDEN[adapter, scenario]


# ------------------------------------------------ (c) who samples, and counters


def counts(eng):
    recs = eng.steptrace.records()
    booked = {p: sum(r[f"first_tokens_{p}"] for r in recs)
              for p in ("program", "host")}
    assert booked == eng.first_tokens       # the records say what /metrics says
    return booked["program"], booked["host"]


@pytest.mark.parametrize("mode", ["fused", "chunk_only"])
def test_unconstrained_chunked_prompt_takes_the_programs_token(world, mode):
    eng = engine_of(world, **CHUNKED)
    if mode == "fused":
        mixed_load(eng, False)
        assert eng.mixed_blocks > 0
    else:
        alone(eng, LONG4, False)
    assert counts(eng) == (1, 0)


def test_contiguous_layout_keeps_host_sampling(world):
    eng = engine_of(world, "contiguous", **CHUNKED)
    mixed_load(eng, False)
    assert counts(eng) == (0, 1)


def test_one_shot_prompts_are_not_counted(world):
    eng = engine_of(world)
    alone(eng, 6, False)
    assert counts(eng) == (0, 0)


def test_paged_suffix_after_a_prefix_hit_takes_the_programs_token(world):
    """A follow-up that shares whole pages prefills only its suffix, through
    a one-row call of the chunk program, which ends the prompt."""
    eng = engine_of(world, prefix_cache=True)
    sp = SamplingParams(greedy=True, max_tokens=4)
    base = prompt(40)
    eng.generate(base, sp)
    want = engine_of(world).generate(base + [5, 6, 7], sp)
    assert eng.generate(base + [5, 6, 7], sp) == want
    assert counts(eng) == (1, 0)


VOCAB_STRS = [chr(i) for i in range(64)]


def test_constrained_chunked_prompt_obeys_its_start_state_on_the_host(world):
    auto = constrain.TokenAutomaton(
        constrain.compile_regex("7[0-9]+"), VOCAB_STRS, eos_id=None)
    eng = engine_of(world, **CHUNKED)
    free = eng.generate(prompt(LONG4), SamplingParams(greedy=True,
                                                      max_tokens=4))
    assert free[0] != ord("7")            # the grammar really steers
    out = eng.generate(prompt(LONG4), SamplingParams(
        greedy=True, max_tokens=4, constraint=auto))
    assert out[0] == ord("7")
    assert all(chr(t).isdigit() for t in out)
    assert counts(eng) == (1, 1)          # the free run, then the grammar's


def test_resumed_chunked_prompt_emits_nothing_twice(world):
    """A pool for ~2 of 3 requests preempts; a resumed request re-prefills
    its whole history in chunks, samples nothing and books the host path."""
    sp = SamplingParams(greedy=True, max_tokens=40)
    prompts = [[(j * 3 + i) % 64 for i in range(20)] for j in range(3)]
    tight = engine_of(world, kv_pool_tokens=96, **CHUNKED)
    rs = [tight.submit(p, sp) for p in prompts]
    drain(tight)
    assert tight.preemptions > 0
    free = engine_of(world, **CHUNKED)
    for p, r in zip(prompts, rs):
        assert r.result() == free.generate(p, sp)
    program, host = counts(tight)
    assert (program, host) == (3, tight.preemptions)


# ------------------------------------------------ (d) sampled first tokens


def filtered_softmax(logits, temperature, top_k, top_p):
    """The distribution ``infer/sampling.py::_filtered`` draws from."""
    scaled = logits.astype(np.float64) / temperature
    order = np.argsort(-scaled, kind="stable")
    keep = order[:top_k]
    p = np.exp(scaled[keep] - scaled[keep].max())
    p /= p.sum()
    cum = np.cumsum(p)
    keep, p = keep[cum - p <= top_p], p[cum - p <= top_p]
    out = np.zeros_like(scaled)
    out[keep] = p / p.sum()
    return out


@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_sampled_first_tokens_follow_the_filtered_softmax(world, layout):
    """Qwen3's published sampling, 480 chunked prompts of one engine key
    stream, four to a program: the program path (paged) and the host's
    jitted sampler (contiguous) draw from the same distribution."""
    model, params, *_ = world
    sp = SamplingParams(temperature=0.6, top_k=20, top_p=0.95, max_tokens=1)
    eng = engine_of(world, layout, rng=jax.random.PRNGKey(7), **CHUNKED)
    reqs = [eng.submit(prompt(LONG3), sp) for _ in range(480)]
    drain(eng)
    draws = np.array([r.result()[0] for r in reqs])
    # (the step records' ring is shorter than this run)
    assert eng.first_tokens == dict(
        zip(("program", "host") if layout == "paged"
            else ("host", "program"), (480, 0)))
    logits = np.asarray(model.apply(
        {"params": params}, jnp.asarray([prompt(LONG3)]))[0, -1], np.float32)
    want = filtered_softmax(logits, 0.6, 20, 0.95)
    got = np.bincount(draws, minlength=64) / len(draws)
    assert not got[want == 0].any()               # nothing outside the filter
    assert len(np.unique(draws)) > 3              # and it really samples
    assert 0.5 * np.abs(got - want).sum() < 0.1   # total variation


# ------------------------------------------------ (e) finalisation is host work


@pytest.mark.parametrize("layout,programs", [("paged", 0), ("contiguous", 1)])
def test_finalisation_dispatches_nothing_on_the_program_path(
        world, layout, programs):
    eng = engine_of(world, layout, **CHUNKED)
    mixed_load(eng, False)                 # compile everything first
    spent = []
    # where a finished prompt is finalised: the read of the paged program
    # that ended it (``_retire``: its first token is the program's), the
    # host fallback of the contiguous layout (``_finalize_prefills``)
    name = "_retire" if layout == "paged" else "_finalize_prefills"
    inner = getattr(eng, name)

    def metered(*flight):
        before = (eng.dispatch_meter.total, eng.compile_meter.compile_events)
        finishing = (len(flight[0].finished) if flight else sum(
            st["done"] >= st["plen"] for st in eng.slot_prefill.values()))
        inner(*flight)
        spent.append((finishing,
                      eng.dispatch_meter.total - before[0],
                      eng.compile_meter.compile_events - before[1]))

    setattr(eng, name, metered)
    mixed_load(eng, False)
    assert sum(f for f, _, _ in spent) == 1
    for finishing, dispatches, compiles in spent:
        # the host fallback is ONE jitted sampler call a finished prompt
        assert dispatches == programs * finishing
        assert compiles == 0
