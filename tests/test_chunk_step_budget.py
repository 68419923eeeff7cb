"""A step's chunks hold a bounded number of prompt tokens (PR 30).

The paged chunk and fused mixed programs pay one trip a chunking row, so
``engine.CHUNK_TOKENS_PER_STEP`` bounds the rows one step advances; a
burst that does not fit gives them to the prompts with the fewest chunks
left (ties: the oldest), so the prompts nearest their first token get it
first and no step stalls its decode rows for the whole burst. The
contiguous layout computes the whole slot plane whatever chunks, so there
every mid-prefill row advances, as before. These tests pin the rows a step
takes, the order the prompts finish in, and that the tokens are those of
the unbounded schedule. Beside them: ``prefill_budget`` chunks a step are
a mid-prefill prompt's whatever decodes beside it.

CPU, small vocabulary, seconds."""

import jax
import jax.numpy as jnp
import pytest

from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
from llm_in_practise_tpu.serve import engine as engine_mod
from llm_in_practise_tpu.serve.engine import InferenceEngine, SamplingParams

CHUNK = 8
PROMPTS = [[(i * k + 3) % 64 for i in range(n)]       # 5, 4, 5, 3, 4 chunks
           for k, n in ((7, 40), (5, 30), (11, 36), (3, 24), (13, 32))]
DECODER = [3, 1, 4, 1, 5, 9]
# every prompt a chunk a step: they end by their length alone
LOCKSTEP_ROWS = [5, 5, 5, 4, 2]
LOCKSTEP = [(3, 3), (1, 4), (4, 4), (0, 5), (2, 5)]


@pytest.fixture(scope="module")
def model_params():
    cfg = GPTConfig(vocab_size=64, seq_len=192, n_layer=2, n_head=2,
                    embed_dim=32, dropout=0.0, pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    return model, params


def burst(model_params, monkeypatch, layout, budget, decoder):
    """Five prompts arrive together (beside one decoding request, so the
    steps are fused mixed steps, or alone and for one token each: chunk
    dispatches). Returns the chunk rows of every step that chunked, the
    prompts in the order of their first tokens with the step that gave
    it (counted from the burst), and every request's tokens."""
    monkeypatch.setattr(engine_mod, "CHUNK_TOKENS_PER_STEP", budget)
    model, params = model_params
    eng = InferenceEngine(model, params, max_slots=6, cache_len=192,
                          cache_dtype=jnp.float32, kv_layout=layout,
                          chunked_prefill=CHUNK)
    greedy = SamplingParams(greedy=True, max_tokens=6 if decoder else 1)
    reqs = []
    if decoder:
        reqs.append(eng.submit(DECODER, SamplingParams(greedy=True,
                                                       max_tokens=40)))
        eng.step()
    first = len(reqs)
    reqs += [eng.submit(p, greedy) for p in PROMPTS]
    # a paged program is read in the step AFTER the one that issued it
    # (the engine runs one dispatch ahead, PR 40): the step that gave a
    # first token is the one before the step that saw it
    order, step, busy = {}, 0 if layout == "contiguous" else -1, True
    while busy:
        busy = eng.step()       # (the last call reads the last program)
        step += 1
        for i, r in enumerate(reqs[first:]):
            if r.first_token_time is not None:
                order.setdefault(i, step)
    rows = [r["chunk_rows"] for r in eng.steptrace.records()
            if r["chunk_rows"]]
    assert (eng.mixed_blocks > 0) == decoder
    return rows, list(order.items()), [r.result() for r in reqs]


@pytest.mark.parametrize("decoder", [False, True],
                         ids=["chunk_dispatch", "fused_mixed_step"])
def test_a_paged_step_advances_the_prompts_nearest_their_end(
        model_params, monkeypatch, decoder):
    rows, order, tokens = burst(model_params, monkeypatch, "paged",
                                2 * CHUNK, decoder)
    # fewest chunks left first, the older of two equals: 3 (3 chunks) and
    # 1 (4) until 3 is through, 4 (4) takes its place, then 0 before 2
    # (5 each), which chunks alone for its last three
    assert rows == [2] * 9 + [1] * 3
    assert order == [(3, 3), (1, 4), (4, 7), (0, 9), (2, 12)]
    free_rows, free_order, free_tokens = burst(
        model_params, monkeypatch, "paged", 10 ** 6, decoder)
    # a budget that holds them all: the five advance in lockstep
    assert free_rows == LOCKSTEP_ROWS and free_order == LOCKSTEP
    assert tokens == free_tokens


def test_a_budget_under_one_chunk_still_advances_a_prompt(
        model_params, monkeypatch):
    rows, order, _ = burst(model_params, monkeypatch, "paged", 1, False)
    assert rows == [1] * 21
    assert order == [(3, 3), (1, 7), (4, 11), (0, 16), (2, 21)]


@pytest.mark.parametrize("decoder", [False, True],
                         ids=["chunk_dispatch", "fused_mixed_step"])
def test_the_contiguous_layout_advances_every_prompt(
        model_params, monkeypatch, decoder):
    rows, order, tokens = burst(model_params, monkeypatch, "contiguous",
                                2 * CHUNK, decoder)
    assert rows == LOCKSTEP_ROWS and order == LOCKSTEP
    _, _, paged = burst(model_params, monkeypatch, "paged", 2 * CHUNK,
                        decoder)
    assert tokens == paged


@pytest.mark.parametrize("budget, bound", [(1, 6), (3, 2)],
                         ids=["one_chunk_a_step", "three_chunks_a_step"])
def test_decode_load_cannot_starve_a_prefill(model_params, budget, bound):
    """A mid-prefill prompt advances ``prefill_budget`` chunks EVERY engine
    step while another slot decodes, so its TTFT is bounded by
    ceil(chunks / budget) engine steps (admission shares the first)."""
    model, params = model_params
    eng = InferenceEngine(model, params, max_slots=2, cache_len=128,
                          cache_dtype=jnp.float32, chunked_prefill=CHUNK,
                          prefill_budget=budget)
    eng.submit(DECODER, SamplingParams(greedy=True, max_tokens=64))
    eng.step()  # admit + activate the decode-load request
    b = eng.submit(list(range(1, 41)),       # 40 tokens -> 5 chunks of 8
                   SamplingParams(greedy=True, max_tokens=4))
    steps = 0
    while b.first_token_time is None and steps < 12:
        eng.step()
        steps += 1
    assert b.first_token_time is not None and steps <= bound
