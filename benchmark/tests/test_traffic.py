"""The traffic generator: determinism, one multiset for every seed,
clipping, and the origin requests are timed from; the reference's reading
of packed weights."""


import numpy as np
import pytest

from benchmark import client, serving, spec, stats, traffic

CHAT = spec.load_json("workloads", "qwen3-8b.chat-steady.json")
DOCS = spec.load_json("workloads", "qwen3-8b.prefill-heavy.json")


def test_the_plan_is_the_cells_and_the_seed_picks_the_words():
    assert traffic.plan(CHAT, 45, 11) == traffic.plan(CHAT, 45, 11)
    assert traffic.plan(DOCS, 45, 11) == traffic.plan(DOCS, 45, 2**31 + 7)
    tok = serving.full_vocab_tokenizer(2048)
    texts = [traffic.PromptWriter(tok, serving.render, seed,
                                  n_words=128).write(200)
             for seed in (11, 11, 2**31 + 7)]
    assert texts[0] == texts[1] != texts[2]
    # exactly the planned length as the server will count it, and every
    # id of the model's vocabulary decodes to text
    assert all(len(tok.encode(serving.render(t))) == 200 for t in texts)
    assert all(tok.decode([i]) for i in range(4, 2048))


def test_open_loop_fills_the_window_at_exactly_the_rate():
    plan = traffic.plan(CHAT, 45, 11)
    assert len(plan) == int(CHAT["arrivals"]["rate_per_s"] * 45)
    due = [p.due_s for p in plan]
    assert due[0] == 0.0 and due == sorted(due) and due[-1] < 45.0


def test_a_seed_begins_the_same_cycle_at_another_point():
    def triples(seed):
        plan = traffic.plan(CHAT, 51, seed)
        ends = [p.due_s for p in plan[1:]] + [51.0]
        return [(round(b - p.due_s, 9), p.prompt_tokens, p.output_tokens)
                for p, b in zip(plan, ends)]

    a, b = triples(2147483659), triples(4294967311)
    assert a != b
    k = b.index(a[0])
    assert b[k:] + b[:k] == a       # same requests, same neighbours


def test_lengths_are_clipped_and_leave_room_for_the_output():
    for w in (CHAT, DOCS):
        plan = traffic.plan(w, 45, 3)
        pr, out = w["prompt_tokens"], w["output_tokens"]
        assert all(pr["min"] <= p.prompt_tokens <= pr["max"] for p in plan)
        assert all(1 <= p.output_tokens <= out["max"] for p in plan)
        assert all(p.prompt_tokens + p.output_tokens
                   <= w["max_total_tokens"] for p in plan)
    docs = traffic.plan(DOCS, 45, 3)
    assert all(p.due_s is None for p in docs)
    assert len(docs) == DOCS["pool"]
    c = DOCS["cycle"]       # the cycle of lengths repeats through the pool
    assert [(p.prompt_tokens, p.output_tokens) for p in docs[:c]] == [
        (p.prompt_tokens, p.output_tokens) for p in docs[c:2 * c]]


def test_gamma_gaps_have_the_asked_burstiness():
    rng = np.random.default_rng(0)
    g = traffic.draw_gaps(10.0, 3.0, 20000, 2000.0, rng)
    assert g.sum() == pytest.approx(2000.0)
    assert np.std(g) / np.mean(g) == pytest.approx(3.0, rel=0.1)


def test_a_request_is_timed_from_when_it_was_due_and_missing_counts():
    ok = client.Outcome(0, 100, 5, t_due=10.0, t_sent=10.4, t_first=10.9,
                        t_done=11.3, token_times=[10.9, 11.0, 11.1, 11.2,
                                                  11.3],
                        finish_reason="length", status=200)
    assert ok.ttft_s() == pytest.approx(0.9)      # from DUE, not from sent
    assert ok.tpot_s() == pytest.approx(0.1)
    lost = client.Outcome(1, 100, 5, t_due=12.0, t_sent=12.0, status=200,
                          error="deadline")
    window = serving.Window(10.0, 45.0, [ok, lost], [0.4, 0.0], 0, {})
    metrics, notes = serving.end_to_end(window, CHAT)
    assert notes["attempted"] == 2 and notes["failed"] == 1
    # the missing request enters the percentile as the window's length
    assert metrics["ttft_p95_ms"] == pytest.approx(45000.0)
    assert metrics["serve_tokens_per_s"] == pytest.approx(5 / 45.0)


def test_percentile_is_an_observed_value():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.median([1, 2, 3, 4]) == 2


def test_warm_plan_follows_the_length_ranges_not_the_seed():
    geom = dict(buckets=(16, 32, 64, 128, 256, 512), chunk=256,
                cache_len=1024, page=16, slots=16)
    chat = serving.warm_plan(CHAT, **geom)
    one_shot = [w for w in chat if all(n == 1 for _, n in w["group"])]
    # prompts of 16-256 tokens fall into five buckets; 1, 2, 4 together
    assert sorted(len(w["group"]) for w in one_shot) == sorted([1, 2, 4] * 5)
    assert {w["group"][0][0] for w in one_shot} == {16, 17, 33, 65, 129}
    docs = serving.warm_plan(DOCS, **geom)
    assert not [w for w in docs if all(n == 1 for _, n in w["group"])]
    assert docs[-1]["lead"][0] == 640 and docs[-1]["group"] == [(960, 2)]


def test_probes_take_the_paths_the_cells_prompts_take():
    assert serving.probe_lengths(CHAT, 256) == [160, 384]   # one shot, chunked
    assert serving.probe_lengths(DOCS, 256) == [800, 800]   # chunked only


@pytest.mark.parametrize("shape", [(128, 64), (7, 9)])
def test_reference_reads_packed_weights_as_the_program_writes_them(shape):
    """The reference decodes packed weights itself; at the commit that
    adds it, it agrees with the program's decoders to the last bit."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import packed
    from llm_in_practise_tpu.quant import int8, nf4

    w = 0.02 * jax.random.normal(jax.random.PRNGKey(3), shape, jnp.float32)
    q8 = int8.quantize(w)
    assert np.array_equal(packed.int8_to_f32(q8), int8.decode(q8, jnp.float32))
    q4 = nf4.quantize(w)
    assert q4.layout == ("kblock" if shape == (128, 64) else "flat")
    got = np.asarray(packed.nf4_to_f32(q4))
    assert np.array_equal(got, nf4.dequantize(q4, jnp.float32))
    assert np.abs(got - np.asarray(w)).max() < 0.02 * 0.5
