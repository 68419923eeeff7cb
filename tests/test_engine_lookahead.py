"""The engine issues step n+1 before it reads step n (PR 40).

A paged engine splits a device step into ISSUE (admit, plan, reserve,
build indices, dispatch: no token VALUE needed, the last tokens stay on
the device) and RETIRE (fetch, emit, finish, book), and runs issue(n+1)
before retire(n) wherever nothing forbids it. These tests hold the
run-ahead engine to THE SAME ENGINE with the run-ahead predicate patched
to "never" (``_ahead_blocker``, a private method: the program has no
option for it; one engine a scenario, so both passes run the same
compiled programs), which reads every program before it plans the next,
as the serial loop did:

- greedy tokens, ``n_generated`` and ``finish_reason`` are the serial
  engine's for a dense GQA, a DeepSeek-V3 and a MiMo-V2 toy engine, over
  a mix that crosses decode -> mixed -> decode, with a prompt admitted
  mid-run and budget finishes while a program is in flight;
- an EOS finish while a program is in flight: the row has already run in
  the next program; that token is discarded, never emitted, and the
  row's pages are not released while a program that writes them is
  unread (the pool's refcounts and ``test_paged_kv``'s churn invariant
  hold);
- every drain reason fires under its own name;
- seeded SAMPLED rows give the serial engine's streams where the rows are
  admitted together and none ends in EOS: one key is split an issued
  step, in issue order, either way. What differs where a row does end
  (in EOS, or at its budget): its slot is free one step later (it is
  read a step after it was issued), so a queued request is admitted a
  step later and draws its tokens from later keys; greedy requests do
  not depend on the key and keep their tokens.

Tiny widths on the CPU; every engine is driven by ``step()`` calls."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_in_practise_tpu.models import deepseek_v3 as dsv3
from llm_in_practise_tpu.models import mimo_v2 as mm
from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
from llm_in_practise_tpu.models.qwen3 import Qwen3, Qwen3Config
from llm_in_practise_tpu.models.sdar_moe import SDARMoE, sdar_moe_config
from llm_in_practise_tpu.serve import constrain
from llm_in_practise_tpu.serve.engine import (
    DRAIN_REASONS,
    InferenceEngine,
    SamplingParams,
)
from tests.thread_state_checks import (
    check_records,
    check_requests,
    spy_window_closes,
)

OPTS = dict(max_slots=4, cache_len=128, kv_layout="paged",
            chunked_prefill=16, cache_dtype=jnp.float32)


def _dense():
    cfg = Qwen3Config(vocab_size=512, hidden_size=64, intermediate_size=128,
                      n_layer=2, n_head=4, n_kv_head=2, head_dim=16,
                      max_seq_len=128, tie_word_embeddings=True,
                      compute_dtype="float32")
    model = Qwen3(cfg)
    return model, model.init_params(jax.random.PRNGKey(0)), {}


def _latent():
    cfg = dsv3.deepseek_v3_config(compute_dtype="float32", experts_held=8)
    return (dsv3.DeepSeekV3(cfg), dsv3.random_params(cfg, 3, jnp.float32),
            {})


def _hybrid():
    cfg = mm.mimo_v2_config(compute_dtype="float32", experts_held=4,
                            expert_offset=4)
    return (mm.MiMoV2(cfg), mm.random_params(cfg, 3, jnp.float32, std=0.2),
            {"kv_page_size": 8})


FAMILIES = {"dense-gqa": _dense, "deepseek-v3": _latent, "mimo-v2": _hybrid}


def engine(world, **kw):
    model, params, extra = world
    return InferenceEngine(model, params, **{**OPTS, **extra, **kw})


def prompts(world, lengths, seed=0):
    vocab = world[0].config.vocab_size
    rng = np.random.default_rng(seed)
    return [rng.integers(4, vocab, n).tolist() for n in lengths]


def drain(eng, limit=2000):
    for _ in range(limit):
        if not eng.step():
            return
    raise AssertionError("the engine never went idle")


def mixed_run(eng, world, sampling=None):
    """A 40-token prompt chunks and decodes; a 70-token one arrives once
    the first decodes and chunks beside it (fused mixed steps both ways:
    decode -> mixed -> decode); a 9-token one is admitted mid-run, and
    with it a 23-token and an 18-token one of budgets 2 and 1 (the one
    ends with the decode issued before its first token was read, the
    other with its first token; the fifth request waits for a slot).
    Budgets of 12, 6 and 3: each ends while a later program is in
    flight."""
    a, b, c, d, e = prompts(world, (40, 70, 9, 23, 18))
    sp = sampling or (lambda n: SamplingParams(greedy=True, max_tokens=n))
    reqs = [eng.submit(a, sp(12))]
    while reqs[0].n_generated == 0:
        assert eng.step()
    reqs.append(eng.submit(b, sp(6)))
    for _ in range(3):
        eng.step()
    reqs += [eng.submit(c, sp(3)), eng.submit(d, sp(2)),
             eng.submit(e, sp(1))]
    drain(eng)
    return reqs


def outcome(reqs):
    return [(r.result(), r.n_generated, r.finish_reason) for r in reqs]


def both_ways(eng, run):
    """``run(eng)`` with the engine running ahead, then again on THE SAME
    ENGINE (its compiled programs, its pool) with the run-ahead predicate
    patched to "never". Returns each pass's outcome and what the recorder
    and the compile meter read for it."""
    passes = {}
    closed = spy_window_closes(eng)
    for name in ("ahead", "serial"):
        if name == "serial":
            eng._ahead_blocker = lambda: "never"
        seen = len(eng.steptrace.records(limit=10 ** 6))
        before = dict(eng.steptrace.snapshot())
        compiles = eng.compile_meter.compile_events
        done = len(eng.finished)
        result = outcome(run(eng))
        after = eng.steptrace.snapshot()
        drains = {k: v - before["step_drains"].get(k, 0)
                  for k, v in after["step_drains"].items()}
        passes[name] = dict(
            outcome=result,
            records=eng.steptrace.records(limit=10 ** 6)[seen:],
            steps=after["steps"] - before["steps"],
            steps_ahead=after["steps_ahead"] - before["steps_ahead"],
            drains={k: v for k, v in drains.items() if v},
            discarded=(after["tokens_discarded"]
                       - before["tokens_discarded"]),
            compiles=eng.compile_meter.compile_events - compiles,
            finished=list(eng.finished)[done:], closed=closed)
    return passes


@pytest.fixture(scope="module", params=list(FAMILIES))
def pair(request):
    """The same scenario run ahead and serially, one engine a family."""
    world = FAMILIES[request.param]()
    eng = engine(world)
    return eng, both_ways(eng, lambda e: mixed_run(e, world))


# ------------------------------------------------------------ greedy parity


def test_greedy_streams_are_the_serial_engines(pair):
    eng, passes = pair
    got, want = passes["ahead"]["outcome"], passes["serial"]["outcome"]
    assert got == want
    assert [len(t) for t, _, _ in got] == [12, 6, 3, 2, 1]
    assert all(reason == "length" for _, _, reason in got)
    assert eng.mixed_blocks >= 6
    eng.paged.pool.check_leaks(0)


def test_most_steps_run_ahead_and_the_serial_engine_never_does(pair):
    _, passes = pair
    ahead, serial = passes["ahead"], passes["serial"]
    issued = [r for r in ahead["records"] if r["dispatches"]]
    assert ahead["steps_ahead"] >= 0.6 * len(issued)
    # across the fused mixed step in both directions
    kinds = [(r["ahead"], {n.split(":")[1] for n, _, _ in r["segments"]
                           if n.startswith("issue:")})
             for r in ahead["records"]]
    assert (True, {"mixed"}) in kinds and (True, {"decode"}) in kinds
    assert serial["steps_ahead"] == 0 and serial["drains"]["never"] > 0
    assert ahead["discarded"] == 0                # no EOS in this vocabulary


def test_every_step_says_ahead_or_why_not(pair):
    for run in pair[1].values():
        for r in run["records"]:
            assert r["ahead"] != (r["drain"] is not None), r
            assert r["drain"] is None or r["drain"] in DRAIN_REASONS + (
                "never",)
        assert run["steps_ahead"] + sum(run["drains"].values()) \
            == run["steps"] == len(run["records"])


def test_step_records_keep_their_contract(pair):
    """issue + wait = device <= wall, the activities fill the rest, and
    the window lane's segments tile (none overlaps the next)."""
    for r in pair[1]["ahead"]["records"]:
        assert r["issue_s"] + r["wait_s"] == pytest.approx(r["device_s"])
        assert r["device_s"] <= r["wall_s"] + 1e-9
        assert sum(r["activities"].values()) + r["device_s"] == \
            pytest.approx(r["wall_s"], abs=1e-6)
        lane = sorted((t0, t1) for n, t0, t1 in r["segments"]
                      if n.startswith(("issue:", "wait:")))
        for (_, end), (begin, _) in zip(lane, lane[1:]):
            assert begin >= end - 1e-9
        assert sum(t1 - t0 for t0, t1 in lane) == pytest.approx(
            r["device_s"], abs=1e-6)


def test_no_program_gains_a_second_cache_entry(pair):
    """The last-token plane has one form whoever built it (a program
    that ran ahead, a program read at once, the host's ``fix``): the
    serial pass finds every program it needs already compiled."""
    _, passes = pair
    assert passes["ahead"]["compiles"] > 0
    assert passes["serial"]["compiles"] == 0


def test_request_windows_tile(pair):
    """Windows are booked to a request from the later of their begin and
    the previous window's end: no more than the request's wall in all."""
    finished = pair[1]["ahead"]["finished"]
    assert len(finished) == 5
    for req in finished:
        booked = sum(v for k, v in req.cp.items()
                     if k in ("prefill_dispatch", "decode_dispatch",
                              "prefill_stall", "decode_interleave"))
        wall = req.finish_time - req.submit_time
        assert booked <= wall + 1e-6
        assert req.cp["host_gap"] >= 0.0
        assert req.cp.get("dispatch_issue", 0.0) <= booked + 1e-9


def test_thread_states_partition_every_step_and_every_request(pair):
    """PR 41: a record's wall by what the engine thread did as a thread
    (cpu + blocked + stalled = wall, whatever a running program covers),
    one ``fetch:`` segment a window closed, in the record that read it
    and inside that window's ``wait:`` lane segment; a record that ran
    ahead reads the program the step BEFORE it issued; every finished
    request carries the four ``engine_*`` overlays, they sum,
    and ``host_gap`` is the parent's residual."""
    _, passes = pair
    for name, run in passes.items():
        check_records(run["records"], run["closed"])
        check_requests(run["finished"])
        assert len(run["finished"]) == 5
        read = [r for r in run["records"] if r["read_seq"] is not None]
        assert sum(r["dispatches"] for r in run["records"]) == sum(
            n.startswith("fetch:") for r in run["records"]
            for n, _, _ in r["segments"])
        if name == "ahead":
            # (the serial engine reads at its next step's begin too, but
            # before it issues: under no other program)
            behind = [r for r in read if r["read_seq"] == r["seq"] - 1]
            assert len(behind) >= 0.6 * len(read)
            assert sum(r["ahead"] for r in behind) >= 0.6 * len(read)
    # the time of a request's steps is the time of the records it held a
    # slot in: no more than the records' in all
    run = passes["ahead"]
    assert sum(r.cp["engine_wall"] for r in run["finished"]) <= (
        4 * sum(r["wall_s"] for r in run["records"]) + 1e-6)


# -------------------------------------------------- EOS with a program unread


@pytest.fixture(scope="module")
def dense():
    return _dense()


def test_eos_while_a_program_is_in_flight(dense):
    """A token the first request emits mid-stream becomes the engine's
    EOS: it ends that row while the next program is in flight."""
    eng = engine(dense)
    eng._ahead_blocker = lambda: "never"
    eos = outcome(mixed_run(eng, dense))[0][0][5]
    del eng._ahead_blocker
    eng.eos_id = eos
    released = []
    inner = eng.paged.release_slot

    def release(slot):
        # no unread program may decode (write the pages of) this row
        unread = [f for f in (eng._flight, eng._ahead) if f is not None]
        assert not any(f.decodes(slot) for f in unread)
        released.append(slot)
        inner(slot)

    eng.paged.release_slot = release
    passes = both_ways(eng, lambda e: mixed_run(e, dense))
    got = passes["ahead"]["outcome"]
    assert got == passes["serial"]["outcome"]
    tokens, n_generated, reason = got[0]
    assert reason == "stop" and eos not in tokens
    assert n_generated == len(tokens) < 12
    assert len(released) == 10
    assert passes["ahead"]["discarded"] >= 1      # the row ran once more
    assert passes["serial"]["discarded"] == 0
    eng.paged.pool.check_leaks(0)


def _churn(eng, world):
    rng = np.random.RandomState(0)
    handles = []
    for wave in range(4):
        for p in prompts(world, rng.randint(8, 60, size=5), seed=wave):
            handles.append(eng.submit(p, SamplingParams(
                greedy=True, max_tokens=int(rng.randint(1, 24)))))
        drain(eng)
    eng.prefix_cache.clear()        # the next pass starts as cold
    eng.paged.pool.check_leaks(0)
    return handles


def test_churn_with_eos_and_a_small_pool_leaks_nothing(dense):
    """``test_paged_kv``'s churn invariant under lookahead: random
    prompts and budgets, EOS finishes, preemptions under a pool of 12
    pages, a prefix index: at the end only the index's references remain
    (none once it is cleared), and every stream is the serial engine's."""
    eng = engine(dense, kv_pool_tokens=192, prefix_cache=True,
                 chunked_prefill=None)
    eng._ahead_blocker = lambda: "never"
    late = [t for tokens, _, _ in outcome(_churn(eng, dense))
            for t in tokens[2:]]
    del eng._ahead_blocker
    eng.eos_id = max(set(late), key=late.count)   # ends rows mid-stream
    before = eng.preemptions
    passes = both_ways(eng, lambda e: _churn(e, dense))
    got = passes["ahead"]["outcome"]
    assert got == passes["serial"]["outcome"]
    assert {r for _, _, r in got} >= {"stop", "length"}
    assert eng.preemptions > before
    assert passes["ahead"]["drains"].get("preempt", 0) > 0
    assert passes["ahead"]["discarded"] > 0


# ------------------------------------------------------------- drain reasons


def _drains(eng):
    return eng.steptrace.snapshot()["step_drains"]


def test_one_shot_admission_and_two_program_steps_drain(dense):
    """A short prompt admitted mid-run prefills in one shot (a program
    that is read at once); without the fused step a chunk and a decode
    block are two programs a step."""
    eng = engine(dense, mixed_step=False)
    mixed_run(eng, dense)
    assert _drains(eng)["oneshot_prefill"] >= 1
    assert _drains(eng)["two_dispatch"] >= 1
    assert eng.steptrace.snapshot()["steps_ahead"] > 0    # decode-only runs


def test_the_contiguous_layout_never_runs_ahead(dense):
    eng = engine(dense, kv_layout="contiguous")
    reqs = mixed_run(eng, dense)
    snap = eng.steptrace.snapshot()
    assert snap["steps_ahead"] == 0 and _drains(eng)["contiguous"] > 0
    assert [len(t) for t, _, _ in outcome(reqs)] == [12, 6, 3, 2, 1]


def test_a_speculative_engine_drains_while_a_round_can_run(dense):
    eng = engine(dense, speculative_k=3)
    (p,) = prompts(dense, (24,))
    greedy = eng.submit(p, SamplingParams(greedy=True, max_tokens=8))
    drain(eng)
    assert _drains(eng)["speculative"] > 0
    assert eng.steptrace.snapshot()["steps_ahead"] == 0
    # sampled rows: no round can run, and the plain decode runs ahead
    eng.submit(p, SamplingParams(temperature=0.8, max_tokens=8))
    drain(eng)
    assert eng.steptrace.snapshot()["steps_ahead"] > 0
    assert len(greedy.result()) == 8


@pytest.mark.parametrize("rule", ["low_confidence_dynamic",
                                  "low_confidence_static"])
def test_a_block_diffusion_engine_drains(rule):
    """While a ready row decodes under the dynamic rule (how many
    positions a pass reveals is a device value); under the static rule
    its passes run ahead (``tests/test_block_lookahead.py``)."""
    model = SDARMoE(sdar_moe_config())
    eng = InferenceEngine(model, model.init_params(jax.random.PRNGKey(0)),
                          **{**OPTS, "chunked_prefill": None})
    req = eng.submit([5, 6, 7, 8, 9], SamplingParams(
        greedy=True, max_tokens=8, remasking=rule))
    drain(eng)
    assert len(req.result()) == 8
    ahead = eng.steptrace.snapshot()["steps_ahead"]
    if rule == "low_confidence_dynamic":
        assert _drains(eng)["block_dynamic"] > 0 and ahead == 0
    else:
        assert "block_dynamic" not in _drains(eng) and ahead > 0


VOCAB = 128


class _CharTok:
    def encode(self, text):
        return [min(ord(c), VOCAB - 1) for c in text]

    def decode(self, ids):
        return "".join(chr(int(i) % VOCAB) for i in ids)


def test_a_grammar_row_drains_and_its_first_token_is_the_hosts():
    cfg = GPTConfig(vocab_size=VOCAB, seq_len=128, n_layer=2, n_head=2,
                    embed_dim=32, dropout=0.0, pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    tok = _CharTok()
    auto = constrain.compile_request_constraint(
        response_format={"type": "json_schema", "json_schema": {"schema": {
            "type": "object", "properties": {"ok": {"type": "boolean"}},
            "required": ["ok"]}}},
        vocab=constrain.vocab_strings(tok, VOCAB), eos_id=None)
    eng = engine((model, params, {}))

    def run(eng):
        free = eng.submit(tok.encode("a free row decodes beside it"),
                          SamplingParams(greedy=True, max_tokens=30))
        while free.n_generated == 0:
            assert eng.step()
        bound = eng.submit(tok.encode("emit json now: " * 3),
                           SamplingParams(greedy=True, max_tokens=40,
                                          constraint=auto))
        drain(eng)
        return [free, bound]

    passes = both_ways(eng, run)
    ahead = passes["ahead"]
    assert ahead["outcome"] == passes["serial"]["outcome"]
    # the chunk program that ends the bound prompt is read at once (the
    # host samples under the start state's mask), and while the bound
    # row is ready nothing runs ahead
    assert ahead["drains"]["host_first_token"] >= 1
    assert ahead["drains"]["grammar"] >= 1
    assert eng.first_tokens["host"] == 2          # one a pass
    assert ahead["steps_ahead"] > 0
    assert tok.decode(ahead["outcome"][1][0]).replace(" ", "") in (
        '{"ok":true}', '{"ok":false}')


# ------------------------------------------------------------- sampled rows


def test_sampled_streams_are_the_serial_engines_without_eos(dense):
    """Rows admitted together, none ending in EOS: one key an issued
    step in issue order on both engines, so the sampled streams agree
    (the module's docstring says what differs where a row ends)."""
    ps = prompts(dense, (20, 33, 47))
    eng = engine(dense)

    def run(eng):
        eng.rng = jax.random.PRNGKey(7)
        reqs = [eng.submit(p, SamplingParams(
            temperature=0.9, top_k=40, top_p=0.95, max_tokens=10))
            for p in ps]
        drain(eng)
        return reqs

    passes = both_ways(eng, run)
    assert passes["ahead"]["outcome"] == passes["serial"]["outcome"]
    assert passes["ahead"]["steps_ahead"] > 0
    assert len({tuple(t) for t, _, _ in passes["ahead"]["outcome"]}) == 3
