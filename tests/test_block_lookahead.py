"""A block pass is issued before the pass before it is read (PR 42).

A block-diffusion engine whose ready rows all decode under the static
reveal rule runs its passes one ahead (serve/block_step.py): the block
plane stays on the device, the host keeps the schedule by COUNTS of
revealed positions, and a pass's values (reveal log, a committed block's
stream, EOS, the capture, the routing load) are read one pass late.
These tests hold that engine to THE SAME ENGINE with ``_ahead_blocker``
patched to "never" (``test_engine_lookahead.both_ways``), which reads
every pass before it plans the next, as the serial step did:

- greedy streams, ``n_generated``, ``finish_reason``, every request's
  ``reveal_log`` and every captured pass (block, pass, commit, logits,
  experts) are the serial engine's, for rows at different phases, prompts
  with a remainder, a chunked prompt admitted mid-run, a request that
  waits for a slot, ``max_tokens`` cuts inside a block;
- EOS inside a committed block while the next pass is in flight: nothing
  is emitted after it, the pass the row ran meanwhile is discarded and
  counted, its pages are not released under an unread pass, and the slot
  and the pool end whole;
- most passes run ahead, every step says ``ahead`` or why not, one fetch
  a pass, the block program keeps ONE cache entry, the counters add up;
- a row under ``low_confidence_dynamic`` drains (``block_dynamic``) while
  it is ready, and the static rows beside it run ahead once it is gone;
- seeded SAMPLED rows admitted together give the serial streams: one key
  is split an issued pass, in issue order, either way.

Tiny widths on the CPU, float32 weights whose argmaxes are no ties."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_in_practise_tpu.models.sdar_moe import (
    SDARMoE, random_params, sdar_moe_config,
)
from llm_in_practise_tpu.serve.engine import (
    DRAIN_REASONS,
    InferenceEngine,
    SamplingParams,
)
from tests.test_engine_lookahead import both_ways, drain, outcome
from tests.thread_state_checks import check_records, check_requests

CFG = sdar_moe_config(compute_dtype="float32")
B = CFG.block_length
GREEDY = dict(temperature=0.0, greedy=True)


@pytest.fixture(scope="module")
def world():
    return SDARMoE(CFG), random_params(CFG, 3, jnp.float32, std=0.2)


def make_engine(world, **kw):
    model, params = world
    kw = {"max_slots": 4, "cache_len": 64, "kv_layout": "paged",
          "cache_dtype": jnp.float32, "chunked_prefill": 16,
          "prefill_buckets": (8, 16, 32), **kw}
    return InferenceEngine(model, params, **kw)


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size - 1, n).tolist()


def greedy(n, **kw):
    return SamplingParams(max_tokens=n, **GREEDY, **kw)


def mixed_run(eng, sampling=greedy):
    """A 6-token prompt (remainder 2) decodes; a 13-token one (remainder
    1) joins two passes later, so the rows are at different phases, with a
    budget of 6 that ends inside its second block; once both decode, a
    22-token prompt (a chunk of 16, then 4, remainder 2) is admitted
    mid-run with a whole-block prompt of 8, and a fifth request waits for
    a slot. Budgets of 11, 6, 9, 8 and 5: three of them end inside a
    block, each while a later pass is in flight."""
    reqs = [eng.submit(prompt_of(6, seed=6), sampling(11))]
    eng.step()
    eng.step()
    reqs.append(eng.submit(prompt_of(13, seed=13), sampling(6)))
    for _ in range(3):
        eng.step()
    reqs += [eng.submit(prompt_of(22, seed=22), sampling(9)),
             eng.submit(prompt_of(8, seed=8), sampling(8)),
             eng.submit(prompt_of(9, seed=9), sampling(5))]
    drain(eng)
    return reqs


def twice(eng, scenario):
    """``both_ways`` over ``scenario`` with every pass captured; each
    pass's entry gains what the block step kept of it: the requests'
    reveal logs and pass counts, the captured passes keyed by (request,
    block, pass), and what the block counters moved by."""
    kept = []

    def run(e):
        e.block.capture = []
        before = e.block.counters()
        reqs = scenario(e)
        index = {r.uid: i for i, r in enumerate(reqs)}
        kept.append(dict(
            logs=[list(r.reveal_log) for r in reqs],
            row_passes=[r.block_passes for r in reqs],
            captured={(index[c["uid"]], c["block"], c["pass"]): c
                      for c in e.block.capture},
            n_captured=len(e.block.capture),
            counters={k: v - before[k]
                      for k, v in e.block.counters().items()}))
        e.block.capture = None
        return reqs

    passes = both_ways(eng, run)
    for name, extra in zip(("ahead", "serial"), kept):
        passes[name].update(extra)
    return passes


@pytest.fixture(scope="module")
def pair(world):
    eng = make_engine(world)
    return eng, twice(eng, mixed_run)


# ------------------------------------------------------------ greedy parity


def test_greedy_streams_are_the_serial_engines(pair):
    eng, passes = pair
    got, want = passes["ahead"]["outcome"], passes["serial"]["outcome"]
    assert got == want
    assert [(n, why) for _, n, why in got] == [
        (11, "length"), (6, "length"), (9, "length"), (8, "length"),
        (5, "length")]
    assert all(len(t) == n for t, n, _ in got)
    assert all(r is None for r in eng.slot_req)
    eng.paged.pool.check_leaks(0)


def test_reveal_logs_and_captured_passes_are_the_serial_engines(pair):
    _, passes = pair
    ahead, serial = passes["ahead"], passes["serial"]
    assert ahead["logs"] == serial["logs"] and all(ahead["logs"])
    assert ahead["row_passes"] == serial["row_passes"]
    # a first block that opens with r prompt tokens revealed costs 5 - r
    assert [p for (i, b, p) in sorted(ahead["captured"])
            if i == 0 and b == 0] == [0, 1, 2]
    assert sorted(ahead["captured"]) == sorted(serial["captured"])
    assert ahead["n_captured"] == len(ahead["captured"]) == sum(
        ahead["row_passes"])
    for key, got in ahead["captured"].items():
        want = serial["captured"][key]
        assert got["commit"] == want["commit"], key
        np.testing.assert_allclose(got["logits"], want["logits"],
                                   rtol=1e-5, atol=1e-6)
        assert np.array_equal(got["experts"], want["experts"]), key


def test_most_passes_run_ahead_and_the_serial_engine_never_does(pair):
    _, passes = pair
    ahead, serial = passes["ahead"], passes["serial"]
    issued = [r for r in ahead["records"] if r["dispatches"]]
    assert ahead["steps_ahead"] >= 0.6 * len(issued)
    # what does not: the admissions, and a chunk beside a pass
    assert set(ahead["drains"]) <= {"idle", "oneshot_prefill",
                                    "two_dispatch"}
    assert ahead["drains"]["oneshot_prefill"] >= 1
    assert ahead["drains"]["two_dispatch"] >= 1
    assert serial["steps_ahead"] == 0 and serial["drains"]["never"] > 0
    assert ahead["discarded"] == 0          # no EOS in this engine


def test_every_step_says_ahead_or_why_not(pair):
    for run in pair[1].values():
        for r in run["records"]:
            assert r["ahead"] != (r["drain"] is not None), r
            assert r["drain"] is None or r["drain"] in DRAIN_REASONS + (
                "never",)
        assert run["steps_ahead"] + sum(run["drains"].values()) \
            == run["steps"] == len(run["records"])


def test_a_pass_is_read_in_one_fetch_a_step_after_it_was_issued(pair):
    """One ``fetch:decode`` a pass, the capture's logits included, in
    the record that read it; a pass that ran ahead is read under the
    pass issued after it; the thread states partition every record and
    every request."""
    _, passes = pair
    for name, run in passes.items():
        check_records(run["records"], run["closed"])
        check_requests(run["finished"])
        read = [r for r in run["records"] if r["block_rows"]]
        assert len(read) == run["counters"]["block_passes"]
        for r in read:
            assert [n for n, _, _ in r["segments"]].count(
                "fetch:decode") == 1
        if name == "ahead":
            behind = [r for r in read
                      if r["ahead"] and r["read_seq"] == r["seq"] - 1]
            assert len(behind) >= 0.6 * len(read)


def test_the_block_program_has_one_cache_entry_a_view_width(pair):
    """The plane has one form whoever built it (the zeros of a fresh
    engine, a pass that ran ahead, a pass read at once) and ``fix`` is
    always passed: one executable a pow2 view width (16, 32, 64 here), as
    before, and the serial pass builds nothing."""
    eng, passes = pair
    assert passes["ahead"]["compiles"] > 0
    assert passes["serial"]["compiles"] == 0
    jitted = eng.block._pg_block
    while not hasattr(jitted, "_cache_size"):
        jitted = jitted.__wrapped__
    assert jitted._cache_size() <= 3


def test_counters_add_up(pair):
    _, passes = pair
    for run in passes.values():
        c = run["counters"]
        assert c["block_row_passes"] == sum(run["row_passes"])
        assert c["block_row_passes_discarded"] == 0
        assert c["block_tokens_committed"] == 11 + 6 + 9 + 8 + 5
        assert c["block_tokens_revealed"] == sum(
            len(log) for log in run["logs"])
        records = run["records"]
        assert sum(r["block_rows"] for r in records) == c["block_row_passes"]
        assert sum(r["block_commits"] for r in records) == \
            c["blocks_committed"]
        assert sum(r["tokens_committed"] for r in records) == 39
    assert passes["ahead"]["counters"] == {
        **passes["serial"]["counters"],
        # idle rows route too, and a slot is idle a pass later here
        **{k: v for k, v in passes["ahead"]["counters"].items()
           if k.startswith("moe_")}}


# ----------------------------------------------------- EOS with a pass unread


def test_eos_inside_a_block_while_the_next_pass_is_in_flight(world):
    """A token some request streams from INSIDE a block after its first
    becomes the engine's EOS: that row's commit pass is read after the
    next pass, in which the row already runs, was issued."""
    eng = make_engine(world)
    eng._ahead_blocker = lambda: "never"
    streams = [t for t, _, _ in outcome(mixed_run(eng))]
    del eng._ahead_blocker
    # a request's outputs are block-aligned at B - (prompt mod B) + k B
    first = [B - n % B for n in (6, 13, 22, 8, 9)]
    who, at = next(
        (i, j) for i, t in enumerate(streams) for j in range(first[i], len(t))
        if (j - first[i]) % B and t[j] not in t[:j])
    tokens = streams[who]
    eng.eos_id = tokens[at]
    released = []
    inner = eng.paged.release_slot

    def release(slot):
        # no unread pass may hold this row
        unread = [f for f in (eng._flight, eng._ahead) if f is not None]
        assert not any(f.decodes(slot) for f in unread)
        released.append(slot)
        inner(slot)

    eng.paged.release_slot = release
    passes = twice(eng, mixed_run)
    ahead, serial = passes["ahead"], passes["serial"]
    assert ahead["outcome"] == serial["outcome"]
    assert ahead["logs"] == serial["logs"]
    got, n_generated, reason = ahead["outcome"][who]
    assert reason == "stop" and got == tokens[:at]       # nothing after it
    assert n_generated == at
    # the row ran once more, for nobody: counted, and not as an advance
    assert ahead["discarded"] >= 1 and serial["discarded"] == 0
    assert ahead["counters"]["block_row_passes_discarded"] >= 1
    assert serial["counters"]["block_row_passes_discarded"] == 0
    assert ahead["counters"]["block_row_passes"] == sum(
        ahead["row_passes"]) == serial["counters"]["block_row_passes"]
    assert ahead["n_captured"] == serial["n_captured"]
    assert len(released) == 10
    assert all(r is None for r in eng.slot_req) and not eng._zombies
    eng.paged.pool.check_leaks(0)


# ------------------------------------------------------------ the dynamic rule


def test_a_dynamic_row_drains_and_static_rows_run_ahead_without_it(world):
    eng = make_engine(world)
    ahead_while_dynamic = []

    def run(e):
        before = e.steptrace.snapshot()["steps_ahead"]
        reqs = [e.submit(prompt_of(10, seed=2), greedy(
                    6, remasking="low_confidence_dynamic",
                    confidence_threshold=0.02)),
                e.submit(prompt_of(7, seed=7), greedy(22))]
        while reqs[0].finish_time is None:
            assert e.step()
        ahead_while_dynamic.append(
            e.steptrace.snapshot()["steps_ahead"] - before)
        drain(e)
        return reqs

    passes = twice(eng, run)
    ahead, serial = passes["ahead"], passes["serial"]
    assert ahead["outcome"] == serial["outcome"]
    assert ahead["logs"] == serial["logs"]
    assert [n for _, n, _ in ahead["outcome"]] == [6, 22]
    # the threshold revealed several positions in one pass: fewer passes
    # than the static rule's five a block
    assert ahead["row_passes"][0] < 2 * (CFG.denoising_steps + 1)
    assert ahead["drains"]["block_dynamic"] >= 2
    # while the dynamic row was ready no pass was issued ahead (the pass
    # after its last commit, which it is not in, may be), and the static
    # row ran ahead once it was gone
    assert ahead_while_dynamic[0] <= 1 < ahead["steps_ahead"]
    eng.paged.pool.check_leaks(0)


# ------------------------------------------------------------- sampled rows


def test_sampled_streams_are_the_serial_engines(world):
    eng = make_engine(world)

    def run(e):
        e.rng = jax.random.PRNGKey(7)
        reqs = [e.submit(prompt_of(n, seed=n), SamplingParams(
            temperature=0.9, top_k=40, top_p=0.95, max_tokens=10))
            for n in (7, 12, 18)]
        drain(e)
        return reqs

    passes = twice(eng, run)
    assert passes["ahead"]["outcome"] == passes["serial"]["outcome"]
    assert passes["ahead"]["logs"] == passes["serial"]["logs"]
    assert passes["ahead"]["steps_ahead"] > 0
    assert len({tuple(t) for t, _, _ in passes["ahead"]["outcome"]}) == 3
